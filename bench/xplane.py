"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, device time by XLA module and operation, and
the longest idle gaps with what the host was doing in each.

``events(path)`` flattens the trace into device operations, program
(XLA module) executions and host spans; ``Summary`` holds them and
answers the readers' questions.  On a TPU, operations are the events of
the ``XLA Ops`` line of each ``/device:TPU:k`` plane, named by their HLO
text (``%fusion.52 = u32[...] fusion(...)``; ``Op.name`` keeps the part
before `` = ``, ``Op.text`` the whole), and modules are the events of its
``XLA Modules`` line (``jit_insert_edges(<fingerprint>)``; ``Module.name``
drops the fingerprint).  Operations nest (a ``while`` spans its body), so
device time is taken from modules, or as a union of operation intervals,
never as a sum over operations.  A trace without device operations is an
error, unless the caller asks for ``host_ops`` (a CPU run, in the tests):
then the operations are the host events that name an ``hlo_module``, and
each module's time is the union of its operations.
"""
from __future__ import annotations

import glob
import os
import re
import warnings
from typing import Iterable, List, NamedTuple, Optional

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans that enclose whole phases, never what the host did in a gap
ENCLOSING = ("bench.window",)


class Op(NamedTuple):
    device: str
    module: str
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""


class Module(NamedTuple):
    device: str
    name: str
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    thread: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _stats(ev) -> dict:
    with warnings.catch_warnings():  # jaxlib's stat type warns as it is read
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            return dict(ev.stats)
        except Exception:  # noqa: BLE001 - a stat the reader cannot decode
            return {}


def _module_name(event_name: str) -> str:
    return event_name.split("(")[0]


def events(path: str, host_ops: bool = False):
    """(device ops, modules, host spans) of one trace file; raises if it
    holds no device operation, unless ``host_ops`` allows CPU events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Module] = []
    spans: List[Span] = []
    cpu_ops: List[Op] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            mods = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                   _module_name(e.name)) for e in line.events)
            modules += [Module(plane.name, n, a, b - a) for a, b, n in mods]
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(plane.name, _containing(mods, e.start_ns),
                                  e.name.split(" = ")[0], e.start_ns, e.duration_ns, e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    st = _stats(e)
                    if "hlo_module" in st:
                        name = str(st.get("hlo_op", e.name))
                        cpu_ops.append(Op(plane.name, str(st["hlo_module"]), name,
                                           e.start_ns, e.duration_ns, name))
                    else:
                        spans.append(Span(line.name, e.name, e.start_ns, e.duration_ns))
    if not ops:
        if not (host_ops and cpu_ops):
            raise ValueError(f"no device operation in the trace {path}")
        ops = cpu_ops
    return ops, modules, spans


def _containing(modules, t: float) -> str:
    import bisect

    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return "?"


def union_ns(intervals: Iterable) -> list:
    """Sorted, merged (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Summary:
    """Device operations, module executions and host spans of one traced
    window.  ``window_ns`` is (start, end) of the traced window on the
    trace's clock: the first and last host span or device operation."""

    def __init__(self, ops: List[Op], spans: List[Span], modules: Optional[List[Module]] = None):
        self.ops = ops
        self.spans = spans
        self.modules = list(modules or [])
        if not self.modules:  # no module events (CPU): one per module, its ops' union
            by = {}
            for o in ops:
                by.setdefault((o.device, o.module), []).append((o.start_ns, o.start_ns + o.dur_ns))
            for (dev, name), iv in by.items():
                for a, b in union_ns(iv):
                    self.modules.append(Module(dev, name, a, b - a))
        ends = [o.start_ns + o.dur_ns for o in ops] + [s.start_ns + s.dur_ns for s in spans]
        starts = [o.start_ns for o in ops] + [s.start_ns for s in spans]
        self.window_ns = (min(starts), max(ends)) if starts else (0.0, 0.0)
        self.devices = sorted({o.device for o in ops})

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_ns_per_device(self) -> dict:
        out = {}
        for d in self.devices:
            iv = union_ns((o.start_ns, o.start_ns + o.dur_ns) for o in self.ops if o.device == d)
            out[d] = sum(e - s for s, e in iv)
        return out

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = self.busy_ns_per_device()
        return (sum(per.values()) / len(per)) * 1e-9 if per else 0.0

    @staticmethod
    def _match(patterns, text: str) -> bool:
        return patterns is None or any(re.search(p, text) for p in patterns)

    def matching(self, modules: Optional[list] = None, names: Optional[list] = None) -> list:
        """Operations whose module matches one of ``modules`` and whose
        name matches one of ``names`` (regular expressions; None: any)."""
        return [o for o in self.ops
                if self._match(modules, o.module) and self._match(names, o.name)]

    def module_runs(self, modules: Optional[list] = None, exclude: Optional[list] = None) -> list:
        """Module executions whose name matches one of ``modules`` (None:
        any) and none of ``exclude``."""
        return [m for m in self.modules if self._match(modules, m.name)
                and not (exclude is not None and self._match(exclude, m.name))]

    @staticmethod
    def seconds(events: list) -> float:
        """Summed durations of ``events`` (module runs, or operations
        that do not nest, such as kernels)."""
        return sum(e.dur_ns for e in events) * 1e-9

    def device_ops(self, top: int = 10) -> list:
        """[name, seconds] of the programs that took most device time
        (module runs summed by module name), averaged over the devices."""
        acc: dict = {}
        for m in self.modules:
            acc[m.name] = acc.get(m.name, 0.0) + m.dur_ns * 1e-9
        k = max(len(self.devices), 1)
        return [[n, s / k] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list:
        """[label, seconds] of the longest device-idle gaps of the first
        device in the window, each labelled by the host span that overlaps
        it most (``bench.*`` spans first), or "no host span"."""
        if not self.devices:
            return []
        busy = union_ns((o.start_ns, o.start_ns + o.dur_ns)
                        for o in self.ops if o.device == self.devices[0])
        lo, hi = self.window_ns
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = [s for s in self.spans if s.name not in ENCLOSING]
        out = []
        for a, b in gaps[:top]:
            best, best_ov = "no host span", 0.0
            for s in inner:
                ov = min(b, s.start_ns + s.dur_ns) - max(a, s.start_ns)
                if ov <= 0:
                    continue
                ov *= 2.0 if s.name.startswith("bench.") else 1.0
                if ov > best_ov:
                    best, best_ov = s.name, ov
            out.append([best, (b - a) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}


def summarize(path: str, host_ops: bool = False) -> Summary:
    ops, modules, spans = events(path, host_ops)
    return Summary(ops, spans, modules)
