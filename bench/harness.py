"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` is what ``run.py`` calls once it has found a TPU; tests call
it on the CPU at a tiny size.  Everything that differs between cells is
data found by name (module docstring of ``bench``):

* the cell: an entry of ``BENCHMARK.json``'s ``workloads``;
* its deployment: ``configs/<config>.json`` (graph generator and scale,
  pool capacity, service settings);
* its traffic: ``traffic/<traffic>.json``, whose ``generator`` names the
  module under ``traffic/`` that plans, warms and drives the window;
* its check limits: ``limits/<cell>.json``;
* each metric: ``metrics/<metric>.py``, whose ``read(record)`` returns a
  number or None (None: nothing to read, and the metric is left out).
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RESULT_WAIT_S = 60.0  # how long a query or write due in the window may come late


def log(tag: str, **kv) -> None:
    """One line of progress on standard error."""
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{tag}] {body}", file=sys.stderr, flush=True)


# -- finding the pieces by name ----------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_piece_" + "".join(c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic,
    limits and generator module, all read from ``bench_dir``."""

    def __init__(self, name: str, bench_json: Path = ROOT / "BENCHMARK.json",
                 bench_dir: Path = BENCH):
        self.bench = load_json(bench_json)
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in {bench_json}")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.bench_dir = Path(bench_dir)
        self.config = load_json(self.bench_dir / "configs" / f"{self.entry['config']}.json")
        self.traffic = load_json(self.bench_dir / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(self.bench_dir / "limits" / f"{name}.json")
        self.generator = load_module(
            self.bench_dir / "traffic" / f"{self.traffic['generator']}.py")

    def metrics(self, traced: bool) -> list:
        """The metric entries this cell reports: ``end_to_end`` untraced,
        ``per_layer`` traced, each kept where its ``workloads`` list (if
        any) names the cell."""
        group = self.bench["per_layer" if traced else "end_to_end"]
        return [m for m in group if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


# -- the record every metric reader reads ------------------------------------


class Query:
    """One query of the window: when it was due (seconds after the
    window opened), and the stamps its ticket took (``submit``, ``flush``,
    ``done_t``: ``time.perf_counter``).  ``answer`` is kept for sampled
    queries only."""

    __slots__ = ("kind", "source", "due", "ticket", "error", "sampled", "answer",
                 "submit", "flush", "done_t", "batch", "cached")

    def __init__(self, kind: str, source: int, due: float):
        self.kind = kind
        self.source = source
        self.due = due
        self.ticket = None
        self.error = None
        self.sampled = False
        self.answer = None
        self.submit = self.flush = self.done_t = None
        self.batch = None
        self.cached = False

    @property
    def ok(self) -> bool:
        return self.error is None and self.done_t is not None

    def latency_s(self, t0: float) -> float:
        """Seconds from when it was due to its answer (inf: none)."""
        return self.done_t - (t0 + self.due) if self.ok else math.inf


class Write:
    """One writer operation: ``op`` is "insert" or "delete" of ``pairs``
    undirected pairs; ``queued`` is when it was handed to the service and
    ``done`` when its publish was seen (inf: never)."""

    __slots__ = ("op", "pairs", "queued", "done")

    def __init__(self, op: str, pairs: np.ndarray, queued: float = -math.inf):
        self.op = op
        self.pairs = pairs
        self.queued = queued
        self.done = math.inf

    @property
    def directed(self) -> int:
        return 2 * int(self.pairs.shape[0])


class Record:
    """What one run saw, in the window and around it.  Times are
    ``time.perf_counter`` seconds; ``t0``/``t1`` bound the window and
    ``trace_t0``/``trace_t1`` the traced part of it (traced runs)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, traced: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.setup: dict = {}
        self.setup_s = None
        self.t0 = self.t1 = None
        self.trace_t0 = self.trace_t1 = None
        self.queries: list = []
        self.writes: list = []
        self.publishes: list = []  # (perf_counter, stamp) seen by the listener
        self.trace = None  # xplane.Summary of the traced part
        self.stats: dict = {}
        self.memory_peak_bytes = None
        self.compiles_in_window = 0
        self.device: dict = {}

    # helpers the readers share
    def publishes_between(self, a: float, b: float) -> int:
        return sum(1 for t, _ in self.publishes if a <= t < b)


# -- set-up --------------------------------------------------------------------


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, by
    JAX's monitoring events, and the seconds spent reading the cache."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.count = self.hits = self.misses = 0
        self.read_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.BUILD:
            self.count += 1
        elif event == self.READ:
            self.read_s += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def build(cell: Cell, seed: int, rec: Record):
    """Generate the graph, build the stream (host tree + device mirror)
    and the service; returns (graph, stream, service)."""
    import jax

    from bench import gen
    from repro.core import graph as G
    from repro.core.streaming import MIRROR, AspenStream
    from repro.serve.graph import GraphQueryService

    cfg = cell.config
    t = time.perf_counter()
    graph = gen.make_graph(cfg, seed)
    rec.setup["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    tree = G.build_graph(graph.n, graph.edges, weights=graph.weights)
    rec.setup["host_tree_s"] = time.perf_counter() - t
    t = time.perf_counter()
    # no edge_capacity: the stream's default pool, the next power of two
    cap = cfg.get("edge_capacity")
    stream = AspenStream(tree, edge_capacity=None if cap is None else int(cap))
    v = stream.acquire()
    jax.block_until_ready(list(v.aux.values()))
    capacity = v.aux[MIRROR].edge_capacity
    stream.release(v)
    rec.setup["mirror_s"] = time.perf_counter() - t
    batch_rows = int(cell.traffic["writer"]["batch_pairs"])
    svc = GraphQueryService(
        stream, backend="jax", update_batch=batch_rows,
        update_queue_size=max(65536, 16 * batch_rows), **cfg["service"],
    )
    log("setup", vertices=graph.n, directed_edges=int(graph.edges.shape[0]),
        edge_capacity=capacity, weighted=graph.weights is not None)
    return graph, stream, svc


def device_peak_bytes() -> Optional[int]:
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# -- the run -------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: Optional[float] = None, device: Optional[dict] = None,
             controls: bool = False) -> dict:
    """One run; returns the result object ``run.py`` prints (with the
    compared numbers under ``check``, last)."""
    from bench import reference as R

    t_start = time.perf_counter() if t_start is None else t_start
    rec = Record(cell, seed, float(seconds), traced)
    rec.device = dict(device or {})
    compiles = CompileCounter()
    graph, stream, svc = build(cell, seed, rec)
    state = R.EdgeState(graph.n, graph.edges, graph.weights)
    gen_mod = cell.generator
    plan = gen_mod.plan(cell.traffic, graph, seed, float(seconds))
    tracer = Tracer(traced)
    with svc:
        t = time.perf_counter()
        gen_mod.warm(plan, svc, stream, state)
        rec.setup["warmup_s"] = time.perf_counter() - t
        rec.setup["cache_loads_s"] = compiles.read_s
        rec.setup["cache_hits"] = compiles.hits
        rec.setup["cache_misses"] = compiles.misses
        rec.setup_s = time.perf_counter() - t_start
        log("setup", setup_s=rec.setup_s, **rec.setup)
        n_compiles = compiles.count
        gen_mod.drive(plan, svc, stream, rec, tracer)
        rec.compiles_in_window = compiles.count - n_compiles
        gen_mod.settle(plan, svc, rec)
        rec.stats = svc.stats()
        rec.memory_peak_bytes = device_peak_bytes()
        final = read_mirror(stream)
    held = plan.release_held(stream)
    del svc, stream
    if traced:
        rec.trace = tracer.summary(host_ops=rec.device.get("platform") == "cpu")
    lanes = rec.stats.get("lanes", {})
    cache = rec.stats.get("cache") or {}
    log("service", retraces=sum(v["retraces"] for v in lanes.values()),
        lane_errors=sum(v["errors"] for v in lanes.values()),
        batch_hist=json.dumps({k: v["batch_size_hist"] for k, v in lanes.items()
                               if v["batch_size_hist"]}),
        cache_hits=cache.get("hits"), cache_misses=cache.get("misses"),
        promotion_errors=cache.get("promotion_errors"),
        live_versions=rec.stats.get("live_versions"))
    log("window", seconds=seconds, programs_built_in_window=rec.compiles_in_window,
        memory_peak_bytes=rec.memory_peak_bytes,
        writer_backlog_at_close=plan.backlog_at_close,
        publishes=len(rec.publishes))
    t = time.perf_counter()
    check = gen_mod.check(plan, rec, state, final, held, controls=controls)
    log("check", reference_s=time.perf_counter() - t)
    return result(cell, rec, check, device)


def read_mirror(stream) -> dict:
    """The current version's device mirror, read back to the host."""
    from repro.core.streaming import MIRROR

    v = stream.acquire()
    try:
        return mirror_arrays(v.aux[MIRROR])
    finally:
        stream.release(v)


def mirror_arrays(m) -> dict:
    n_valid = int(np.asarray(m.m))
    return {
        "keys": np.asarray(m.keys)[:n_valid],
        "weights": None if m.weights is None else np.asarray(m.weights)[:n_valid],
        "offsets": np.asarray(m.offsets),
    }


class Tracer:
    """Profiler control for the traced run: ``start``/``stop`` around a
    steady part of the window; the trace goes to a temporary directory
    that ``summary`` reduces and then removes."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans: TraceAnnotations and JAX's own
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.on or self.t0 is None:
            return
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def summary(self, host_ops: bool = False):
        """The trace reduced; ``host_ops`` (CPU runs only) takes the
        operations from host events, as a CPU trace has no device plane."""
        from bench import xplane

        try:
            return xplane.summarize(xplane.find_xplane(self.dir), host_ops=host_ops)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def result(cell: Cell, rec: Record, check: dict, device: Optional[dict]) -> dict:
    metrics = {}
    for m in cell.metrics(rec.traced):
        value = cell.reader(m["name"]).read(rec)
        if value is None:
            log("metric", name=m["name"], value="none (nothing to read; left out)")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in check["numbers"].values())
    correct = correct and check["failed"] == 0
    dev = dict(device or {})
    dev["memory_peak_bytes"] = rec.memory_peak_bytes
    out = {
        "correct": bool(correct),
        "attempted": int(check["attempted"]),
        "failed": int(check["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if rec.traced and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    if "controls" in check:
        out["controls"] = check["controls"]
    out["check"] = check["numbers"]
    return out
