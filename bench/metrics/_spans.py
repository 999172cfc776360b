"""The program's own profiler spans in the traced window, on the trace's
clock (``jax.profiler.TraceAnnotation`` sites in ``core/streaming.py``
and ``serve/graph``): ``aspen.publish`` with ``aspen.publish.tree`` and
``aspen.publish.mirror`` inside it, ``aspen.engine_build``,
``serve.flush`` with ``serve.flush.fetch`` inside it, ``serve.promote``.

A program without these spans (a build from before they were added)
reads nothing: every reader returns None there.  Only spans that lie
wholly inside the window count; the profiler itself records no span
that was open when the trace started or stopped (its inner spans that
were not are recorded, without it)."""
from bench.xplane import union_ns

PROGRAM = ("aspen.", "serve.")


def whole(summary, name: str) -> list:
    """Spans called ``name`` that start and end inside the window."""
    lo, hi = summary.window_ns
    return [s for s in summary.spans if s.name == name
            and lo <= s.start_ns and s.start_ns + s.dur_ns <= hi]


def mean_ms(rec, name: str):
    """Mean duration (ms) of the window's ``name`` spans; None if none."""
    if rec.trace is None:
        return None
    spans = whole(rec.trace, name)
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / len(spans) * 1e-6


def _overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_under_pct(rec, name: str):
    """% of the window in which the first device runs no operation while
    a ``name`` span is open on any thread; None when the trace holds no
    program span at all."""
    s = rec.trace
    if s is None or not s.devices or s.window_s <= 0:
        return None
    if not any(p.name.startswith(PROGRAM) for p in s.spans):
        return None
    busy = union_ns((o.start_ns, o.start_ns + o.dur_ns)
                    for o in s.ops if o.device == s.devices[0])
    open_ = union_ns((p.start_ns, p.start_ns + p.dur_ns) for p in whole(s, name))
    idle = sum(e - a for a, e in open_) - _overlap_ns(open_, busy)
    lo, hi = s.window_ns
    return 100.0 * idle / (hi - lo)


def inner_sum_ms(rec, outer: str, inner: str):
    """Mean over the window's ``outer`` spans of the summed durations of
    the ``inner`` spans inside them (same thread line, nested in time;
    each inner span counts once); None if no ``outer`` span lies in the
    window."""
    if rec.trace is None:
        return None
    outs = whole(rec.trace, outer)
    if not outs:
        return None
    total = sum(f.dur_ns for f in whole(rec.trace, inner)
                if any(o.thread == f.thread and o.start_ns <= f.start_ns
                       and f.start_ns + f.dur_ns <= o.start_ns + o.dur_ns for o in outs))
    return total / len(outs) * 1e-6
