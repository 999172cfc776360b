"""engine_device_ms (ms, device trace): device time of the query programs
(the batched drivers, engine_aux, PageRank's message gather, segment sum
and elementwise steps, carry-forward promotions: every program but the
write path's) in the traced part of the window, per query the engine
answered in it."""
from bench.harness import BENCH, load_module

_wp = load_module(BENCH / "metrics" / "_write_path.py")


def read(rec):
    if rec.trace is None:
        return None
    query_s = rec.trace.seconds(rec.trace.module_runs(exclude=_wp.MODULES))
    answered = sum(1 for q in rec.queries if q.ok and not q.cached
                   and rec.trace_t0 <= q.done_t < rec.trace_t1)
    if not answered or not query_s:
        return None
    return query_s / answered * 1e3
