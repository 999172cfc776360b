"""merge_device_ms (ms, device trace): device time of the write path's
programs (batch sort, mirror insert and delete merges) in the traced part
of the window, per publish seen in it."""
from bench.harness import BENCH, load_module

_wp = load_module(BENCH / "metrics" / "_write_path.py")


def read(rec):
    if rec.trace is None:
        return None
    pubs = rec.publishes_between(rec.trace_t0, rec.trace_t1)
    runs = _wp.runs(rec.trace)
    if not pubs or not runs:
        return None
    return rec.trace.seconds(runs) / pubs * 1e3
