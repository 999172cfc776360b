"""query_p90_ms (ms, host clock): 90th percentile latency of every query
due in the window, from when it was due to its answer; a failed query
counts as missing (answered only when the benchmark stopped waiting)."""
from bench.harness import BENCH, load_module

_lat = load_module(BENCH / "metrics" / "_latency.py")


def read(rec):
    return _lat.percentile(rec, 90)
