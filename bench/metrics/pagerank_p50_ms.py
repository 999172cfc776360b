"""pagerank_p50_ms (ms, host clock): median latency of the window's pagerank
queries, from when each was due to its answer; a failed query counts as
answered when the benchmark stopped waiting."""
from bench.harness import BENCH, load_module

_lat = load_module(BENCH / "metrics" / "_latency.py")


def read(rec):
    return _lat.percentile(rec, 50, kind="pagerank")
