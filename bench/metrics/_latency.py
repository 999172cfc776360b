"""Latencies of the window's queries, from when each was due to its
answer (host clock).  A query that failed or never came counts as
answered when the benchmark stopped waiting, a minute past the close."""
import numpy as np

from bench.harness import RESULT_WAIT_S


def latencies_ms(rec, kind=None) -> np.ndarray:
    give_up = rec.t1 + RESULT_WAIT_S
    return np.asarray([
        ((q.done_t if q.ok else give_up) - (rec.t0 + q.due)) * 1e3
        for q in rec.queries if kind is None or q.kind == kind
    ])


def percentile(rec, q: float, kind=None):
    lat = latencies_ms(rec, kind)
    return float(np.percentile(lat, q)) if lat.size else None
