"""lane_wait_ms (ms, program span): mean of t_flush - t_submit over the
window's queries that went through a lane (not served from the cache),
as the service's tickets stamp them."""
import numpy as np


def read(rec):
    w = [(q.flush - q.submit) * 1e3 for q in rec.queries
         if q.ok and not q.cached and q.flush is not None]
    return float(np.mean(w)) if w else None
