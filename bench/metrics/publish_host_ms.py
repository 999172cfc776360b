"""publish_host_ms (ms, host clock less device trace): the closed-loop
writer's wall time per publish in the traced part of the window, less
``merge_device_ms``: what each publish costs besides the device merge
(the host C-tree, packing, the service's writer thread)."""
from bench.harness import BENCH, load_module

_merge = load_module(BENCH / "metrics" / "merge_device_ms.py")


def read(rec):
    merge = _merge.read(rec)
    pubs = rec.publishes_between(rec.trace_t0, rec.trace_t1)
    if merge is None or not pubs:
        return None
    return (rec.trace_t1 - rec.trace_t0) / pubs * 1e3 - merge
