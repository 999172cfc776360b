"""Device idle share under the writer (%, program span and device
trace): 100 * the time in which the first device runs no operation
while an ``aspen.publish`` span is open on any thread, over the traced
window.  Set against the cell's ``idle_pct``, the share of the idle
time the writer's transactions account for."""
from bench.harness import BENCH, load_module

_spans = load_module(BENCH / "metrics" / "_spans.py")


def read(rec):
    return _spans.idle_under_pct(rec, "aspen.publish")
