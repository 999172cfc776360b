"""merge_dispatch_ms (ms, program span): mean duration of the writer's
``aspen.publish.mirror`` spans in the traced window: batch packing, the
host->device copy and the dispatch of the mirror's merge.  Near the
merge's device time, the writer waits for the merge; a few ms, the
dispatch is asynchronous."""
from bench.harness import BENCH, load_module

_spans = load_module(BENCH / "metrics" / "_spans.py")


def read(rec):
    return _spans.mean_ms(rec, "aspen.publish.mirror")
