"""publish_tree_ms (ms, program span): mean duration of the writer's
``aspen.publish.tree`` spans in the traced window: the host C-tree
update of one publish (``AspenStream._publish``)."""
from bench.harness import BENCH, load_module

_spans = load_module(BENCH / "metrics" / "_spans.py")


def read(rec):
    return _spans.mean_ms(rec, "aspen.publish.tree")
