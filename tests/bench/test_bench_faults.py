"""A run with the timed path broken underneath must come out not
correct: a step that returns its state unchanged, half of a batch left
out, an answer altered where it is produced, and the control: the
reference put in the program's place at the next lower precision than
the configuration states (``Precision.HIGH``, three passes, for float32
at ``HIGHEST``).  The harness's look for a chip is skipped; the rest of a
run is driven as on the chip, at a tiny size."""
import numpy as np

from _tiny import run_tiny, tiny_cell
from bench import reference as R
from repro.core.streaming import AspenStream
from repro.core.traversal import algorithms as talg
from repro.core.traversal.jax_backend import JaxEngine


def _updates():
    return tiny_cell("rmat16-updates", scale=10, batch_pairs=64)


def _traverse(scale=9):
    return tiny_cell("g500-s18-traverse-live", scale=scale)


def _not_correct(out, number):
    assert out["correct"] is False
    c = out["check"][number]
    assert c["value"] > c["limit"]


def test_insert_step_returning_its_state_unchanged(monkeypatch):
    monkeypatch.setattr(AspenStream, "_mirror_insert",
                        lambda self, mirror, g_old, edges, weights=None: mirror)
    _not_correct(run_tiny(_updates()), "mirror_diff")


def test_delete_step_returning_its_state_unchanged(monkeypatch):
    monkeypatch.setattr(AspenStream, "_mirror_delete", lambda self, mirror, edges: mirror)
    _not_correct(run_tiny(_updates()), "mirror_diff")


def test_half_of_each_insert_batch_left_out(monkeypatch):
    orig = AspenStream._mirror_insert

    def half(self, mirror, g_old, edges, weights=None):
        k = edges.shape[0] // 2
        return orig(self, mirror, g_old, edges[:k], None if weights is None else weights[:k])

    monkeypatch.setattr(AspenStream, "_mirror_insert", half)
    _not_correct(run_tiny(_updates()), "mirror_diff")


def test_half_of_each_insert_batch_left_out_under_queries(monkeypatch):
    orig = AspenStream._mirror_insert

    def half(self, mirror, g_old, edges, weights=None):
        return orig(self, mirror, g_old, edges[: edges.shape[0] // 2], None)

    monkeypatch.setattr(AspenStream, "_mirror_insert", half)
    _not_correct(run_tiny(_traverse()), "mirror_diff")


def test_bfs_answer_altered_where_it_is_produced(monkeypatch):
    orig = JaxEngine.bfs_batch

    def altered(self, sources):
        parents, depths = orig(self, sources)
        far = int(np.argmax(np.asarray(depths[0])))  # a reached vertex, not the source
        return parents.at[0, far].set(-1), depths

    monkeypatch.setattr(JaxEngine, "bfs_batch", altered)
    _not_correct(run_tiny(_traverse()), "bfs_bad_vertices")


def test_pagerank_answer_altered_where_it_is_produced(monkeypatch):
    orig = JaxEngine.edge_map_reduce_batch

    def altered(self, values):
        return orig(self, values) * 1.001

    monkeypatch.setattr(JaxEngine, "edge_map_reduce_batch", altered)
    _not_correct(run_tiny(_traverse()), "pagerank_max_gap")


def test_control_high_precision_reference_in_the_programs_place(monkeypatch):
    def control(engine, resets=None, **kw):
        g = engine.g
        keys = np.asarray(g.keys)[: int(g.m)]
        pairs = np.stack([keys >> 32, keys & R.MASK32], axis=1)
        snap = R.EdgeState(engine.n, pairs).snapshot()
        srcs = np.argmax(np.asarray(resets), axis=1)
        return R.pagerank(snap, srcs, "high")

    monkeypatch.setattr(talg, "pagerank_multi", control)
    out = run_tiny(_traverse(scale=11))
    _not_correct(out, "pagerank_rel_gap")
    assert out["check"]["bfs_bad_vertices"]["value"] == 0
