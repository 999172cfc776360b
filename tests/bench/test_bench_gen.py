"""The benchmark's own copies of the generators: the same arrays for the
same seed, and the same arrays as the program's originals."""
import numpy as np
import pytest

from _tiny import ROOT  # noqa: F401
from bench import gen


@pytest.mark.parametrize("log_n,m,seed", [(6, 200, 0), (10, 3000, 17), (12, 4096, 2**31 + 5)])
def test_rmat_copy_draws_the_programs_numbers(log_n, m, seed):
    from repro.data import rmat

    assert np.array_equal(gen.rmat_edges(log_n, m, seed=seed), rmat.rmat_edges(log_n, m, seed=seed))
    e = gen.rmat_edges(log_n, m, seed=seed)
    assert np.array_equal(gen.symmetrize(e), rmat.symmetrize(e))


def test_weights_copy_matches_chip_smoke():
    import sys

    sys.path.insert(0, str(ROOT))
    import chip_smoke

    edges, w = chip_smoke.make_graph(8, seed=3)
    assert np.array_equal(gen.hash_weights(edges), w)


def test_kronecker_is_seeded_and_follows_graph500():
    a, b, c = 0.57, 0.19, 0.19
    p1, perm1 = gen.kronecker_edges(10, 16, a, b, c, gen.rng_for(7, gen.GRAPH))
    p2, perm2 = gen.kronecker_edges(10, 16, a, b, c, gen.rng_for(7, gen.GRAPH))
    p3, _ = gen.kronecker_edges(10, 16, a, b, c, gen.rng_for(8, gen.GRAPH))
    assert np.array_equal(p1, p2) and np.array_equal(perm1, perm2)
    assert not np.array_equal(p1, p3)
    assert p1.shape == (16 << 10, 2) and p1.min() >= 0 and p1.max() < 1 << 10
    assert np.array_equal(np.sort(perm1), np.arange(1 << 10))
    bits = gen.kronecker_bits(10, 200_000, a, b, c, gen.rng_for(1, 0))
    # the top-left quadrant (both bits 0) at each level has probability A
    both0 = ((bits[:, 0] & 1) == 0) & ((bits[:, 1] & 1) == 0)
    assert both0.mean() == pytest.approx(a, abs=0.01)
    src1 = (bits[:, 0] & 1) == 1
    assert src1.mean() == pytest.approx(1 - a - b, abs=0.01)


@pytest.mark.parametrize("config", ["graph500-s18", "aspen-rmat16"])
def test_graph_and_batches_repeat_for_a_seed(config):
    from bench import harness

    cfg = dict(harness.load_json(harness.BENCH / "configs" / f"{config}.json"), scale=8)
    g1, g2 = gen.make_graph(cfg, 2**33 + 1), gen.make_graph(cfg, 2**33 + 1)
    assert np.array_equal(g1.edges, g2.edges)
    assert (g1.weights is None) == (cfg["weights"] is None)
    e = g1.edges
    assert (e[:, 0] != e[:, 1]).all()
    keys = set(map(tuple, e.tolist()))
    assert all((d, s) in keys for s, d in e.tolist())
    r1, r2 = gen.rng_for(5, gen.UPDATES), gen.rng_for(5, gen.UPDATES)
    b1, b2 = g1.batch(r1, 100), g2.batch(r2, 100)
    assert np.array_equal(b1, b2)
    assert b1.shape == (100, 2) and (b1[:, 0] < b1[:, 1]).all()


def test_query_plans_repeat_and_share_their_schedule():
    from _tiny import tiny_cell

    cell = tiny_cell("g500-s18-traverse-live", scale=8, rate=10.0)
    graph = gen.make_graph(cell.config, 3)
    mod = cell.generator
    p1 = mod.plan(cell.traffic, graph, 3, 20.0)
    p2 = mod.plan(cell.traffic, graph, 3, 20.0)
    p3 = mod.plan(cell.traffic, gen.make_graph(cell.config, 4), 4, 20.0)
    key = lambda p: [(q.kind, q.source, q.due) for q in p.queries]  # noqa: E731
    assert key(p1) == key(p2) and key(p1) != key(p3)
    # another seed: the same arrivals and kinds, other sources
    assert [(q.kind, q.due) for q in p1.queries] == [(q.kind, q.due) for q in p3.queries]
    assert [q.source for q in p1.queries] != [q.source for q in p3.queries]
    assert len(p1.queries) == 200
    due = np.asarray([q.due for q in p1.queries])
    gaps = np.sort(np.append(np.diff(due), 20.0 - due[-1]))
    want = -np.log1p(-(np.arange(200) + 0.5) / 200)
    assert np.allclose(gaps, want * 20.0 / want.sum())
    assert sum(q.kind == "bfs" for q in p1.queries) == 100
    assert sum(q.sampled for q in p1.queries) == cell.traffic["queries"]["check_sample"]
