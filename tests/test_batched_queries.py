"""Batched multi-source query engine (DESIGN.md §7).

Pins the PR's contract: (1) ``bfs_batch`` over B=64 sources issues O(1)
host syncs total (HOST_SYNCS spy, analogous to FLAT_REBUILDS) and its
parents/depths match 64 serial ``bfs()`` calls on BOTH backends;
(2) the generic batched edgeMap step agrees with per-lane serial steps
in every direction mode; (3) ``bc_multi`` / ``pagerank_multi`` /
``landmark_distances`` agree across backends and with their serial
texts; (4) ``AspenStream.query_batch`` coalesces queries against one
version-pinned engine and tracks versions; (5) ``run_concurrent``
reports batched query throughput via ``queries_per_call``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import flat_graph as fg
from repro.core import graph as G
from repro.core.streaming import AspenStream, make_update_stream, run_concurrent
from repro.core.traversal import HOST_SYNCS, NumpyEngine, make_engine
from repro.core.traversal import algorithms as talg
from repro.data.rmat import rmat_edges, symmetrize


@pytest.fixture(scope="module")
def rmat_graph():
    edges = symmetrize(rmat_edges(8, 2000, seed=11))  # 256 vertices
    return 256, edges


@pytest.fixture(scope="module")
def engines(rmat_graph):
    n, edges = rmat_graph
    eng_np = NumpyEngine(G.flat_snapshot(G.build_graph(n, edges)))
    eng_jx = make_engine(fg.from_edges(n, edges))
    return eng_np, eng_jx


@pytest.fixture(scope="module")
def sources(rmat_graph):
    n, _ = rmat_graph
    return np.random.default_rng(3).integers(0, n, 64)


# ---------------------------------------------------------------------------
# bfs_batch: O(1) syncs, exact parity with serial on both backends
# ---------------------------------------------------------------------------


def test_bfs_batch_matches_serial_both_backends(rmat_graph, engines, sources):
    eng_np, eng_jx = engines
    p_jx, d_jx = talg.bfs_multi(eng_jx, sources)
    p_np, d_np = talg.bfs_multi(eng_np, sources)  # serial-loop fallback
    assert p_jx.shape == p_np.shape == (64, eng_np.n)
    np.testing.assert_array_equal(p_np, p_jx)  # same max-parent rule
    np.testing.assert_array_equal(d_np, d_jx)
    # and against 64 serial bfs() calls on the jax engine itself
    for i, s in enumerate(sources):
        p_ser = talg.bfs(eng_jx, int(s))
        np.testing.assert_array_equal(p_ser, p_jx[i])
        np.testing.assert_array_equal(talg.bfs_depths(p_ser, int(s)), d_jx[i])


def test_bfs_batch_constant_syncs(engines, sources):
    """The whole B-source traversal costs a CONSTANT number of host
    syncs (one dispatch + result fetches), independent of B — the
    serial loop pays one per round per source."""
    _, eng_jx = engines
    talg.bfs_multi(eng_jx, sources)  # warm the jit at B=64
    talg.bfs_multi(eng_jx, sources[:8])  # ... and at B=8

    base = HOST_SYNCS.count
    talg.bfs_multi(eng_jx, sources[:8])
    syncs_b8 = HOST_SYNCS.count - base
    base = HOST_SYNCS.count
    talg.bfs_multi(eng_jx, sources)
    syncs_b64 = HOST_SYNCS.count - base
    assert syncs_b64 == syncs_b8 <= 4  # O(1), not O(D * B)

    base = HOST_SYNCS.count
    for s in sources[:8]:
        talg.bfs(eng_jx, int(s))
    serial_syncs = HOST_SYNCS.count - base
    assert serial_syncs > 8 * syncs_b8  # the loop the batch engine kills


def test_batch_size_quantization(rmat_graph, engines, sources):
    """Ragged batch sizes pad to power-of-two lanes (the serving path
    must not recompile the while_loop driver per distinct B); the pad
    lanes are sliced off and never leak into results."""
    import repro.core.traversal.jax_backend as jb

    _, eng_jx = engines
    for B, pad in ((3, 4), (5, 8), (7, 8)):  # 5 and 7 share the B=8 trace
        padded, b = jb.JaxEngine._quantized_sources(sources[:B])
        assert padded.shape[0] == pad and b == B
        p, d = talg.bfs_multi(eng_jx, sources[:B])
        assert p.shape == d.shape == (B, eng_jx.n)
        for i in range(B):
            np.testing.assert_array_equal(p[i], talg.bfs(eng_jx, int(sources[i])))
    dep = talg.bc_multi(eng_jx, sources[:3])
    assert dep.shape == (3, eng_jx.n)
    np.testing.assert_allclose(
        dep[1], talg.bc(eng_jx, int(sources[1])), rtol=1e-4, atol=1e-4
    )


def test_bfs_batch_duplicate_and_isolated_sources(rmat_graph):
    n = 16
    gf = fg.from_edges(n, np.array([[0, 1], [1, 2], [2, 3]]))
    eng = make_engine(gf)
    parents, depths = talg.bfs_multi(eng, [0, 0, 5])
    np.testing.assert_array_equal(parents[0], parents[1])
    assert depths[2][5] == 0 and (depths[2] >= 0).sum() == 1  # isolated lane
    np.testing.assert_array_equal(depths[0][:4], [0, 1, 2, 3])


# ---------------------------------------------------------------------------
# the generic batched step: per-lane direction optimization
# ---------------------------------------------------------------------------


def _count_F(ops, state, us, vs, ws, valid):
    out = ops.scatter_or(ops.xp.zeros(state.shape[0], dtype=bool), vs, valid)
    return state, out


def _all_C(ops, state, vs):
    return ops.xp.ones(vs.shape, dtype=bool)


@pytest.mark.parametrize("mode", ["auto", "sparse", "dense"])
def test_edge_map_batch_matches_per_lane_serial(rmat_graph, engines, mode):
    """Mixed lanes (one tiny sparse-routed frontier, one full frontier)
    through the batched step equal each lane's serial edge_map."""
    n, edges = rmat_graph
    _, eng_jx = engines
    U_small = eng_jx.frontier_from_ids([int(edges[0, 0])])
    U_all = eng_jx.frontier_all()
    U_b = jnp.stack([U_small.dense, U_all.dense])
    state_b = jnp.zeros((2, n))
    out_b, _ = eng_jx.edge_map_batch(U_b, _count_F, _all_C, state_b, mode=mode)
    for i, U in enumerate((U_small, U_all)):
        out, _ = eng_jx.edge_map(U, _count_F, _all_C, jnp.zeros(n), mode=mode)
        np.testing.assert_array_equal(np.asarray(out_b[i]), np.asarray(out.to_dense()))


def test_engine_cc_labels_unified(rmat_graph, engines):
    """The engine-level in-trace CC entry point reuses the prebuilt aux
    and agrees with both the module-level jit loop and the generic
    round-looped text (symmetric graph: labels are exact)."""
    from repro.core.traversal.jax_backend import cc_labels

    _, eng_jx = engines
    labels = np.asarray(eng_jx.cc_labels())
    np.testing.assert_array_equal(labels, np.asarray(cc_labels(eng_jx.g)))
    np.testing.assert_array_equal(labels, talg.connected_components(eng_jx))


# ---------------------------------------------------------------------------
# bc_multi / landmark_distances / pagerank_multi
# ---------------------------------------------------------------------------


def test_bc_multi_parity(rmat_graph, engines, sources):
    eng_np, eng_jx = engines
    dep_jx = talg.bc_multi(eng_jx, sources[:8])
    dep_np = talg.bc_multi(eng_np, sources[:8])  # serial-loop fallback
    # batched pull reduces via segmented scans: f32 summation order
    # differs from the serial scatter-adds — parity to f32 tolerance
    np.testing.assert_allclose(dep_jx, dep_np, rtol=1e-4, atol=1e-4)
    # and against the serial text on the jax engine itself
    np.testing.assert_allclose(
        dep_jx[0], talg.bc(eng_jx, int(sources[0])), rtol=1e-4, atol=1e-4
    )


def test_landmark_distances(engines, sources):
    eng_np, eng_jx = engines
    lm = sources[:4]
    dist = talg.landmark_distances(eng_jx, lm)
    assert dist.shape == (4, eng_jx.n)
    np.testing.assert_array_equal(dist, talg.bfs_multi(eng_np, lm)[1])
    for i, s in enumerate(lm):
        assert dist[i][int(s)] == 0


def test_pagerank_multi_parity(engines):
    eng_np, eng_jx = engines
    n = eng_np.n
    # uniform row == the serial global pagerank
    np.testing.assert_allclose(
        talg.pagerank_multi(eng_jx, iters=8)[0],
        talg.pagerank(eng_jx, iters=8),
        atol=1e-7,
    )
    # personalized rows: mass conserved per lane, backends agree
    resets = np.zeros((3, n))
    resets[0, 1] = 1.0
    resets[1, 7] = 1.0
    resets[2] = 1.0 / n
    pp_jx = talg.pagerank_multi(eng_jx, resets=resets, iters=8)
    pp_np = talg.pagerank_multi(eng_np, resets=resets, iters=8)
    np.testing.assert_allclose(pp_jx.sum(axis=1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(pp_jx, pp_np, atol=1e-6)
    assert not np.allclose(pp_jx[0], pp_jx[1])  # personalization matters


def test_edge_map_reduce_batch_parity(rmat_graph, engines):
    n, _ = rmat_graph
    eng_np, eng_jx = engines
    vals = np.random.default_rng(0).standard_normal((5, n))
    out_np = eng_np.edge_map_reduce_batch(vals)  # base-class loop
    out_jx = np.asarray(eng_jx.edge_map_reduce_batch(vals.astype(np.float32)))
    assert out_np.shape == out_jx.shape == (5, n)
    np.testing.assert_allclose(out_np, out_jx, rtol=1e-4, atol=1e-4)
    # each batched row equals the scalar reduce of that row
    np.testing.assert_allclose(
        out_jx[2],
        np.asarray(eng_jx.edge_map_reduce(jnp.asarray(vals[2], jnp.float32))),
        rtol=1e-5,
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# streaming: query_batch coalesces against one version-pinned engine
# ---------------------------------------------------------------------------


def test_query_batch_serves_pending_queries(rmat_graph):
    n, edges = rmat_graph
    s = AspenStream(G.build_graph(n, edges[:-200]))
    srcs = np.random.default_rng(1).integers(0, n, 16)
    parents = s.query_batch(srcs, kind="bfs")
    eng = s.engine("jax")
    for i, src in enumerate(srcs):
        np.testing.assert_array_equal(parents[i], talg.bfs(eng, int(src)))
    dist = s.query_batch(srcs[:4], kind="distances")
    np.testing.assert_array_equal(dist, talg.bfs_multi(eng, srcs[:4])[1])
    dep = s.query_batch(srcs[:4], kind="bc")
    np.testing.assert_allclose(dep, talg.bc_multi(eng, srcs[:4]))
    pr = s.query_batch(kind="pagerank", iters=4)
    assert pr.shape == (1, s.engine("jax").n)
    with pytest.raises(ValueError):
        s.query_batch(srcs, kind="nope")


def test_query_batch_tracks_versions(rmat_graph):
    """A batch served after an update sees the new version (the engine
    is version-pinned, re-resolved per batch)."""
    n, edges = rmat_graph
    keep, batch = edges[:-100], edges[-100:]
    s = AspenStream(G.build_graph(n, keep))
    src = int(batch[0, 0])
    before = s.query_batch([src], kind="bfs")[0]
    s.insert_edges(batch)
    after = s.query_batch([src], kind="bfs")[0]
    assert (after >= 0).sum() >= (before >= 0).sum()
    np.testing.assert_array_equal(after, talg.bfs(s.engine("jax"), src))


def test_run_concurrent_batched_throughput(rmat_graph):
    n, edges = rmat_graph
    keep, stream = make_update_stream(edges, 150, seed=8)
    s = AspenStream(G.build_graph(n, keep))
    srcs = np.random.default_rng(2).integers(0, n, 16)
    s.query_batch(srcs, kind="bfs")  # warm the batch jit
    stats = run_concurrent(
        s,
        stream,
        query_fn=lambda eng: talg.bfs_multi(eng, srcs),
        duration_s=1.0,
        batch_size=25,
        engine_backend="jax",
        queries_per_call=len(srcs),
    )
    assert stats.n_queries > 0 and stats.n_queries % len(srcs) == 0
    assert stats.queries_per_sec > 0


@pytest.mark.parametrize("L", [8, 832, 5000])
def test_blocked_scans_match_numpy(L):
    """The drivers' scan primitives (blocked shifted-add scans, int32)
    against numpy: prefix sum, nonzero, and segmented sum/min/max over
    bounds with empty segments."""
    from repro.core.traversal import jax_backend as jb

    rng = np.random.default_rng(L)
    x = rng.integers(-5, 5, (3, L)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(jb._cumsum(jnp.asarray(x))), np.cumsum(x, axis=1))
    mask = rng.random(L) < 0.3
    for size in (7, L):
        got = np.asarray(jb._nonzero_i32(jnp.asarray(mask), size, L))
        want = np.full(size, L)
        nz = np.flatnonzero(mask)[:size]
        want[: nz.size] = nz
        np.testing.assert_array_equal(got, want)
    bounds = np.sort(rng.integers(0, L, 11)).astype(np.int32)
    bounds[0], bounds[-1] = 0, L
    bounds[3] = bounds[4]  # an empty segment
    msg = rng.integers(0, 9, (2, L)).astype(np.int32)
    segs = list(zip(bounds[:-1], bounds[1:]))
    b = jnp.asarray(bounds)
    want = [[m[a:e].sum() for a, e in segs] for m in msg]
    np.testing.assert_array_equal(np.asarray(jb._segsum_rows(jnp.asarray(msg), b)), want)
    want = [[m[a:e].max() if e > a else -1 for a, e in segs] for m in msg]
    np.testing.assert_array_equal(np.asarray(jb._segmax_rows(jnp.asarray(msg), b)), want)
    f = msg.astype(np.float32)
    want = [[m[a:e].min() if e > a else np.inf for a, e in segs] for m in f]
    np.testing.assert_array_equal(np.asarray(jb._segmin_rows(jnp.asarray(f), b)), want)
