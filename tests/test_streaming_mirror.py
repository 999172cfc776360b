"""Dual-representation streaming: the resident FlatGraph mirror.

Pins the PR's contract: (1) the mirror is *exactly* the flat graph you
would get by rebuilding from the tree snapshot, across interleaved
insert/delete streams with edge-capacity and vertex-count growth;
(2) ``stream.engine("jax")`` after a batch update performs no O(m) host
rebuild (FLAT_REBUILDS spy) and no host argsort (np.argsort trap);
(3) engines are version-pinned: O(1) reuse on an unchanged version,
fresh engine per new version; (4) the mirror-less rebuild path remains
available and correct.
"""
import numpy as np
import pytest

from repro.core import flat_graph as fg
from repro.core import graph as G
from repro.core import traversal
from repro.core.streaming import AspenStream, make_update_stream, run_concurrent
from repro.core.traversal import algorithms as talg
from repro.data.rmat import rmat_edges, symmetrize


@pytest.fixture(scope="module")
def small_graph():
    edges = symmetrize(rmat_edges(7, 900, seed=13))  # 128 vertices
    return 128, edges


def assert_mirror_parity(s: AspenStream):
    """mirror == from_edges(flat_snapshot): same n, edges, offsets, m.
    (Capacities may differ — the mirror's pool grows monotonically.)"""
    snap = s.flat_snapshot()
    mirror = s.flat_graph()
    rebuilt = traversal.flat_graph_of(snap)
    assert mirror.n == rebuilt.n
    assert int(mirror.m) == int(rebuilt.m) == snap.m
    np.testing.assert_array_equal(fg.to_edge_array(mirror), fg.to_edge_array(rebuilt))
    np.testing.assert_array_equal(
        np.asarray(mirror.offsets), np.asarray(rebuilt.offsets)
    )


def test_mirror_parity_interleaved_stream(small_graph):
    n, edges = small_graph
    keep, stream = make_update_stream(edges, 400, seed=3)
    s = AspenStream(G.build_graph(n, keep))
    assert_mirror_parity(s)
    for i in range(0, stream.shape[0], 40):
        batch = stream[i : i + 40]
        ins = batch[batch[:, 2] == 0][:, :2]
        dels = batch[batch[:, 2] == 1][:, :2]
        if ins.size:
            s.insert_edges(ins)
        if dels.size:
            s.delete_edges(dels)
        assert_mirror_parity(s)


def test_mirror_parity_capacity_growth(small_graph):
    n, edges = small_graph
    s = AspenStream(G.build_graph(n, edges[:100]))
    cap0 = s.flat_graph().edge_capacity
    s.insert_edges(edges[100:])  # force pool growth past the initial capacity
    assert s.flat_graph().edge_capacity > cap0
    assert_mirror_parity(s)
    s.delete_edges(edges[: len(edges) // 2])
    assert_mirror_parity(s)


def test_mirror_parity_vertex_growth(small_graph):
    n, edges = small_graph
    s = AspenStream(G.build_graph(n, edges))
    assert s.flat_graph().n == n
    grow = np.array([[3, n + 70], [n + 70, 3], [n + 10, 4]])
    s.insert_edges(grow, symmetric=False)
    assert s.flat_graph().n == n + 71
    assert_mirror_parity(s)
    s.delete_edges(grow[:1], symmetric=False)
    assert_mirror_parity(s)
    # vertex-set ops take the rebuild path but stay consistent
    s.insert_vertices(np.array([n + 100]))
    assert s.flat_graph().n == n + 101
    assert_mirror_parity(s)


def test_engine_no_rebuild_no_host_argsort(small_graph, monkeypatch):
    n, edges = small_graph
    keep, stream = make_update_stream(edges, 200, seed=5)
    s = AspenStream(G.build_graph(n, keep))
    s.engine("jax")  # warm the jit caches for this shape
    base = traversal.FLAT_REBUILDS.count

    ins = stream[stream[:, 2] == 0][:30, :2]
    dels = stream[stream[:, 2] == 1][:10, :2]
    s.insert_edges(ins)
    s.delete_edges(dels)

    def _trap(*a, **k):  # host argsort = the old O(m log m) precompute
        raise AssertionError("host np.argsort on the mirror engine path")

    with monkeypatch.context() as mp:
        mp.setattr(np, "argsort", _trap)
        eng = s.engine("jax")
    assert traversal.FLAT_REBUILDS.count == base, "mirror engine path rebuilt"

    # and the engine it handed out answers correctly
    src = int(keep[0, 0])
    p_jx = talg.bfs(eng, src)
    p_np = talg.bfs(s.engine("numpy"), src)
    np.testing.assert_array_equal(
        talg.bfs_depths(p_np, src), talg.bfs_depths(p_jx, src)
    )


def test_engine_version_pinned_reuse(small_graph):
    n, edges = small_graph
    s = AspenStream(G.build_graph(n, edges[:-100]))
    e0 = s.engine("jax")
    assert s.engine("jax") is e0  # O(1): same version -> same engine
    assert s.engine("numpy") is s.engine("numpy")
    s.insert_edges(edges[-100:])
    e1 = s.engine("jax")
    assert e1 is not e0  # new version -> new engine
    assert e1.m > e0.m
    assert s.engine("jax") is e1


def test_mirrorless_stream_falls_back_to_rebuild(small_graph):
    n, edges = small_graph
    s = AspenStream(G.build_graph(n, edges), mirror=False)
    base = traversal.FLAT_REBUILDS.count
    eng = s.engine("jax")
    assert traversal.FLAT_REBUILDS.count == base + 1  # the historical path
    assert s.engine("jax") is eng  # still version-cached
    src = int(edges[0, 0])
    p = talg.bfs(eng, src)
    np.testing.assert_array_equal(
        talg.bfs_depths(p, src),
        talg.bfs_depths(talg.bfs(s.engine("numpy"), src), src),
    )


def test_device_update_entry_points(small_graph):
    """insert/delete_edges_device: host-free batches (and the donating
    variant) agree with the host-driven path."""
    import jax.numpy as jnp

    from repro.core import flat_ctree as fct

    n, edges = small_graph
    keep, batch = edges[:-200], edges[-200:]
    gf = fg.from_edges(n, keep)
    keys = (batch[:, 0] << 32) | batch[:, 1]
    dev = fct.from_device(jnp.asarray(keys), fct.grown_capacity(keys.size))
    np.testing.assert_array_equal(fct.to_array(dev), np.unique(keys))

    g_dev = fg.insert_edges_device(gf, dev)
    np.testing.assert_array_equal(fg.to_edge_array(g_dev), edges)
    g_back = fg.delete_edges_device(g_dev, dev)
    np.testing.assert_array_equal(fg.to_edge_array(g_back), keep)

    # donating variant: caller owns the sole reference to its input
    g_own = fg.from_edges(n, keep)
    g_don = fg.insert_edges_device(g_own, dev, donate=True)
    np.testing.assert_array_equal(fg.to_edge_array(g_don), edges)


def test_queries_drop_foreign_dst():
    """Every query direction must DROP a valid edge whose destination is
    outside [0, n) (asymmetric stream naming a never-source vertex),
    not fold it into the clipped vertex n-1 (regression: the jit
    engine_aux once sorted by the clipped dst; the whole-graph loops
    and the sparse branch clipped too)."""
    import jax.numpy as jnp

    from repro.core.traversal import make_engine
    from repro.core.traversal.jax_backend import bfs_levels, cc_labels

    gf = fg.from_edges(4, np.array([[0, 1], [1, 2], [2, 500]]))
    eng = make_engine(gf)
    # reduce: (2,500)'s mass must not land on vertex 3
    out = np.asarray(eng.edge_map_reduce(jnp.ones(4, jnp.float64)))
    np.testing.assert_allclose(out, [0.0, 1.0, 1.0, 0.0])
    # sparse and dense edgeMap: vertex 3 stays unreached
    for mode in ("sparse", "dense"):
        p = talg.bfs(eng, 0, direction_optimize=(mode == "dense"))
        assert p[3] == -1, mode
    # whole-graph jit loops: vertex 3 isolated
    np.testing.assert_array_equal(np.asarray(bfs_levels(gf, 0)), [0, 1, 2, -1])
    np.testing.assert_array_equal(np.asarray(cc_labels(gf)), [0, 0, 0, 3])


def test_publish_self_heals_after_raw_vg_write(small_graph):
    """A version published through the raw vg writer API carries no
    mirror; the next stream update must rebuild it, not KeyError."""
    n, edges = small_graph
    s = AspenStream(G.build_graph(n, edges[:400]))
    s.vg.update(lambda g: G.insert_edges(g, edges[400:500]))  # no aux
    s.insert_edges(edges[500:600])  # heals: rebuild from the new tree
    assert_mirror_parity(s)
    s.delete_edges(edges[:100])  # and is incremental again afterwards
    assert_mirror_parity(s)


def test_run_concurrent_engine_backend(small_graph):
    n, edges = small_graph
    keep, stream = make_update_stream(edges, 150, seed=8)
    s = AspenStream(G.build_graph(n, keep))
    src = int(keep[0, 0])
    stats = run_concurrent(
        s,
        stream,
        query_fn=lambda eng: talg.bfs(eng, src),
        duration_s=1.0,
        batch_size=25,
        engine_backend="jax",
    )
    assert stats.n_updates > 0 and stats.n_queries > 0
    assert_mirror_parity(s)


@pytest.mark.parametrize("step", ["insert_grows_n", "delete", "donated"])
def test_incremental_offsets_match_rebuild(small_graph, step):
    """The merge updates CSR offsets from the batch rows alone; they must
    equal a full rebuild by search over the merged pool."""
    import jax.numpy as jnp

    from repro.core import flat_ctree as fct

    n, edges = small_graph
    g = fg.from_edges(n, edges[:-300], fct.grown_capacity(edges.shape[0] + 64))
    rows = np.concatenate([edges[-300:], edges[:100]])  # new edges and present ones
    if step == "insert_grows_n":
        rows = np.concatenate([rows, [[n + 3, 1], [n + 7, n + 2], [n + 7, 0]]])
    keys = (rows[:, 0] << 32) | rows[:, 1]
    batch = fct.from_device(jnp.asarray(keys), fct.grown_capacity(keys.size))
    donate = step == "donated"
    if step == "delete":
        out = fg.delete_edges_device(g, batch)
    else:
        n_out = n + 8 if step == "insert_grows_n" else None
        out = fg.insert_edges_device(g, batch, g.edge_capacity, n_out=n_out, donate=donate)
        if donate:
            out = fg.delete_edges_device(out, batch, donate=True)
    np.testing.assert_array_equal(
        np.asarray(out.offsets), np.asarray(fg._offsets_from_keys(out.keys, out.m, out.n))
    )
    assert int(out.offsets[-1]) == int(out.m)


def _index_rows(var) -> int:
    shape = var.aval.shape
    return int(np.prod(shape[:-1])) if len(shape) > 1 else int(np.prod(shape))


def _pool_length_work(closed, batch_cap: int) -> list:
    """Operations of a program, sub-programs included, that scatter or
    gather more index rows, or loop over more queries (a searchsorted's
    carried bounds), than the batch has rows."""
    from jax.extend import core as jcore

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name.startswith("scatter") or name == "gather":
                rows = _index_rows(eqn.invars[1])
                if rows > batch_cap:
                    found.append(f"{name}: {rows} index rows")
            elif name == "while":
                carry = eqn.invars[eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]:]
                found.extend(f"while carry of {v.aval.size}" for v in carry
                             if v.aval.size > batch_cap)
            elif name == "scan":
                k, c = eqn.params["num_consts"], eqn.params["num_carry"]
                found.extend(f"scan carry of {v.aval.size}" for v in eqn.invars[k:k + c]
                             if v.aval.size > batch_cap)
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub)

    walk(closed.jaxpr)
    return found


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("op", ["insert_edges", "delete_edges"])
def test_merge_programs_do_no_pool_length_search_or_scatter(op, weighted):
    """Regression guard: the insert and delete programs search only the
    batch rows and scatter or gather at most that many rows; the pool is
    only streamed.  The baseline's offsets rebuild (one search per
    vertex) is what the guard exists to catch."""
    import jax
    import jax.numpy as jnp

    from repro.core import flat_ctree as fct

    cap, n, bcap = 4096, 512, 64
    w = jax.ShapeDtypeStruct((cap,), jnp.float32) if weighted else None
    g = fg.FlatGraph(jax.ShapeDtypeStruct((n + 1,), jnp.int32),
                     jax.ShapeDtypeStruct((cap,), jnp.int64),
                     jax.ShapeDtypeStruct((), jnp.int32), w)
    bw = jax.ShapeDtypeStruct((bcap,), jnp.float32) if weighted else None
    batch = fct.FlatCTree(jax.ShapeDtypeStruct((bcap,), jnp.int64),
                          jax.ShapeDtypeStruct((), jnp.int32), bw)
    if op == "insert_edges":
        program = jax.make_jaxpr(fg.insert_edges, static_argnums=(2, 3, 4))
        assert _pool_length_work(program(g, batch, cap, True, n + 64), bcap) == []
        assert _pool_length_work(program(g, batch, cap, False, None), bcap)  # the guard bites
    else:
        program = jax.make_jaxpr(fg.delete_edges, static_argnums=(2,))
        assert _pool_length_work(program(g, batch, cap), bcap) == []
