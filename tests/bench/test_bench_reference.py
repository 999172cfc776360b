"""The plain reference against brute force on small graphs, the byte
count of the segment sum against a hand count, and the peaks table."""
import collections

import numpy as np
import pytest

from _tiny import ROOT  # noqa: F401
from bench import costs, gen, peaks
from bench import reference as R


def _graph(seed=1, scale=7):
    cfg = {"generator": "kronecker", "scale": scale, "edgefactor": 4,
           "a": 0.57, "b": 0.19, "c": 0.19}
    return gen.make_graph(cfg, seed)


def _bfs(n, edges, s):
    adj = collections.defaultdict(list)
    for u, v in edges.tolist():
        adj[u].append(v)
    depth = [-1] * n
    depth[s] = 0
    dq = collections.deque([s])
    while dq:
        u = dq.popleft()
        for v in adj[u]:
            if depth[v] < 0:
                depth[v] = depth[u] + 1
                dq.append(v)
    return np.asarray(depth)


def test_bfs_depths_match_a_queue_bfs():
    g = _graph()
    snap = R.EdgeState(g.n, g.edges).snapshot()
    srcs = [int(g.edges[0, 0]), int(g.edges[-1, 0]), 0]
    got = R.bfs_depths(snap, srcs)
    for row, s in zip(got, srcs):
        assert np.array_equal(row, _bfs(g.n, g.edges, s))


def test_pagerank_matches_dense_power_iteration():
    g = _graph()
    n = g.n
    A = np.zeros((n, n))
    A[g.edges[:, 0], g.edges[:, 1]] = 1.0
    deg = A.sum(1)
    s = int(g.edges[3, 0])
    r = np.zeros(n)
    r[s] = 1.0
    pr = r.copy()
    for _ in range(R.PR_ITERS):
        w = np.where(deg > 0, pr / np.where(deg > 0, deg, 1), 0)
        dang = pr[deg == 0].sum()
        pr = (1 - R.DAMPING) * r + R.DAMPING * (A.T @ w + dang * r)
    got = R.pagerank(R.EdgeState(n, g.edges).snapshot(), [s])[0]
    assert np.allclose(got, pr, rtol=1e-12, atol=1e-15)
    assert R.pagerank_gaps(got, pr)["max"] < 1e-12
    # the control reads further from it
    snap = R.EdgeState(n, g.edges).snapshot()
    high = R.pagerank_gaps(R.pagerank(snap, [s], "high")[0], pr)
    assert high["l1"] > 1e-9


def test_check_bfs_counts_each_fault():
    g = _graph()
    snap = R.EdgeState(g.n, g.edges).snapshot()
    s = int(g.edges[0, 0])
    depth = R.bfs_depths(snap, [s])[0]
    parents = np.full(g.n, -1)
    parents[s] = s
    for v in np.flatnonzero(depth > 0):  # any neighbour one level up
        nb = g.edges[g.edges[:, 1] == v, 0]
        parents[v] = nb[depth[nb] == depth[v] - 1][0]
    assert R.check_bfs(snap, s, parents, depth) == 0
    v = int(np.flatnonzero(depth == 2)[0])
    bad = parents.copy()
    bad[v] = s  # not an edge, or not one level up
    assert R.check_bfs(snap, s, bad, depth) == 1
    lost = parents.copy()
    lost[v] = -1  # a reached vertex left out
    assert R.check_bfs(snap, s, lost, depth) == 1
    assert R.check_bfs(snap, s, parents[:-1], depth) == g.n


def test_edge_state_follows_the_streams_rules():
    edges = gen.symmetrize(np.array([[0, 1], [1, 2]]))
    st = R.EdgeState(4, edges, weights=np.array([5.0, 5.0, 7.0, 7.0]))
    st.insert(np.array([[1, 2], [2, 3]]))  # overwrite (1,2), add (2,3)
    snap = st.snapshot()
    assert snap.keys.tolist() == R.pack(np.array(
        [[0, 1], [1, 0], [1, 2], [2, 1], [2, 3], [3, 2]])).tolist()
    assert snap.weights.tolist() == [5.0, 5.0, 1.0, 1.0, 1.0, 1.0]
    st.delete(np.array([[0, 1], [2, 3]]))
    assert st.snapshot().keys.tolist() == R.pack(np.array([[1, 2], [2, 1]])).tolist()
    st.insert(np.array([[0, 1]]))
    assert st.snapshot().weights.tolist() == [1.0, 1.0, 1.0, 1.0]


def test_mirror_diff_counts_keys_weights_and_offsets():
    edges = gen.symmetrize(np.array([[0, 1], [1, 2], [2, 3]]))
    snap = R.EdgeState(4, edges, weights=np.ones(6)).snapshot()
    offs = np.searchsorted(snap.keys >> 32, np.arange(5))
    assert R.mirror_diff(snap, snap.keys, snap.weights, offs) == 0
    assert R.mirror_diff(snap, snap.keys[1:], snap.weights[1:], offs) == 1
    w = snap.weights.copy()
    w[2] = 3.0
    assert R.mirror_diff(snap, snap.keys, w, offs) == 1
    o = offs.copy()
    o[2] += 1
    assert R.mirror_diff(snap, snap.keys, snap.weights, o) == 1


def test_segment_sum_bytes_by_hand():
    # 1024 edges of D=4 float32 messages onto 256 rows:
    # 1024 int32 indices (4096 B) + 1024*4 floats (16384 B) + 256*4 floats (4096 B)
    assert costs.segment_sum_bytes(1024, 4, 256) == 4096 + 16384 + 4096


def test_peaks_of_a_v5e_and_no_default():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
