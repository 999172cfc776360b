"""The plain reference the benchmark holds the service to.

``EdgeState`` is the edge set (and weights) the benchmark keeps itself,
from the graph it generated and every batch it sent, under the stream's
stated semantics: an insert adds both directions of each pair at unit
weight (overwriting the weight of a pair already there, since the
service's batch API carries no weights), a delete removes both
directions.  ``Snapshot`` freezes it at one version; ``bfs_depths`` and
``pagerank`` answer queries over a snapshot with scipy sparse products in
float64; ``check_bfs``, ``pagerank_gaps`` and ``mirror_diff`` compare the
program's answers with them.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

MASK32 = (1 << 32) - 1
DAMPING = 0.85
PR_ITERS = 10


def pack(pairs: np.ndarray) -> np.ndarray:
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    return (pairs[:, 0] << 32) | pairs[:, 1]


def both_ways(pairs: np.ndarray) -> np.ndarray:
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    return np.unique(pack(np.concatenate([pairs, pairs[:, ::-1]])))


class EdgeState:
    """The benchmark's own record of the graph: the generated edges as a
    sorted key array with an ``alive`` mask, plus a small dict of edges
    inserted that the generated graph did not hold."""

    def __init__(self, n: int, edges: np.ndarray, weights=None):
        keys = pack(edges)
        order = np.argsort(keys, kind="stable")
        self.n = n
        self.base = keys[order]
        self.w = None if weights is None else np.asarray(weights, np.float64)[order]
        self.alive = np.ones(self.base.size, bool)
        self.extra: dict = {}

    def _locate(self, keys):
        idx = np.minimum(np.searchsorted(self.base, keys), max(self.base.size - 1, 0))
        inb = self.base[idx] == keys if self.base.size else np.zeros(keys.size, bool)
        return idx, inb

    def insert(self, pairs) -> None:
        keys = both_ways(pairs)
        idx, inb = self._locate(keys)
        self.alive[idx[inb]] = True
        if self.w is not None:
            self.w[idx[inb]] = 1.0
        for k in keys[~inb].tolist():
            self.extra[k] = 1.0

    def delete(self, pairs) -> None:
        keys = both_ways(pairs)
        idx, inb = self._locate(keys)
        self.alive[idx[inb]] = False
        for k in keys[~inb].tolist():
            self.extra.pop(k, None)

    def snapshot(self) -> "Snapshot":
        return Snapshot(self)


class Snapshot:
    """One version of ``EdgeState``: sorted keys, weights (None when
    unweighted) and the sparse adjacency used by the queries."""

    def __init__(self, st: EdgeState):
        ek = np.asarray(sorted(st.extra), dtype=np.int64)
        ew = np.asarray([st.extra[k] for k in ek.tolist()], np.float64)
        keys = np.concatenate([st.base[st.alive], ek])
        order = np.argsort(keys, kind="stable")
        self.n = st.n
        self.keys = keys[order]
        self.weights = None
        if st.w is not None:
            self.weights = np.concatenate([st.w[st.alive], ew])[order]
        self._adj = None

    @property
    def m(self) -> int:
        return int(self.keys.size)

    def adjacency(self) -> sp.csr_matrix:
        """A[u, v] = weight of edge u -> v (1 when unweighted)."""
        if self._adj is None:
            src = (self.keys >> 32).astype(np.int64)
            dst = (self.keys & MASK32).astype(np.int64)
            data = np.ones(self.m) if self.weights is None else self.weights
            indptr = np.searchsorted(src, np.arange(self.n + 1)).astype(np.int64)
            self._adj = sp.csr_matrix((data, dst, indptr), shape=(self.n, self.n))
        return self._adj

    def has_edges(self, src, dst) -> np.ndarray:
        q = (np.asarray(src, np.int64) << 32) | np.asarray(dst, np.int64)
        idx = np.minimum(np.searchsorted(self.keys, q), max(self.m - 1, 0))
        return self.keys[idx] == q if self.m else np.zeros(q.size, bool)


# -- queries -----------------------------------------------------------------


def bfs_depths(snap: Snapshot, sources) -> np.ndarray:
    """Hop depths int64[B, n] from each source (-1 = unreached), level by
    level: next = (A^T frontier > 0) and not yet visited."""
    sources = np.asarray(sources, np.int64).reshape(-1)
    n, B = snap.n, sources.size
    a = snap.adjacency()
    at = sp.csr_matrix((np.ones_like(a.data), a.indices, a.indptr), shape=a.shape).T
    depth = np.full((n, B), -1, np.int64)
    depth[sources, np.arange(B)] = 0
    frontier = np.zeros((n, B))
    frontier[sources, np.arange(B)] = 1.0
    level = 0
    while frontier.any():
        level += 1
        reach = (at @ frontier) > 0
        new = reach & (depth < 0)
        depth[new] = level
        frontier = new.astype(np.float64)
    return depth.T


def _round_bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def pagerank(snap: Snapshot, sources, precision: str = "float64") -> np.ndarray:
    """Personalized PageRank rows float[B, n], the service's semantics:
    ``PR_ITERS`` rounds of pr <- (1-d) r + d (A^T (pr / outdeg) + dang r),
    with the one-hot reset row r at each source, dangling mass returned
    through r, and weighted out-degree on weighted graphs.

    ``precision`` is ``"float64"`` (the reference) or ``"high"``, the
    control: float32 with each message split into two bfloat16 parts
    before the reduction, as a three-pass (``Precision.HIGH``) product
    reads it."""
    sources = np.asarray(sources, np.int64).reshape(-1)
    n, B = snap.n, sources.size
    dt = np.float64 if precision == "float64" else np.float32
    a = snap.adjacency()
    at = a.T.astype(dt)
    deg = np.asarray(a.sum(axis=1)).reshape(-1).astype(dt)
    dangling = deg == 0
    denom = np.where(dangling, 1.0, deg).astype(dt)[:, None]
    reset = np.zeros((n, B), dt)
    reset[sources, np.arange(B)] = 1.0
    pr = reset.copy()
    d = dt(DAMPING)
    for _ in range(PR_ITERS):
        w = np.where(dangling[:, None], dt(0), pr / denom).astype(dt)
        if precision == "high":
            hi = _round_bf16(w)
            w = hi + _round_bf16(w - hi)
        contrib = (at @ w).astype(dt)
        dang = pr[dangling].sum(axis=0, dtype=dt)[None, :]
        pr = ((dt(1) - d) * reset + d * (contrib + dang * reset)).astype(dt)
    return pr.T


# -- comparisons ---------------------------------------------------------------


def check_bfs(snap: Snapshot, source: int, parents: np.ndarray, depth: np.ndarray) -> int:
    """Vertices at which ``parents`` is not a BFS tree of ``snap`` from
    ``source`` with the reference ``depth``: a reached set that differs,
    a parent that is not one level up, or a tree edge the graph lacks."""
    parents = np.asarray(parents, np.int64).reshape(-1)
    if parents.shape != depth.shape:
        return int(depth.size)
    reached = parents >= 0
    bad = reached != (depth >= 0)
    bad[source] |= parents[source] != source
    v = np.flatnonzero(reached & (depth > 0))
    p = parents[v]
    ok = (p >= 0) & (p < snap.n)
    pd = np.where(ok, depth[np.clip(p, 0, snap.n - 1)], -2)
    ok &= pd == depth[v] - 1
    ok &= snap.has_edges(np.clip(p, 0, None), v)
    bad[v[~ok]] = True
    return int(bad.sum())


def pagerank_gaps(got, want) -> dict:
    """How far a score row lies from the reference row: the largest
    absolute difference (``max``), the L1 distance of the two
    distributions (``l1``), and the mean relative difference over the
    vertices the reference gives a score above 0 (``rel``).  All are inf
    for a shape mismatch or a value that is not finite."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"max": np.inf, "l1": np.inf, "rel": np.inf}
    d = np.abs(got - want)
    pos = want > 0
    return {"max": float(d.max()), "l1": float(d.sum()),
            "rel": float((d[pos] / want[pos]).mean()) if pos.any() else 0.0}


def mirror_diff(snap: Snapshot, keys, weights, offsets) -> int:
    """Edges, weights and CSR offsets of the device mirror that differ
    from ``snap``: keys in one set and not the other, weights that differ
    on common keys, and offsets that do not bound each source's keys."""
    keys = np.asarray(keys, np.int64).reshape(-1)
    if keys.size == snap.m and np.array_equal(keys, snap.keys):
        ia = ib = slice(None)
        diff = 0
    else:
        common, ia, ib = np.intersect1d(keys, snap.keys, return_indices=True)
        diff = (keys.size - common.size) + (snap.m - common.size)
        diff += int(np.unique(keys).size != keys.size) * keys.size
    if snap.weights is not None:
        if weights is None:
            diff += snap.m
        else:
            diff += int((np.asarray(weights, np.float64)[ia] != snap.weights[ib]).sum())
    want_offsets = np.searchsorted(snap.keys >> 32, np.arange(snap.n + 1))
    offsets = np.asarray(offsets, np.int64).reshape(-1)
    if offsets.shape != want_offsets.shape:
        diff += snap.n + 1
    else:
        diff += int((offsets != want_offsets).sum())
    return int(diff)
