"""Seeded graph and update-batch generators, kept with the benchmark so
that a change to the program cannot change the traffic.

* ``kronecker_edges``: the Graph500 Kronecker generator (graph500.org
  specification, reference ``kronecker_generator.m``): ``edgefactor * 2^scale``
  pairs, quadrant probabilities A, B, C (D = 1 - A - B - C) per bit,
  vertex labels permuted, pair order permuted.
* ``rmat_edges``: the rMAT generator of Aspen's §7.4 (a=0.5, b=c=0.1),
  a copy of the program's ``data/rmat.py`` that draws the same numbers.
* ``symmetrize``, ``hash_weights``: undirected edge sets and the integer
  weights 1..16 that ``chip_smoke.make_graph`` gives each pair.

Every function takes its random numbers from a ``numpy.random.Generator``
or a seed; nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

# streams of one run's randomness, split so that changing one part of a
# cell (say the query mix) leaves the graph of the same seed unchanged
GRAPH, UPDATES, QUERIES, SAMPLE = 0, 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of run seed ``seed`` (any int >= 0)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, int(stream)])


def kronecker_bits(scale: int, m: int, a: float, b: float, c: float,
                   rng: np.random.Generator) -> np.ndarray:
    """(m, 2) int64 Kronecker pairs over 2^scale vertices, before the label
    permutation (Graph500 ``kronecker_generator.m``, one bit per level)."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), dtype=np.int64)
    for level in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] |= ii.astype(np.int64) << level
        ij[1] |= jj.astype(np.int64) << level
    return ij.T.copy()


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float, c: float,
                    rng: np.random.Generator):
    """Graph500 edge list: returns ``(pairs, perm)`` where ``pairs`` is
    (edgefactor * 2^scale, 2) int64 with labels permuted by ``perm`` and
    rows in random order; self loops and duplicates are kept, as the
    specification generates them."""
    n = 1 << scale
    m = edgefactor * n
    ij = kronecker_bits(scale, m, a, b, c, rng)
    perm = rng.permutation(n).astype(np.int64)
    ij = perm[ij]
    return ij[rng.permutation(m)], perm


def rmat_edges(log_n: int, n_edges: int, a: float = 0.5, b: float = 0.1,
               c: float = 0.1, seed: int = 0) -> np.ndarray:
    """(n_edges, 2) int64 directed rMAT pairs over 2^log_n vertices, the
    same numbers as the program's ``data/rmat.py`` for the same seed
    (including its two unused draws per level)."""
    rng = np.random.default_rng(seed)
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    q_ab = a + b
    for _ in range(log_n):
        rng.random(n_edges)
        rng.random(n_edges)
        r = rng.random(n_edges)
        src_bit = (r >= q_ab).astype(np.int64)
        dst_bit = np.where(src_bit == 0, (r >= a).astype(np.int64),
                           (r >= q_ab + c).astype(np.int64))
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    return np.stack([src, dst], axis=1)


def symmetrize(edges: np.ndarray) -> np.ndarray:
    """Both directions of every pair, deduplicated, self loops dropped,
    sorted by (src, dst)."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    both = np.concatenate([e, e[:, ::-1]])
    keys = np.unique((both[:, 0] << 32) | both[:, 1])
    out = np.stack([keys >> 32, keys & 0xFFFFFFFF], axis=1)
    return out[out[:, 0] != out[:, 1]]


def hash_weights(edges: np.ndarray) -> np.ndarray:
    """One integer weight in 1..16 per pair, equal in both directions
    (the rule of ``chip_smoke.make_graph``)."""
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    return (1 + (lo * 2654435761 + hi * 40503) % 16).astype(np.float64)


class Graph:
    """A generated deployment graph: ``edges`` (symmetric, sorted,
    directed rows), optional ``weights``, and a ``batch(rng, pairs)``
    source of fresh update pairs from the same distribution."""

    def __init__(self, n, edges, weights, batch_fn):
        self.n = n
        self.edges = edges
        self.weights = weights
        self._batch_fn = batch_fn

    def batch(self, rng: np.random.Generator, pairs: int) -> np.ndarray:
        """Exactly ``pairs`` fresh undirected pairs (src < dst, no self
        loops; duplicates possible), drawn from the graph's generator."""
        out = np.empty((0, 2), np.int64)
        while out.shape[0] < pairs:
            raw = self._batch_fn(rng, 2 * pairs)
            raw = raw[raw[:, 0] != raw[:, 1]]
            out = np.concatenate([out, raw])
        out = out[:pairs]
        return np.stack([out.min(axis=1), out.max(axis=1)], axis=1)


def make_graph(cfg: dict, seed: int) -> Graph:
    """The configuration's graph from run seed ``seed``."""
    scale = int(cfg["scale"])
    n = 1 << scale
    rng = rng_for(seed, GRAPH)
    kind = cfg["generator"]
    if kind == "kronecker":
        a, b, c = cfg["a"], cfg["b"], cfg["c"]
        pairs, perm = kronecker_edges(scale, int(cfg["edgefactor"]), a, b, c, rng)

        def batch_fn(r, k):
            return perm[kronecker_bits(scale, k, a, b, c, r)]
    elif kind == "rmat":
        a, b, c = cfg["a"], cfg["b"], cfg["c"]
        m = int(cfg["pairs_per_vertex"]) * n
        pairs = rmat_edges(scale, m, a, b, c, seed=int(rng.integers(1 << 62)))

        def batch_fn(r, k):
            return rmat_edges(scale, k, a, b, c, seed=int(r.integers(1 << 62)))
    else:
        raise ValueError(f"unknown graph generator {kind!r}")
    edges = symmetrize(pairs)
    weights = hash_weights(edges) if cfg.get("weights") == "hash16" else None
    return Graph(n, edges, weights, batch_fn)
