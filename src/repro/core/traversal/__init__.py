"""Unified edgeMap traversal engine: one algorithm text, three backends.

See ``base.py`` for the backend contract, ``numpy_backend`` /
``jax_backend`` / ``sharded_backend`` for the substrates, and
``algorithms`` for the backend-generic BFS / PageRank / CC / SSSP / BC.

Quick start::

    from repro.core import graph as G, flat_graph as fg
    from repro.core import sharded_pool as sp
    from repro.core.traversal import make_engine, algorithms as talg

    eng_np = make_engine(G.flat_snapshot(g))       # CPU / FlatSnapshot
    eng_jx = make_engine(fg.from_edges(n, edges))  # TPU / FlatGraph
    eng_sh = make_engine(sp.graph_from_edges(n, edges))  # mesh / ShardedGraph
    assert (talg.bfs(eng_np, 0) >= 0).sum() == (talg.bfs(eng_jx, 0) >= 0).sum()
"""
from __future__ import annotations

from . import algorithms
from .base import (
    DENSE_THRESHOLD_DENOM,
    HOST_SYNCS,
    TRACES,
    ArrayOps,
    Counter,
    TraversalEngine,
    dense_threshold,
)
from .numpy_backend import (
    NumpyEngine,
    VertexSubset,
    edge_map,
    engine_of,
    from_dense,
    from_ids,
    gather_csr,
)

__all__ = [
    "DENSE_THRESHOLD_DENOM",
    "ArrayOps",
    "TraversalEngine",
    "dense_threshold",
    "NumpyEngine",
    "JaxEngine",
    "CompressedEngine",
    "ShardedEngine",
    "CompressedShardedEngine",
    "VertexSubset",
    "edge_map",
    "engine_of",
    "from_dense",
    "from_ids",
    "gather_csr",
    "algorithms",
    "make_engine",
    "flat_graph_of",
    "FLAT_REBUILDS",
    "ENGINE_BUILDS",
    "HOST_SYNCS",
    "TRACES",
]


# Counts FlatSnapshot -> FlatGraph host rebuilds (the O(m) path the
# resident mirror exists to avoid).  Tests spy on ``count`` to assert
# the mirror's engine path never falls back to a rebuild.
FLAT_REBUILDS = Counter()

# Counts engine constructions in the version-pinned engine cache
# (``AspenStream._engine_for``).  Tests spy on ``count`` to assert a
# mixed-kind batch against one version builds its engine exactly once.
ENGINE_BUILDS = Counter()


def __getattr__(name):
    # JaxEngine / ShardedEngine import jax + the Pallas kernel wrappers;
    # keep the numpy-only path importable without paying that (lazy).
    if name == "JaxEngine":
        from .jax_backend import JaxEngine

        return JaxEngine
    if name == "CompressedEngine":
        from .jax_backend import CompressedEngine

        return CompressedEngine
    if name == "ShardedEngine":
        from .sharded_backend import ShardedEngine

        return ShardedEngine
    if name == "CompressedShardedEngine":
        from .sharded_backend import CompressedShardedEngine

        return CompressedShardedEngine
    raise AttributeError(name)


def make_engine(obj, backend: str | None = None) -> TraversalEngine:
    """Engine for a snapshot object, dispatched on type (or forced by
    ``backend`` in {"numpy", "jax", "sharded"}).

    Accepts a ``FlatGraph`` (-> JaxEngine), a ``ShardedGraph``
    (-> ShardedEngine), anything with the FlatSnapshot protocol
    (-> NumpyEngine), or a tree-level ``Graph`` (snapshotted first;
    backend selects the substrate).
    """
    from ..flat_graph import CompressedPool, FlatGraph
    from ..graph import Graph, flat_snapshot
    from ..sharded_pool import CompressedShardedGraph, ShardedGraph

    if backend not in (None, "numpy", "jax", "sharded"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'numpy', 'jax' or 'sharded'"
        )
    if isinstance(obj, CompressedPool):
        if backend in ("numpy", "sharded"):
            raise TypeError("CompressedPool is jax-native; decompress first")
        from .jax_backend import CompressedEngine

        return CompressedEngine(obj)
    if isinstance(obj, CompressedShardedGraph):
        if backend in ("numpy", "jax"):
            raise TypeError("CompressedShardedGraph is sharded-native")
        from .sharded_backend import CompressedShardedEngine

        return CompressedShardedEngine(obj)
    if isinstance(obj, ShardedGraph):
        if backend in ("numpy", "jax"):
            raise TypeError("ShardedGraph is sharded-native; pass backend='sharded'")
        from .sharded_backend import ShardedEngine

        return ShardedEngine(obj)
    if isinstance(obj, FlatGraph):
        if backend == "numpy":
            raise TypeError("FlatGraph is jax-native; build a FlatSnapshot for numpy")
        if backend == "sharded":
            return make_engine(sharded_graph_of_flat(obj))
        from .jax_backend import JaxEngine

        return JaxEngine(obj)
    if isinstance(obj, Graph):
        snap = flat_snapshot(obj)
        if backend in ("jax", "sharded"):
            return make_engine(_flat_graph_of(snap), backend=backend)
        return engine_of(snap)
    if backend in ("jax", "sharded"):
        return make_engine(_flat_graph_of(obj), backend=backend)
    return engine_of(obj)


def sharded_graph_of_flat(g, n_shards: int | None = None):
    """FlatGraph -> ShardedGraph: range-partition the packed-key pool
    (and its value lane) over the mesh.  Host-side O(m); streams keep a
    resident sharded mirror precisely so queries never pay this per
    version."""
    from ..flat_graph import to_edge_array, to_weight_array
    from ..sharded_pool import graph_from_edges

    return graph_from_edges(
        g.n, to_edge_array(g), n_shards=n_shards, weights=to_weight_array(g)
    )


def flat_graph_of(snap, edge_capacity: int | None = None):
    """FlatSnapshot -> FlatGraph (host-side O(m) CSR rebuild; weighted
    snapshots carry their per-edge values into the pool's value array).
    ``edge_capacity`` is a floor on the pool capacity (default: the
    power of two above the edge count).

    This is the *fallback* substrate conversion — streams keep a
    resident mirror precisely so queries never pay this per version
    (``FLAT_REBUILDS`` counts how often anyone still does)."""
    import numpy as np

    from ..flat_ctree import grown_capacity
    from ..flat_graph import from_edges

    FLAT_REBUILDS.bump()
    offsets, nbrs = gather_csr(snap, np.arange(snap.n, dtype=np.int64))
    srcs = np.repeat(np.arange(snap.n, dtype=np.int64), np.diff(offsets))
    weights = (
        snap.edge_weights(srcs, nbrs)
        if getattr(snap, "weighted", False)
        else None
    )
    if edge_capacity is not None:
        edge_capacity = max(edge_capacity, grown_capacity(nbrs.size))
    return from_edges(
        snap.n, np.stack([srcs, nbrs], axis=1),
        edge_capacity=edge_capacity, weights=weights,
    )


_flat_graph_of = flat_graph_of  # backward-compatible alias
