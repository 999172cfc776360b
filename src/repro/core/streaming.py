"""Aspen streaming interface (paper §6 + §7.3): updates ∥ queries.

``AspenStream`` is the top-level object: a VersionedGraph plus the
Ligra-style update API (InsertEdges / DeleteEdges / InsertVertices /
DeleteVertices).  Updates are functional: each batch produces a new
version published with SET; readers ACQUIRE snapshots and never block.

Dual representation (DESIGN.md §6): alongside the faithful C-tree
``Graph``, every version carries a device-resident ``FlatGraph`` mirror
kept current *incrementally* — each edge batch is applied to the tree
(functional, faithful) AND rank-merged into the mirror on device
(O(n+k), amortized capacity doubling), then both are published
atomically as ONE version.  ``engine("jax")`` over an unchanged version
is O(1): engines are cached on the version itself (version-pinned, so
the cache dies with the version), and a fresh version's engine refresh
is one jit ``engine_aux`` call over the already-merged mirror — no O(m)
host rebuild, no host argsort.  Streams opened with ``mirror=False``
keep the historical rebuild-per-query path.

Sharded mirror (DESIGN.md §9): ``mirror="sharded"`` maintains a
range-sharded ``ShardedGraph`` mirror instead — updates go through the
shard-local rank-merge / delete steps of ``sharded_pool`` (O(batch)
collective traffic, amortized host-driven rebalance), queries through
``engine("sharded")``, the mesh-parallel edgeMap backend.  Both are
published atomically next to the tree exactly like the flat mirror, and
``query_batch`` routes to the sharded engine by default on such
streams.

Incremental queries (DESIGN.md §11): every edge publish records its
batch as a ``versioning.Delta`` in the version's aux, and
``stream.subscribe(kind, ...)`` returns a ``Subscription`` whose
``refresh()`` advances a standing result (pagerank / cc / bfs / sssp)
across publishes through the delta-aware warm-start path instead of
recomputing — time-to-fresh-result scales with the batch, not the
graph.

``run_concurrent`` reproduces the paper's §7.3 experiment: one writer
thread applying a stream of edge updates while reader threads run global
queries; reports update throughput, per-edge visibility latency, and
query latencies (concurrent vs isolated) — plus subscriber staleness
when the reader is a live ``Subscription``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, NamedTuple, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from . import graph as G
from .versioning import DELTA, Delta, Version, VersionedGraph

MIRROR = "flat"  # aux key of the FlatGraph mirror on a Version
SHARDED_MIRROR = "sharded"  # aux key of the ShardedGraph mirror

# hi-plane slack for adaptive compressed mirrors: fraction of chunk rows
# reserved beyond the build-time wide-chunk count, so incremental
# recompression absorbs width drift between full rebuilds
HI_HEADROOM = 1 / 16


class UpdateQueue:
    """Bounded thread-safe queue of pending edge updates feeding a
    writer loop — the backpressure surface of the serving layer
    (DESIGN.md §13).

    One entry per directed-or-symmetric *update request*: ``(src, dst,
    delete, weight)``.  Producers ``put`` (blocking while full unless
    ``block=False``, which rejects instead — the caller's admission
    decision); the single writer drains with ``drain_updates`` below.
    ``stats()`` exposes queue depth, high-water mark, and the
    accepted / drained / rejected totals, so a service can report how
    hard its writer is backpressuring producers.  ``maxsize=None``
    makes the queue unbounded (the replay use in ``run_concurrent``)."""

    def __init__(self, maxsize: Optional[int] = 65536):
        self.maxsize = maxsize
        self._q: deque = deque()
        self._cond = threading.Condition()
        self._high_water = 0
        self._enqueued = 0
        self._drained = 0
        self._rejected = 0

    def __len__(self) -> int:
        with self._cond:
            return len(self._q)

    def put(
        self,
        src: int,
        dst: int,
        *,
        delete: bool = False,
        weight: Optional[float] = None,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> bool:
        """Enqueue one update; returns False (and counts a rejection)
        instead of enqueueing when the queue stays full — on
        ``block=False`` immediately, else after ``timeout``."""
        with self._cond:
            if self.maxsize is not None:
                if not block and len(self._q) >= self.maxsize:
                    self._rejected += 1
                    return False
                if not self._cond.wait_for(
                    lambda: len(self._q) < self.maxsize, timeout=timeout
                ):
                    self._rejected += 1
                    return False
            self._q.append((int(src), int(dst), bool(delete), weight))
            self._enqueued += 1
            self._high_water = max(self._high_water, len(self._q))
            self._cond.notify_all()
            return True

    def put_many(
        self,
        edges: np.ndarray,
        *,
        delete: bool = False,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> int:
        """Enqueue a (k, 2) batch of updates as one unit (in pieces of at
        most ``maxsize``), so a writer drain never splits it into several
        publishes of odd sizes.  Returns how many were enqueued; a piece
        that finds no room (``block=False``, or after ``timeout``) is
        rejected whole."""
        rows = [(int(s), int(d), bool(delete), None)
                for s, d in np.asarray(edges, dtype=np.int64).reshape(-1, 2)]
        step = max(len(rows) if self.maxsize is None else self.maxsize, 1)
        done = 0
        for i in range(0, len(rows), step):
            piece = rows[i:i + step]
            with self._cond:
                if self.maxsize is not None:
                    room = lambda: len(self._q) + len(piece) <= self.maxsize  # noqa: E731
                    if (not block and not room()) or not self._cond.wait_for(
                        room, timeout=timeout
                    ):
                        self._rejected += len(piece)
                        continue
                self._q.extend(piece)
                self._enqueued += len(piece)
                self._high_water = max(self._high_water, len(self._q))
                self._cond.notify_all()
                done += len(piece)
        return done

    def pop_batch(self, k: int) -> list:
        """Dequeue up to ``k`` pending updates (possibly empty; never
        blocks) in FIFO order."""
        with self._cond:
            out = []
            while self._q and len(out) < k:
                out.append(self._q.popleft())
            if out:
                self._drained += len(out)
                self._cond.notify_all()  # wake producers blocked on full
            return out

    def wait_nonempty(self, timeout: Optional[float] = None) -> bool:
        """Park until at least one update is pending (the writer loop's
        idle wait); True when woken non-empty."""
        with self._cond:
            return self._cond.wait_for(lambda: len(self._q) > 0, timeout=timeout)

    def stats(self) -> dict:
        with self._cond:
            return {
                "depth": len(self._q),
                "maxsize": self.maxsize,
                "high_water": self._high_water,
                "enqueued": self._enqueued,
                "drained": self._drained,
                "rejected": self._rejected,
            }


def drain_updates(
    queue: UpdateQueue,
    stream: "AspenStream",
    max_batch: int,
    symmetric: bool = True,
) -> int:
    """Drain up to ``max_batch`` pending updates from ``queue`` and
    apply them to ``stream`` as (at most) one ``insert_edges`` plus one
    ``delete_edges`` publish; returns how many updates were applied
    (0 = queue empty; never blocks).

    This is THE writer-loop body — ``run_concurrent``'s updater thread
    and ``GraphQueryService``'s writer thread both call it, so update
    batching semantics (inserts applied before deletes within a drain,
    symmetrization forwarded to both calls, the weight lane riding
    inserts with unit fill for weight-less rows in a mixed batch) live
    in exactly one place and cannot drift between the bench harness and
    the serving path."""
    rows = queue.pop_batch(max_batch)
    if not rows:
        return 0
    ins = [(s, d, w) for s, d, dl, w in rows if not dl]
    dels = [(s, d) for s, d, dl, w in rows if dl]
    if ins:
        edges = np.asarray([(s, d) for s, d, _ in ins], dtype=np.int64)
        if any(w is not None for _, _, w in ins):
            weights = np.asarray(
                [1.0 if w is None else float(w) for _, _, w in ins], np.float64
            )
        else:
            weights = None
        stream.insert_edges(edges, symmetric=symmetric, weights=weights)
    if dels:
        stream.delete_edges(np.asarray(dels, dtype=np.int64), symmetric=symmetric)
    return len(rows)


class AspenStream:
    def __init__(
        self,
        initial: Optional[G.Graph] = None,
        b: int = 256,
        seed: int = 0x9E3779B9,
        mirror: "bool | str" = True,
        donate_buffers: bool = False,
        n_shards: Optional[int] = None,
        compressed: bool = False,
        edge_capacity: Optional[int] = None,
    ):
        """``mirror=True`` (default, = ``"flat"``) maintains the resident
        FlatGraph alongside the tree; ``mirror="sharded"`` maintains a
        range-sharded ``ShardedGraph`` mirror instead (updates via the
        shard-local rank-merge, queries via ``engine("sharded")``;
        ``n_shards`` defaults to the device count).  ``mirror=False``
        keeps the rebuild-per-query path.  ``donate_buffers=True``
        additionally donates the old flat-mirror pool to each merge —
        ONLY safe when no reader can still hold a previous version
        (single-reader pipelines), since donation invalidates the shared
        buffer.

        ``compressed=True`` keeps the mirror in the chunk-compressed
        layout (``flat_graph.CompressedPool`` /
        ``sharded_pool.CompressedShardedPool``, DESIGN.md §10): each
        edge batch runs the decompress -> rank-merge -> recompress jit,
        so the RESIDENT state is always a few bytes/edge, and
        ``engine()`` serves the matching compressed engine.  Donation is
        unavailable on compressed mirrors (the merge's uncompressed pool
        is a transient, never a reusable buffer).

        ``edge_capacity`` floors the flat mirror's pool capacity (default:
        the power of two above the edge count).  Capacity is a static
        shape of every compiled query, so a stream that will grow past
        the default reserves the room up front; growth beyond it still
        re-quantizes to the next power of two (and recompiles)."""
        g0 = initial if initial is not None else G.empty(b, seed)
        kind = {True: MIRROR, False: None}.get(mirror, mirror)
        if kind not in (None, MIRROR, SHARDED_MIRROR):
            raise ValueError(
                f"mirror must be bool, 'flat' or 'sharded'; got {mirror!r}"
            )
        if compressed and kind is None:
            raise ValueError("compressed=True requires a resident mirror")
        if compressed and donate_buffers:
            raise ValueError("donate_buffers is unavailable on compressed mirrors")
        self._mirror_kind = kind
        self._mirror_enabled = kind is not None
        self._edge_capacity = edge_capacity
        self._compressed = compressed
        self._donate = donate_buffers
        if kind == SHARDED_MIRROR:
            from . import sharded_pool as sp

            self._n_shards = n_shards if n_shards is not None else sp.default_n_shards()
            self._smesh = sp.pool_mesh(self._n_shards)
            self._s_insert = sp.make_insert_step(self._smesh, ("shard",))
            self._s_delete = sp.make_delete_step(self._smesh, ("shard",))
            if compressed:
                self._s_insert_c = sp.make_insert_step_compressed(
                    self._smesh, ("shard",)
                )
                self._s_delete_c = sp.make_delete_step_compressed(
                    self._smesh, ("shard",)
                )
        aux = {kind: self._mirror_from_tree(g0)} if kind else None
        self.vg: VersionedGraph[G.Graph] = VersionedGraph(g0, aux=aux)
        self._wlock = threading.Lock()  # serializes writers (incl. mirror merge)
        self._publish_listeners: List[Callable[[Version[G.Graph]], None]] = []
        self._listener_lock = threading.Lock()

    # -- publish notification ----------------------------------------------
    def on_publish(self, fn: Callable[[Version[G.Graph]], None]) -> Callable[[], None]:
        """Register a non-blocking publish listener: ``fn(version)`` is
        called on the WRITER thread after each version becomes current
        (outside the write lock, so listeners can acquire/query).  The
        contract is fire-and-forget: listeners must be fast — set an
        event, bump a counter — never compute; exceptions are swallowed
        so a broken listener cannot take down the writer.  Returns an
        unsubscribe callable (idempotent)."""
        with self._listener_lock:
            self._publish_listeners.append(fn)

        def unsubscribe() -> None:
            with self._listener_lock:
                if fn in self._publish_listeners:
                    self._publish_listeners.remove(fn)

        return unsubscribe

    def _notify_publish(self, v: Version[G.Graph]) -> None:
        with self._listener_lock:
            listeners = list(self._publish_listeners)
        for fn in listeners:
            try:
                fn(v)
            except Exception:  # noqa: BLE001 — listener bugs never block the writer
                pass

    # -- mirror maintenance -------------------------------------------------
    def _flat_from_tree(self, g: G.Graph):
        """Full FlatGraph rebuild (O(m) host): construction and the rare
        vertex-set operations; edge batches take the incremental path."""
        from .traversal import flat_graph_of

        return flat_graph_of(G.flat_snapshot(g), edge_capacity=self._edge_capacity)

    def _mirror_from_tree(self, g: G.Graph):
        """Full mirror rebuild in the stream's configured representation.
        On compressed streams the rebuild is also the spill recovery
        point: ``compress_host`` / ``compress_sharded`` re-check the
        escape-lane flag from scratch and raise rather than publish a
        mis-decoding mirror."""
        flat = self._flat_from_tree(g)
        if self._mirror_kind == SHARDED_MIRROR:
            from .traversal import sharded_graph_of_flat

            sg = sharded_graph_of_flat(flat, self._n_shards)
            if self._compressed:
                from . import sharded_pool as sp

                # Adaptive per-chunk widths with hi-plane headroom: the
                # mirror keeps slack wide-chunk rows so incremental
                # recompression absorbs width drift between rebuilds.
                return sp.compress_sharded(sg, hi_headroom=HI_HEADROOM)
            return sg
        if self._compressed:
            from . import flat_graph as fg

            return fg.compress_host(flat, hi_headroom=HI_HEADROOM)
        return flat

    @staticmethod
    def _device_batch(edges: np.ndarray, weights: Optional[np.ndarray] = None):
        """Pack an edge batch and ship it to device at a *quantized*
        shape (padded with the pool sentinel, which ``fct.from_device``
        drops): batch sizes 1..k all share O(log k) jit traces instead
        of one per distinct size.  ``weights`` rides along as the batch
        pool's value array (pad 0; dropped with the sentinel keys)."""
        import jax.numpy as jnp

        from . import flat_ctree as fct

        keys = (edges[:, 0] << 32) | edges[:, 1]
        cap = fct.grown_capacity(keys.size)
        padded = np.full(cap, fct.SENTINEL64, dtype=np.int64)
        padded[: keys.size] = keys
        if weights is None:
            return fct.from_device(jnp.asarray(padded), cap)
        wpad = np.zeros(cap, dtype=np.float32)
        wpad[: keys.size] = weights
        return fct.from_device(jnp.asarray(padded), cap, vals=jnp.asarray(wpad))

    def _mirror_insert(
        self,
        mirror,
        g_old: G.Graph,
        edges: np.ndarray,
        weights: Optional[np.ndarray] = None,
    ):
        """Apply an insert batch to the mirror on device: pack keys, build
        the batch pool with the jit sort/dedup, rank-merge.  Capacity and
        vertex growth are decided from host-known counts (tree edge count
        via the O(1) augmentation; max source id from the batch), so no
        device->host sync is needed.

        A weighted batch against an unweighted mirror upgrades the
        mirror to unit weights first (the rank-merge then permutes the
        value array alongside the keys; an existing edge's weight is
        overwritten).  Unweighted streams never take these branches —
        no value array is allocated, and the merge compiles the exact
        pre-v2 traces."""
        from . import flat_ctree as fct
        from . import flat_graph as fg

        if edges.shape[0] == 0:
            return mirror
        compressed = isinstance(mirror, fg.CompressedPool)
        if weights is not None and mirror.weights is None:
            mirror = (
                fg.with_unit_weights_compressed(mirror)
                if compressed
                else fg.with_unit_weights(mirror)
            )
        batch = self._device_batch(edges, weights)
        # vertices are created by their first out-edge (matching the
        # tree, whose vertex set is the set of inserted sources)
        n_out = max(mirror.n, int(edges[:, 0].max()) + 1)
        need = G.num_edges(g_old) + edges.shape[0]
        cap = max(mirror.edge_capacity, fct.grown_capacity(need))
        if compressed:
            # decompress -> merge -> recompress, one jit; no donation
            # (the uncompressed pool is a transient of the trace, not a
            # reusable buffer)
            return fg.insert_edges_compressed(
                mirror, batch, cap, True,
                None if n_out == mirror.n else n_out,
            )
        return fg.insert_edges_device(
            mirror, batch, cap,
            n_out=None if n_out == mirror.n else n_out,
            donate=self._donate,
        )

    def _mirror_delete(self, mirror, edges: np.ndarray):
        from . import flat_graph as fg

        if edges.shape[0] == 0:
            return mirror
        if isinstance(mirror, fg.CompressedPool):
            return fg.delete_edges_compressed(
                mirror, self._device_batch(edges), mirror.edge_capacity
            )
        return fg.delete_edges_device(
            mirror, self._device_batch(edges), donate=self._donate
        )

    def _sharded_insert(
        self,
        mirror,
        g_old: G.Graph,
        edges: np.ndarray,
        weights: Optional[np.ndarray] = None,
    ):
        """Apply an insert batch to the sharded mirror: pack keys, build
        the batch pool with the jit sort/dedup, shard-local rank-merge
        (ONE batch all-gather on the wire — O(batch), not O(pool)).

        Capacity policy: one host read of the per-shard counts per
        batch; when the fullest shard could overflow, the pool takes an
        amortized REBALANCE (O(n) redistribution to equal counts, the
        LSM-compaction analogue) at a grown per-shard capacity first.
        A weighted batch against an unweighted mirror upgrades the pool
        to unit values (the value lane then rides every merge)."""
        from . import flat_ctree as fct
        from . import sharded_pool as sp

        if edges.shape[0] == 0:
            return mirror
        pool = mirror.pool
        compressed = isinstance(pool, sp.CompressedShardedPool)
        batch = self._device_batch(edges, weights)
        counts = np.asarray(pool.n)
        k = int(edges.shape[0])
        n_out = max(mirror.n, int(edges[:, 0].max()) + 1)
        if compressed:
            import jax.numpy as jnp

            if weights is not None and pool.vals is None:
                pool = pool._replace(
                    vals=jnp.ones(
                        (pool.n_shards, pool.cap_per), jnp.float32
                    )
                )
            if int(counts.max()) + k > pool.cap_per:
                per = -(-int(counts.sum()) // self._n_shards)
                pool = sp.rebalance_compressed(
                    pool, mirror.n,
                    cap_per=max(pool.cap_per, fct.grown_capacity(per + k)),
                )
            elif sp.should_rebalance(pool):
                # Auto-rebalance policy: the per-batch host read of the
                # counts doubles as the imbalance probe — rebalance when
                # skew (max/mean occupancy) crosses the threshold, long
                # before any shard hits capacity.
                pool = sp.rebalance_compressed(pool, mirror.n)
            pool = self._s_insert_c(pool, batch.data, batch.vals, n=n_out)
            return sp.CompressedShardedGraph(pool, n_out)
        if weights is not None and pool.vals is None:
            pool = sp.with_unit_vals(pool)
        cap_per = pool.data.shape[1]
        if int(counts.max()) + k > cap_per:
            per = -(-int(counts.sum()) // self._n_shards)
            pool = sp.rebalance(
                pool, cap_per=max(cap_per, fct.grown_capacity(per + k))
            )
        elif sp.should_rebalance(pool):
            pool = sp.rebalance(pool)
        pool = self._s_insert(pool, batch.data, batch.vals)
        return sp.ShardedGraph(pool, n_out)

    def _sharded_delete(self, mirror, edges: np.ndarray):
        from . import sharded_pool as sp

        if edges.shape[0] == 0:
            return mirror
        batch = self._device_batch(edges)
        if isinstance(mirror.pool, sp.CompressedShardedPool):
            return sp.CompressedShardedGraph(
                self._s_delete_c(mirror.pool, batch.data, n=mirror.n), mirror.n
            )
        return sp.ShardedGraph(self._s_delete(mirror.pool, batch.data), mirror.n)

    def _apply_insert(self, mirror, g_old, edges, weights=None):
        if self._mirror_kind == SHARDED_MIRROR:
            return self._sharded_insert(mirror, g_old, edges, weights)
        return self._mirror_insert(mirror, g_old, edges, weights)

    def _apply_delete(self, mirror, edges):
        if self._mirror_kind == SHARDED_MIRROR:
            return self._sharded_delete(mirror, edges)
        return self._mirror_delete(mirror, edges)

    def _heal_spill(self, m, g2: G.Graph):
        """Compressed-mirror self-heal: incremental recompression can
        overflow the escape lane or (adaptive streams) the hi plane —
        the step folds that into the sticky ``spill`` flag rather than
        branching in-trace.  One host flag-read per publish catches it
        here, and the mirror is rebuilt from the tree (which re-selects
        widths and re-sizes the hi plane from scratch) BEFORE the spilled
        state can be published — readers never observe a mis-decoding
        mirror."""
        if not self._compressed or m is None:
            return m
        from . import flat_graph as fg
        from . import sharded_pool as sp

        if isinstance(m, fg.CompressedPool):
            spilled = bool(np.asarray(m.dst.spill))
        elif isinstance(m, sp.CompressedShardedGraph):
            spilled = bool(np.asarray(m.pool.dst.spill).any())
        else:
            return m
        return self._mirror_from_tree(g2) if spilled else m

    def _publish(
        self, tree_fn, mirror_fn, delta: Optional[Delta] = None, *, op: str, rows: int
    ) -> Version[G.Graph]:
        """One writer transaction: update tree + mirror from the held
        version, publish both atomically as a single new version.

        Profiler spans (``jax.profiler.TraceAnnotation``; recorded only
        while a trace runs): ``aspen.publish`` (``op``, ``rows``: the
        directed edge rows) around the whole transaction, lock wait and
        listeners included; inside it ``aspen.publish.tree`` around the
        host C-tree update and ``aspen.publish.mirror`` around the
        mirror's batch packing, host->device copy and merge dispatch,
        both with ``parent``, the stamp of the version they read.

        ``delta`` — the applied edge batch as a ``versioning.Delta`` —
        rides the published aux under ``versioning.DELTA``: the update
        record is a first-class artifact of its version (GC'd with it),
        and ``vg.delta_between`` recovers the exact diff between any two
        still-live stamps for the incremental query path.  Vertex-set
        ops publish no delta (the full-recompute signal).

        Self-healing: if the held version carries no mirror (e.g. it was
        published through the raw ``vg`` writer API), the mirror is
        rebuilt from the new tree instead of merged incrementally."""

        def txn(v: Version[G.Graph]):
            with TraceAnnotation("aspen.publish.tree", parent=v.stamp):
                g2 = tree_fn(v.graph)
            aux = {} if delta is None else {DELTA: delta}
            if self._mirror_enabled:
                with TraceAnnotation("aspen.publish.mirror", parent=v.stamp):
                    m = v.aux.get(self._mirror_kind)
                    m2 = (
                        mirror_fn(m, v.graph, g2)
                        if m is not None
                        else self._mirror_from_tree(g2)
                    )
                    aux[self._mirror_kind] = self._heal_spill(m2, g2)
            return g2, (aux or None)

        with TraceAnnotation("aspen.publish", op=op, rows=rows):
            with self._wlock:
                v = self.vg.update_with_aux(txn)
            self._notify_publish(v)
        return v

    # -- update API (paper Appendix 10.4) ---------------------------------
    def insert_edges(
        self,
        edges: np.ndarray,
        symmetric: bool = True,
        weights: Optional[np.ndarray] = None,
    ):
        """InsertEdges, optionally weighted: ``weights`` is one value
        per batch edge (a symmetric insert carries the value on both
        directions).  Inserting an edge that already exists overwrites
        its weight; the tree and the device mirror are updated through
        their own value paths and published atomically as one version.
        The first weighted batch upgrades an unweighted stream (prior
        edges read as unit weight); weight-less batches on a weighted
        stream insert at unit weight."""
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if weights is not None:
            weights = np.asarray(weights, dtype=np.float64).reshape(-1)
            if weights.size != edges.shape[0]:
                raise ValueError("one weight per edge")
        if symmetric:
            edges = np.concatenate([edges, edges[:, ::-1]])
            if weights is not None:
                weights = np.concatenate([weights, weights])
        return self._publish(
            lambda g: G.insert_edges(g, edges, weights=weights),
            lambda m, g_old, g_new: self._apply_insert(m, g_old, edges, weights),
            delta=Delta(ins=edges, ins_w=weights),
            op="insert",
            rows=edges.shape[0],
        )

    def delete_edges(self, edges: np.ndarray, symmetric: bool = True):
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if symmetric:
            edges = np.concatenate([edges, edges[:, ::-1]])
        return self._publish(
            lambda g: G.delete_edges(g, edges),
            lambda m, g_old, g_new: self._apply_delete(m, edges),
            delta=Delta(dels=edges),
            op="delete",
            rows=edges.shape[0],
        )

    def rebalance(self):
        """Publish the current graph with its sharded mirror redistributed
        to equal per-shard counts: the compaction ``insert_edges`` takes
        by itself on skew or overflow, on demand.  The graph, and so every
        answer, is unchanged (an empty delta)."""
        if self._mirror_kind != SHARDED_MIRROR:
            raise ValueError("rebalance needs mirror='sharded'")
        from . import sharded_pool as sp

        def mirror_fn(m, g_old, g_new):
            if isinstance(m, sp.CompressedShardedGraph):
                return sp.CompressedShardedGraph(
                    sp.rebalance_compressed(m.pool, m.n), m.n
                )
            return sp.ShardedGraph(sp.rebalance(m.pool), m.n)

        return self._publish(lambda g: g, mirror_fn, delta=Delta(), op="rebalance", rows=0)

    def insert_vertices(self, vs: np.ndarray):
        # vertex-set ops are control-plane-rare: the mirror takes the
        # rebuild path (vertex growth/shrink reshapes the offsets array)
        return self._publish(
            lambda g: G.insert_vertices(g, vs),
            lambda m, g_old, g_new: self._mirror_from_tree(g_new),
            op="vertices",
            rows=0,
        )

    def delete_vertices(self, vs: np.ndarray):
        return self._publish(
            lambda g: G.delete_vertices(g, vs),
            lambda m, g_old, g_new: self._mirror_from_tree(g_new),
            op="vertices",
            rows=0,
        )

    # -- read API -----------------------------------------------------------
    def acquire(self):
        return self.vg.acquire()

    def release(self, v):
        return self.vg.release(v)

    def flat_snapshot(self) -> G.FlatSnapshot:
        v = self.acquire()
        try:
            return G.flat_snapshot(v.graph)
        finally:
            self.release(v)

    def flat_graph(self):
        """The current version's FlatGraph: the resident mirror (zero
        work; a compressed mirror is decompressed on the way out), or,
        on mirror-less / sharded streams, a one-off rebuild."""
        from . import flat_graph as fg

        v = self.acquire()
        try:
            if MIRROR in v.aux:
                m = v.aux[MIRROR]
                return fg.decompress(m) if isinstance(m, fg.CompressedPool) else m
            return self._flat_from_tree(v.graph)
        finally:
            self.release(v)

    def sharded_graph(self):
        """The current version's ShardedGraph: the resident sharded
        mirror (zero work; a compressed mirror is decompressed on the
        way out), or, on other streams, a one-off rebuild."""
        from . import sharded_pool as sp
        from .traversal import sharded_graph_of_flat

        v = self.acquire()
        try:
            if SHARDED_MIRROR in v.aux:
                m = v.aux[SHARDED_MIRROR]
                if isinstance(m, sp.CompressedShardedGraph):
                    return sp.decompress_sharded(m)
                return m
            flat = v.aux.get(MIRROR)
            if flat is None:
                flat = self._flat_from_tree(v.graph)
            return sharded_graph_of_flat(flat)
        finally:
            self.release(v)

    def shard_stats(self) -> Optional[dict]:
        """Occupancy skew of the current sharded mirror plus the policy
        outputs derived from it: ``imbalance`` (max/mean shard counts),
        whether the auto-rebalance trigger would fire, and the
        recommended shard count for the current edge total (None on
        streams without a sharded mirror)."""
        from . import sharded_pool as sp

        v = self.acquire()
        try:
            m = v.aux.get(SHARDED_MIRROR) if v.aux else None
            if m is None:
                return None
            pool = m.pool
            stats = sp.imbalance_stats(pool)
            stats["n_shards"] = pool.n_shards if hasattr(pool, "n_shards") else pool.data.shape[0]
            stats["should_rebalance"] = sp.should_rebalance(pool)
            stats["recommended_n_shards"] = sp.recommend_n_shards(
                int(np.asarray(pool.n).sum())
            )
            return stats
        finally:
            self.release(v)

    def engine(self, backend: str = "numpy"):
        """Traversal engine over the current version: the caller picks
        the query substrate at snapshot time.

        backend="numpy"   -> NumpyEngine over a FlatSnapshot (CPU);
        backend="jax"     -> JaxEngine over the version's resident
                             FlatGraph mirror (jit / Pallas query path);
                             rebuilt from the tree snapshot only when
                             the stream keeps no flat mirror.
        backend="sharded" -> ShardedEngine over the version's resident
                             ShardedGraph mirror (mesh-parallel
                             shard_map query path, DESIGN.md §9);
                             rebuilt from the tree snapshot on streams
                             not opened with mirror="sharded".

        Engines are cached per (version, backend): repeated calls on an
        unchanged version are O(1) dict hits, and the cache dies with
        the version (version-pinned — it can never serve a stale graph).
        """
        v = self.acquire()
        try:
            return self._engine_for(v, backend)
        finally:
            self.release(v)

    def _default_backend(self) -> str:
        return "sharded" if self._mirror_kind == SHARDED_MIRROR else "jax"

    def _engine_for(self, v: Version[G.Graph], backend: str):
        """``engine`` for an ALREADY-ACQUIRED version (the caller holds
        the reference): subscriptions pin their engine to the version
        they hold, never the racy current one."""
        from .traversal import ENGINE_BUILDS, make_engine

        key = ("engine", backend)
        eng = v.cache.get(key)
        if eng is None:
            ENGINE_BUILDS.bump()
            # profiler span: a new version's engine refresh (engine_aux on
            # the jax backend), on whichever thread first asks for it
            with TraceAnnotation("aspen.engine_build", stamp=v.stamp):
                if backend == "jax" and MIRROR in v.aux:
                    eng = make_engine(v.aux[MIRROR])
                elif backend == "sharded" and SHARDED_MIRROR in v.aux:
                    eng = make_engine(v.aux[SHARDED_MIRROR])
                else:
                    eng = make_engine(G.flat_snapshot(v.graph), backend=backend)
            eng = v.cache.setdefault(key, eng)
        return eng

    def query_batch(
        self, sources=None, kind: str = "bfs", backend: Optional[str] = None, **kw
    ):
        """Serve a coalesced batch of queries against ONE version-pinned
        engine (DESIGN.md §7): many users' pending single-source queries
        ride a single engine acquire and — on the jax/sharded backends —
        a single in-trace multi-source dispatch, instead of K independent
        traversals each paying per-round host syncs.

        ``backend=None`` routes to the stream's resident mirror: the
        sharded engine on ``mirror="sharded"`` streams, the jax engine
        otherwise.

        kinds: ``"bfs"`` -> int64[B, n] parent rows; ``"distances"`` ->
        int64[B, n] hop counts (landmark rows); ``"bc"`` -> float[B, n]
        dependency scores; ``"sssp"`` -> float64[B, n] weighted
        shortest-path distances (+inf = unreached; the in-trace
        Bellman–Ford driver on jax); ``"pagerank"`` -> float[B, n]
        scores for the personalization rows passed as ``resets``
        (``sources`` unused).  Extra kwargs are forwarded to the
        traversal-layer ``*_multi``.

        Identical ``(kind, source)`` requests inside one batch compute
        ONCE: the engine sees the unique sources and the result rows fan
        back out to every caller's lane (Zipfian query mixes repeat hot
        sources constantly, so the dedup is free qps).

        An EMPTY request set — ``sources`` None/empty, or a pagerank
        ``resets`` with zero rows — returns ``[]`` without touching an
        engine: a serving lane whose pending set collapsed to nothing
        (dedup, cancellation) must flush as a no-op, not an error.
        """
        if kind not in ("bfs", "distances", "bc", "sssp", "pagerank"):
            raise ValueError(f"unknown query kind {kind!r}")
        if self._empty_request(kind, sources, kw):
            return []
        if backend is None:
            backend = self._default_backend()
        return self._serve_kind(self.engine(backend), kind, sources, kw)

    @staticmethod
    def _empty_request(kind: str, sources, kw) -> bool:
        """The no-op-flush check, applied BEFORE any engine is fetched
        (an empty request must not pay an acquire or a build)."""
        if kind == "pagerank":
            resets = kw.get("resets")
            return resets is not None and np.asarray(resets).shape[0] == 0
        if sources is None:
            return True
        return np.asarray(sources, dtype=np.int64).reshape(-1).size == 0

    @staticmethod
    def _serve_kind(eng, kind: str, sources, kw):
        """One kind's dispatch against an already-fetched engine: the
        shared tail of ``query_batch`` / ``query_multi`` (source dedup +
        fan-out; pagerank takes its ``resets`` rows verbatim)."""
        from .traversal import algorithms as talg

        if kind == "pagerank":
            return talg.pagerank_multi(eng, **kw)
        sources = np.asarray(sources, dtype=np.int64).reshape(-1)
        uniq, inv = np.unique(sources, return_inverse=True)
        if kind == "bfs":
            return talg.bfs_multi(eng, uniq, **kw)[0][inv]
        if kind == "distances":
            return talg.landmark_distances(eng, uniq, **kw)[inv]
        if kind == "bc":
            return talg.bc_multi(eng, uniq, **kw)[inv]
        if kind == "sssp":
            return talg.sssp_multi(eng, uniq, **kw)[inv]
        raise ValueError(f"unknown query kind {kind!r}")

    def query_multi(self, requests, backend: Optional[str] = None):
        """Serve a MIXED-kind batch against one version: a list of
        ``query_batch``-style request dicts (``{"kind": ..., "sources":
        ..., **kwargs}``) answered in order against a single acquired
        version and a single engine fetch.

        ``query_batch`` called K times pays K acquire/engine lookups
        and — worse — may straddle a publish, answering later requests
        on a newer graph.  ``query_multi`` hoists the per-version work:
        ONE acquire, ONE ``_engine_for`` (the engine-cache aux lookup
        happens once; ``traversal.ENGINE_BUILDS`` pins single
        construction in tests), and every answer reflects the same
        snapshot.  Empty requests return ``[]`` in place, and a batch of
        only-empty requests never fetches an engine at all."""
        if backend is None:
            backend = self._default_backend()
        out = []
        v = self.acquire()
        try:
            eng = None
            for req in requests:
                req = dict(req)
                kind = req.pop("kind", "bfs")
                sources = req.pop("sources", None)
                if kind not in ("bfs", "distances", "bc", "sssp", "pagerank"):
                    raise ValueError(f"unknown query kind {kind!r}")
                if self._empty_request(kind, sources, req):
                    out.append([])
                    continue
                if eng is None:
                    eng = self._engine_for(v, backend)
                out.append(self._serve_kind(eng, kind, sources, req))
        finally:
            self.release(v)
        return out

    def subscribe(
        self,
        kind: str,
        sources=None,
        backend: Optional[str] = None,
        **params,
    ) -> "Subscription":
        """Open a live subscription: a handle whose ``refresh()`` keeps
        the result of one standing query (``"pagerank"`` / ``"cc"`` /
        ``"bfs"`` / ``"sssp"``) continuously fresh across publishes by
        applying the delta-aware incremental path per new version
        instead of recomputing from scratch (see ``Subscription``)."""
        return Subscription(self, kind, sources=sources, backend=backend, **params)


class Subscription:
    """A standing query kept continuously fresh across publishes.

    The handle holds (acquires) the version its current result was
    computed against — version-pinned exactly like the engine cache, so
    the pinned version, its delta record and its cached engines are all
    GC'd together the moment the subscription advances past them or
    closes.  ``refresh()`` compares the held stamp with the writer's
    current one; when behind, it asks ``vg.delta_between`` for the
    composed update record and applies the *incremental* path over the
    new snapshot:

      pagerank  warm-start power iteration from the previous scores to
                the same fixed-point tolerance (valid for ANY change —
                damping < 1 gives a unique fixed point, the init only
                sets how far away iteration starts);
      cc        min-label propagation seeded from the delta endpoints
                (exact; deltas with deletions fall back to full);
      bfs/sssp  dirty-subtree revalidation seeded into the warm
                ``sssp_batch_from`` drivers (exact, see
                ``algorithms.incremental_bfs`` / ``incremental_sssp``).

    A broken delta chain (a hop GC'd before this subscriber caught up,
    or a version published without a delta record) downgrades that one
    refresh to a full recompute — never to a wrong answer.
    ``n_full`` / ``n_incremental`` count which path each refresh took.
    Thread-safe; at most one refresh runs at a time."""

    KINDS = ("pagerank", "cc", "bfs", "sssp")

    def __init__(
        self,
        stream: AspenStream,
        kind: str,
        sources=None,
        backend: Optional[str] = None,
        damping: float = 0.85,
        tol: float = 1e-6,
        max_iters: int = 200,
    ):
        if kind not in self.KINDS:
            raise ValueError(f"unknown subscription kind {kind!r}")
        if kind in ("bfs", "sssp"):
            if sources is None:
                raise ValueError(f"{kind!r} subscriptions need sources")
            self._sources = np.asarray(sources, dtype=np.int64).reshape(-1)
        else:
            self._sources = None
        self._stream = stream
        self.kind = kind
        self._backend = backend
        self._damping, self._tol, self._max_iters = damping, tol, max_iters
        self._lock = threading.Lock()
        self.n_full = 0
        self.n_incremental = 0
        self._closed = False
        self._v = stream.acquire()
        try:
            self._recompute(self._v)
        except BaseException:
            stream.release(self._v)
            raise

    @property
    def stamp(self) -> int:
        """The version stamp the current result reflects."""
        return self._v.stamp

    @property
    def value(self):
        """The current result, as of ``stamp`` (no refresh): pagerank ->
        scores (n,); cc -> labels (n,); bfs -> (parents, depths)
        int64[B, n]; sssp -> distances float64[B, n]."""
        if self.kind == "pagerank":
            return self._scores
        if self.kind == "cc":
            return self._labels
        if self.kind == "bfs":
            return self._parents, self._depths
        return self._dist

    def _engine(self, v: Version[G.Graph]):
        backend = self._backend
        if backend is None:
            backend = self._stream._default_backend()
        return self._stream._engine_for(v, backend)

    def _recompute(self, v: Version[G.Graph]) -> None:
        from .traversal import algorithms as talg

        eng = self._engine(v)
        if self.kind == "pagerank":
            self._scores = talg.pagerank(
                eng, damping=self._damping, tol=self._tol, max_iters=self._max_iters
            )
        elif self.kind == "cc":
            self._labels = np.asarray(talg.connected_components(eng), np.int64)
        elif self.kind == "bfs":
            parents, depths = talg.bfs_multi(eng, self._sources)
            self._parents = np.asarray(parents, np.int64)
            self._depths = np.asarray(depths, np.int64)
        else:
            self._dist = np.asarray(talg.sssp_multi(eng, self._sources), np.float64)
            # the shortest-path-tree parents are the state the NEXT
            # delta's dirty-subtree computation needs
            self._tree = talg.shortest_path_parents(eng, self._dist, self._sources)
        self.n_full += 1

    def _advance(self, v: Version[G.Graph], delta: Optional[Delta]) -> None:
        from .traversal import algorithms as talg

        if self.kind == "pagerank":
            eng = self._engine(v)
            self._scores = talg.pagerank(
                eng,
                damping=self._damping,
                tol=self._tol,
                max_iters=self._max_iters,
                init=self._scores,
            )
            self.n_incremental += 1
            return
        if delta is None or (self.kind == "cc" and delta.has_deletions):
            self._recompute(v)
            return
        eng = self._engine(v)
        if self.kind == "cc":
            self._labels = np.asarray(
                talg.incremental_connected_components(eng, self._labels, delta),
                np.int64,
            )
        elif self.kind == "bfs":
            self._parents, self._depths = talg.incremental_bfs(
                eng, self._sources, self._parents, self._depths, delta
            )
        else:
            self._dist = talg.incremental_sssp(
                eng, self._sources, self._dist, self._tree, delta
            )
            self._tree = talg.shortest_path_parents(eng, self._dist, self._sources)
        self.n_incremental += 1

    def refresh(self):
        """Bring the result up to the writer's current version (no-op
        when already fresh) and return it."""
        with self._lock:
            if self._closed:
                raise RuntimeError("subscription is closed")
            cur = self._stream.acquire()
            if cur.stamp == self._v.stamp:
                self._stream.release(cur)
                return self.value
            try:
                delta = self._stream.vg.delta_between(self._v, cur)
                self._advance(cur, delta)
            except BaseException:
                self._stream.release(cur)
                raise
            old, self._v = self._v, cur
            self._stream.release(old)
            return self.value

    def close(self) -> None:
        """Release the pinned version (idempotent).  The held version —
        and with it the delta record and cached engines — becomes
        collectible as soon as no other reader holds it."""
        with self._lock:
            if not self._closed:
                self._closed = True
                self._stream.release(self._v)

    def __enter__(self) -> "Subscription":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ConcurrentStats(NamedTuple):
    updates_per_sec: float
    mean_update_latency_s: float
    query_latency_concurrent_s: float
    query_latency_isolated_s: float
    n_updates: int
    n_queries: int
    queries_per_sec: float = 0.0  # single-source queries served / reader-busy s
    subscriber_staleness: float = 0.0  # mean versions-behind after refresh


def run_concurrent(
    stream: AspenStream,
    updates: np.ndarray,  # (k, 3): src, dst, is_delete
    query_fn: Callable[[object], object],
    duration_s: float = 5.0,
    batch_size: int = 1,
    symmetric: bool = True,
    engine_backend: Optional[str] = None,
    queries_per_call: int = 1,
    subscription: Optional[Subscription] = None,
) -> ConcurrentStats:
    """Paper §7.3: writer applies updates one batch at a time while a
    reader repeatedly runs query_fn against fresh snapshots.

    ``query_fn`` receives a ``FlatSnapshot`` per query by default; pass
    ``engine_backend`` ("numpy"/"jax") to hand it the stream's cached
    traversal engine instead (the dual-representation serve path), or
    ``subscription`` to hand it a live ``Subscription`` handle (the
    incremental serve path: ``query_fn`` typically just calls
    ``refresh()``).  In subscriber mode the reader additionally samples
    *staleness* — how many versions the writer has published past the
    one the subscriber serves, measured right after each refresh —
    reported as ``subscriber_staleness``.

    ``queries_per_call`` declares how many user queries one ``query_fn``
    invocation serves (a batched reader passes e.g. a ``bfs_multi``
    over B sources and ``queries_per_call=B``), so the reported
    ``queries_per_sec`` measures batched vs. serial query throughput on
    equal terms.

    ``symmetric`` is forwarded to the insert/delete calls; the reported
    throughput counts the directed edges actually applied (2x the batch
    only when symmetric), not a hard-coded doubling.
    """
    stop = threading.Event()
    upd_lat: List[float] = []
    n_upd = [0]
    n_directed = [0]
    per_update = 2 if symmetric else 1

    # the writer loop is the SAME code path the serving layer runs
    # (``drain_updates`` over an ``UpdateQueue``), so batching semantics
    # measured here are the semantics a GraphQueryService writer has
    pending = UpdateQueue(maxsize=None)
    for row in updates:
        pending.put(int(row[0]), int(row[1]), delete=bool(row[2]), block=False)

    def updater():
        while not stop.is_set():
            t0 = time.perf_counter()
            k = drain_updates(pending, stream, batch_size, symmetric=symmetric)
            if k == 0:
                break
            upd_lat.append(time.perf_counter() - t0)
            n_upd[0] += k
            n_directed[0] += k * per_update

    q_lat: List[float] = []
    staleness: List[int] = []

    def _substrate():
        if subscription is not None:
            return subscription
        if engine_backend is not None:
            return stream.engine(engine_backend)
        return stream.flat_snapshot()

    def reader():
        while not stop.is_set():
            sub = _substrate()
            t0 = time.perf_counter()
            query_fn(sub)
            q_lat.append(time.perf_counter() - t0)
            if subscription is not None:
                staleness.append(stream.vg.current_stamp - subscription.stamp)

    tu = threading.Thread(target=updater)
    tq = threading.Thread(target=reader)
    tu.start()
    tq.start()
    time.sleep(duration_s)
    stop.set()
    tu.join()
    tq.join()

    # isolated query latency on the final version
    sub = _substrate()
    iso: List[float] = []
    for _ in range(max(3, min(10, len(q_lat)))):
        t0 = time.perf_counter()
        query_fn(sub)
        iso.append(time.perf_counter() - t0)

    total_upd_time = sum(upd_lat) if upd_lat else 1e-9
    return ConcurrentStats(
        updates_per_sec=n_directed[0] / total_upd_time,  # directed edges/s
        mean_update_latency_s=float(np.mean(upd_lat)) if upd_lat else 0.0,
        query_latency_concurrent_s=float(np.mean(q_lat)) if q_lat else 0.0,
        query_latency_isolated_s=float(np.mean(iso)),
        n_updates=n_upd[0],
        n_queries=len(q_lat) * queries_per_call,
        queries_per_sec=len(q_lat) * queries_per_call / max(sum(q_lat), 1e-9),
        subscriber_staleness=float(np.mean(staleness)) if staleness else 0.0,
    )


def make_update_stream(
    edges: np.ndarray, n_updates: int, seed: int = 0, delete_frac: float = 0.1
) -> Tuple[np.ndarray, np.ndarray]:
    """Paper §7.3 methodology: sample updates from the input graph.

    Returns (graph_edges_after_removal, update_stream[k,3]) where 90% of
    the sampled edges are first removed from the graph and re-inserted by
    the stream; 10% stay and get deleted by the stream.
    """
    rng = np.random.default_rng(seed)
    m = edges.shape[0]
    k = min(n_updates, m)
    pick = rng.choice(m, size=k, replace=False)
    sampled = edges[pick]
    n_ins = int(k * (1 - delete_frac))
    ins, dels = sampled[:n_ins], sampled[n_ins:]
    keep_mask = np.ones(m, dtype=bool)
    keep_mask[pick[:n_ins]] = False  # insertions start absent
    stream = np.concatenate(
        [
            np.concatenate([ins, np.zeros((ins.shape[0], 1), np.int64)], axis=1),
            np.concatenate([dels, np.ones((dels.shape[0], 1), np.int64)], axis=1),
        ]
    )
    rng.shuffle(stream)
    return edges[keep_mask], stream
