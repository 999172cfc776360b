"""Sharded flat C-tree pool: the beyond-paper distributed substrate.

The baseline flat union (flat_ctree.union_merge) is a *global* rank-merge:
under GSPMD, its batch search probes every shard and its pool-wide
prefix sum and shifts cross shard boundaries — collective-bound at pod
scale (EXPERIMENTS.md §Perf baseline).

Here each device owns a contiguous KEY RANGE of the pool (range-sharded,
like a distributed LSM level).  A batch update becomes:

  1. all-gather the (small) batch — k << n bytes on the wire;
  2. every shard slices the batch rows falling in its key range
     (two searchsorteds against its own boundaries);
  3. shard-LOCAL rank-merge into its own slack capacity.

Collective traffic drops from O(pool) to O(batch); the merge itself stays
bandwidth-optimal locally.  Queries (member) need one searchsorted against
the shard boundary table (replicated, n_shards entries) then a local
binary-search probe over flat index math — O(queries · log cap) scalar
gathers on the wire, never a cross-shard row gather.

Rebalancing: shards fill unevenly; when any shard exceeds its capacity
the host triggers a REBALANCE (an O(n) all-to-all redistribution to equal
counts — amortized over many updates, like LSM compaction).  The
imbalance statistics and trigger live here; the dry run lowers the
steady-state update step.

Graph substrate (DESIGN.md §9)
------------------------------
Beyond the bare sorted-int64 set, the pool is a full graph substrate:
keys are the packed ``(src << 32) | dst`` edge encoding of
``flat_graph``, an optional VALUE LANE carries one float32 per slot
(the property-graph weight array, permuted by the same shard-local
rank-merge; insert overwrites, delete drops), ``make_delete_step``
is the shard-local MultiDelete, and ``shard_aux`` derives the
per-shard CSR auxiliary state (src offsets, clipped endpoints,
dst-major permutation — the shard-local ``EngineAux``) that the
sharded traversal backend (``traversal/sharded_backend.py``) runs
edgeMap over.  ``ShardedGraph`` pairs the pool with its static vertex
count; ``AspenStream(mirror="sharded")`` maintains one per version.

Implemented with shard_map so the collective schedule is explicit, not
GSPMD-inferred.  ``n_shards`` may exceed the mesh size (each device then
owns a BLOCK of shard rows); it must be a multiple of the mesh size.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import compressed as cz
from .flat_ctree import sentinel_for


def shard_map(f, *, mesh, in_specs, out_specs):
    """The package's one ``shard_map`` (``jax.shard_map``).

    Replication checking is off: every body that returns a replicated
    (``P()``) output makes it so with an explicit collective (psum, pmax,
    pmin), which is the contract the sharded engine is written to."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


SENT = sentinel_for(jnp.int64)


class ShardedPool(NamedTuple):
    """Range-sharded sorted pool; a jax pytree.

    data  : (n_shards, cap_per) sorted within each shard; pad = SENT
    n     : (n_shards,) valid counts
    lo    : (n_shards,) inclusive lower key boundary of each shard
    vals  : optional (n_shards, cap_per) per-slot values (pad 0),
            permuted by every shard-local merge / compaction alongside
            the keys (insert overwrites a duplicate key's value, delete
            drops it — the flat_ctree.FlatCTree.vals semantics, sharded)
    """

    data: jax.Array
    n: jax.Array
    lo: jax.Array
    vals: Optional[jax.Array] = None


def from_array(
    values: np.ndarray,
    n_shards: int,
    cap_per: int | None = None,
    vals: np.ndarray | None = None,
) -> ShardedPool:
    """Host build: dedup + range-partition to equal counts.  ``vals``
    optionally attaches one value per element (a duplicated key keeps
    the FIRST occurrence's value, matching ``flat_ctree.from_array``)."""
    raw = np.asarray(values, dtype=np.int64)
    if vals is None:
        v = np.unique(raw)
        w = None
    else:
        v, first = np.unique(raw, return_index=True)
        w = np.asarray(vals, dtype=np.float32).reshape(-1)[first]
    per = -(-v.size // n_shards) if v.size else 1
    if cap_per is None:
        cap_per = max(8, int(2 ** np.ceil(np.log2(per * 2 + 1))))
    data = np.full((n_shards, cap_per), SENT, dtype=np.int64)
    wdata = np.zeros((n_shards, cap_per), dtype=np.float32) if w is not None else None
    n = np.zeros((n_shards,), dtype=np.int32)
    lo = np.full((n_shards,), np.iinfo(np.int64).min, dtype=np.int64)
    # An EMPTY shard's lo must start strictly past every key stored
    # before it (last key + 1, not a copy of the previous lo): with
    # duplicated boundaries, a query equal to the boundary key routes —
    # by the searchsorted(side="right") convention — to the LAST shard
    # claiming that lo, an empty one, and membership misses; worse, the
    # insert step would re-insert that key there as a duplicate.
    next_lo = 0
    for s in range(n_shards):
        chunk = v[s * per : (s + 1) * per]
        data[s, : chunk.size] = chunk
        n[s] = chunk.size
        if chunk.size:
            lo[s] = chunk[0]
            next_lo = int(chunk[-1]) + 1
        else:
            lo[s] = next_lo
        if wdata is not None:
            wdata[s, : chunk.size] = w[s * per : (s + 1) * per]
    lo[0] = np.iinfo(np.int64).min
    # shard rows live on their mesh devices from the start (not all on
    # device 0 until the first shard_map'd step moves them)
    mesh = pool_mesh(n_shards)
    rows = NamedSharding(mesh, P("shard"))
    rows2 = NamedSharding(mesh, P("shard", None))
    return ShardedPool(
        jax.device_put(data, rows2),
        jax.device_put(n, rows),
        jax.device_put(lo, rows),
        None if wdata is None else jax.device_put(wdata, rows2),
    )


def to_array(p: ShardedPool) -> np.ndarray:
    data = np.asarray(p.data)
    n = np.asarray(p.n)
    return np.concatenate([data[s, : n[s]] for s in range(data.shape[0])])


def to_val_array(p: ShardedPool) -> np.ndarray | None:
    """Valid-prefix values aligned with ``to_array`` (None on plain sets)."""
    if p.vals is None:
        return None
    vals = np.asarray(p.vals)
    n = np.asarray(p.n)
    return np.concatenate([vals[s, : n[s]] for s in range(vals.shape[0])])


def with_unit_vals(p: ShardedPool) -> ShardedPool:
    """Attach a unit value lane (the upgrade an unweighted pool takes
    when its first weighted batch arrives)."""
    if p.vals is not None:
        return p
    return p._replace(vals=jnp.ones(p.data.shape, jnp.float32))


def _local_merge(
    pool_row: jax.Array,
    n_valid: jax.Array,
    batch: jax.Array,
    b_lo: jax.Array,
    b_hi: jax.Array,
    vrow: jax.Array | None = None,
    bvals: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array | None]:
    """Merge batch[b_lo:b_hi) into one shard row (fixed shapes, O(n+k)).

    The value lane, when present, rides the same two scatters as
    ``flat_ctree.union_merge``: a duplicate batch key lands its value on
    the matched pool slot (insert overwrites)."""
    cap = pool_row.shape[0]
    kcap = batch.shape[0]
    # mask the batch to this shard's range
    idx = jnp.arange(kcap)
    mine = (idx >= b_lo) & (idx < b_hi)
    masked = jnp.where(mine, batch, SENT)
    if bvals is None:
        b = jnp.sort(masked)  # my rows to the front (already sorted among themselves)
        bv = None
    else:
        order = jnp.argsort(masked)  # stable: value lane rides along
        b = masked[order]
        bv = bvals[order]
    n_mine = (b_hi - b_lo).astype(jnp.int32)
    valid_a = jnp.arange(cap) < n_valid
    valid_b = jnp.arange(kcap) < n_mine
    # dedup b against a
    ia = jnp.minimum(jnp.searchsorted(pool_row, b), cap - 1)
    dup_b = (pool_row[ia] == b) & valid_b
    keep_b = valid_b & ~dup_b
    kb_excl = jnp.cumsum(keep_b, dtype=jnp.int32) - keep_b
    ra = jnp.searchsorted(b, pool_row)
    kept_below_a = jnp.where(
        ra > 0,
        kb_excl[jnp.minimum(ra - 1, kcap - 1)] + keep_b[jnp.minimum(ra - 1, kcap - 1)],
        0,
    )
    pos_a = jnp.arange(cap, dtype=jnp.int32) + kept_below_a.astype(jnp.int32)
    pos_a = jnp.where(valid_a, pos_a, cap)
    rb = jnp.searchsorted(pool_row, b)
    pos_b = rb.astype(jnp.int32) + kb_excl.astype(jnp.int32)
    pos_b = jnp.where(keep_b, pos_b, cap)
    out = jnp.full((cap,), SENT, dtype=pool_row.dtype)
    out = out.at[pos_a].set(pool_row, mode="drop")
    out = out.at[pos_b].set(b, mode="drop")
    n_new = n_valid + keep_b.sum().astype(jnp.int32)
    if vrow is None:
        return out, n_new, None
    vout = jnp.zeros((cap,), dtype=vrow.dtype)
    vout = vout.at[pos_a].set(vrow, mode="drop")
    vout = vout.at[pos_b].set(bv, mode="drop")
    pos_dup = jnp.where(dup_b, pos_a[ia], cap)  # insert overwrites
    vout = vout.at[pos_dup].set(bv, mode="drop")
    return out, n_new, vout


def make_insert_step(mesh: Mesh, axis_names: Tuple[str, ...]):
    """Build the shard_map'd update step for a given mesh.

    axis_names: the mesh axes the shard dimension is split over (all of
    them: every chip owns one BLOCK of shard rows — n_shards must be a
    multiple of the mesh size; the common case is one row per chip).

    The returned ``step(pool, batch, batch_vals=None)`` merges a sorted,
    deduped, SENT-padded batch into every shard's key range.  A value
    lane on either side upgrades the other to unit values at trace time
    (the flat_ctree ``_aligned_vals`` semantics)."""
    flat_axes = axis_names
    spec_sharded = P(flat_axes)
    spec_sharded2 = P(flat_axes, None)

    def local_plain(data, n, lo, hi, batch):
        # shapes inside shard_map: data (rows, cap), n/lo/hi (rows,),
        # batch (kcap,) REPLICATED (this is the one collective: GSPMD
        # all-gathers the batch operand once).
        def row(drow, nrow, lorow, hirow):
            b_lo = jnp.searchsorted(batch, lorow)
            b_hi = jnp.searchsorted(batch, hirow)
            out, n_new, _ = _local_merge(drow, nrow, batch, b_lo, b_hi)
            return out, n_new

        return jax.vmap(row)(data, n, lo, hi)

    def local_vals(data, n, vals, lo, hi, batch, bvals):
        def row(drow, nrow, vrow, lorow, hirow):
            b_lo = jnp.searchsorted(batch, lorow)
            b_hi = jnp.searchsorted(batch, hirow)
            return _local_merge(drow, nrow, batch, b_lo, b_hi, vrow, bvals)

        return jax.vmap(row)(data, n, vals, lo, hi)

    step_plain = shard_map(
        local_plain,
        mesh=mesh,
        in_specs=(spec_sharded2, spec_sharded, spec_sharded, spec_sharded, P()),
        out_specs=(spec_sharded2, spec_sharded),
    )
    step_vals = shard_map(
        local_vals,
        mesh=mesh,
        in_specs=(
            spec_sharded2, spec_sharded, spec_sharded2,
            spec_sharded, spec_sharded, P(), P(),
        ),
        out_specs=(spec_sharded2, spec_sharded, spec_sharded2),
    )

    @jax.jit  # retraces only on shape / weightedness change
    def step(
        pool: ShardedPool, batch: jax.Array, batch_vals: jax.Array | None = None
    ) -> ShardedPool:
        hi = jnp.concatenate(
            [pool.lo[1:], jnp.asarray([jnp.iinfo(jnp.int64).max], jnp.int64)]
        )
        if pool.vals is None and batch_vals is None:
            out, n_new = step_plain(pool.data, pool.n, pool.lo, hi, batch)
            return ShardedPool(out, n_new, pool.lo)
        vals = pool.vals if pool.vals is not None else jnp.ones(
            pool.data.shape, batch_vals.dtype
        )
        bv = batch_vals if batch_vals is not None else jnp.ones(
            batch.shape, vals.dtype
        )
        out, n_new, vout = step_vals(pool.data, pool.n, vals, pool.lo, hi, batch, bv)
        return ShardedPool(out, n_new, pool.lo, vout)

    return step


def make_delete_step(mesh: Mesh, axis_names: Tuple[str, ...]):
    """Shard-local MultiDelete: each shard drops its elements found in
    the (replicated, sorted, SENT-padded) batch and compacts in place.
    Shard boundaries are unchanged — deletion never moves keys across
    ranges.  A dropped key drops its value-lane entry."""
    flat_axes = axis_names
    spec_sharded = P(flat_axes)
    spec_sharded2 = P(flat_axes, None)

    def _rows(data, n, batch, vals=None):
        kcap = batch.shape[0]

        def row(drow, nrow, vrow):
            cap = drow.shape[0]
            idx = jnp.minimum(jnp.searchsorted(batch, drow), kcap - 1)
            hit = (batch[idx] == drow) & (drow != SENT)
            keep = (jnp.arange(cap) < nrow) & ~hit
            pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
            pos = jnp.where(keep, pos, cap)
            out = jnp.full((cap,), SENT, jnp.int64).at[pos].set(drow, mode="drop")
            n_new = keep.sum().astype(jnp.int32)
            if vrow is None:
                return out, n_new, None
            vout = jnp.zeros((cap,), vrow.dtype).at[pos].set(vrow, mode="drop")
            return out, n_new, vout

        if vals is None:
            out, n_new, _ = jax.vmap(lambda d, c: row(d, c, None))(data, n)
            return out, n_new, None
        return jax.vmap(row)(data, n, vals)

    def local_plain(data, n, batch):
        out, n_new, _ = _rows(data, n, batch)
        return out, n_new

    def local_vals(data, n, vals, batch):
        return _rows(data, n, batch, vals)

    step_plain = shard_map(
        local_plain,
        mesh=mesh,
        in_specs=(spec_sharded2, spec_sharded, P()),
        out_specs=(spec_sharded2, spec_sharded),
    )
    step_vals = shard_map(
        local_vals,
        mesh=mesh,
        in_specs=(spec_sharded2, spec_sharded, spec_sharded2, P()),
        out_specs=(spec_sharded2, spec_sharded, spec_sharded2),
    )

    @jax.jit
    def step(pool: ShardedPool, batch: jax.Array) -> ShardedPool:
        if pool.vals is None:
            out, n_new = step_plain(pool.data, pool.n, batch)
            return ShardedPool(out, n_new, pool.lo)
        out, n_new, vout = step_vals(pool.data, pool.n, pool.vals, batch)
        return ShardedPool(out, n_new, pool.lo, vout)

    return step


# ---------------------------------------------------------------------------
# queries + rebalance policy (host-driven)
# ---------------------------------------------------------------------------


@jax.jit
def member(p: ShardedPool, queries: jax.Array) -> jax.Array:
    """shard id via the (replicated) boundary table, then a LOCAL probe
    by flat index math: an unrolled binary search over
    ``data.reshape(-1)[s * cap + mid]`` — O(queries · log cap) scalar
    gathers, never a cross-shard ``p.data[s]`` row gather (which would
    put a (queries, cap) block on the wire under GSPMD)."""
    S, cap = p.data.shape
    q = queries.astype(jnp.int64)
    flat = p.data.reshape(-1)
    s = jnp.clip(jnp.searchsorted(p.lo, q, side="right") - 1, 0, S - 1)
    base = s.astype(jnp.int64) * cap
    ns = p.n[s].astype(jnp.int64)
    lo = jnp.zeros(q.shape, jnp.int64)
    hi = ns
    for _ in range(int(np.ceil(np.log2(cap))) + 1):  # static unroll
        active = lo < hi
        mid = (lo + hi) // 2
        v = flat[base + mid]
        go_right = active & (v < q)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    probe = flat[base + jnp.minimum(lo, cap - 1)]
    return (lo < ns) & (probe == q)


def needs_rebalance(p: ShardedPool, slack: float = 0.9) -> bool:
    return bool((np.asarray(p.n) >= slack * p.data.shape[1]).any())


def rebalance(p: ShardedPool, cap_per: int | None = None) -> ShardedPool:
    """O(n) redistribution to equal counts (the amortized compaction);
    the value lane, when present, is preserved through the round-trip."""
    return from_array(
        to_array(p),
        p.data.shape[0],
        cap_per=p.data.shape[1] if cap_per is None else cap_per,
        vals=to_val_array(p),
    )


# ---------------------------------------------------------------------------
# graph substrate: packed-key pool + per-shard CSR aux (DESIGN.md §9)
# ---------------------------------------------------------------------------


class ShardedGraph(NamedTuple):
    """A graph over the range-sharded pool: keys are the packed
    ``(src << 32) | dst`` encoding, ``n`` is the STATIC vertex count
    (host-known; never passed through jit as a tracer).  The pool's
    value lane, when present, is the per-edge weight array."""

    pool: ShardedPool
    n: int

    @property
    def n_shards(self) -> int:
        return self.pool.data.shape[0]

    @property
    def weighted(self) -> bool:
        return self.pool.vals is not None


class ShardAux(NamedTuple):
    """Per-shard CSR auxiliary state: the shard-local ``EngineAux``.

    Every field is laid out (n_shards, ...) so a ``P('shard', ...)``
    in_spec hands each device exactly its own rows; refreshing it is ONE
    fixed-shape jit call over the pool (``shard_aux``), the sharded
    analogue of ``jax_backend.engine_aux``.

    offsets      : int32[S, n+1] CSR into each shard's OWN row (vertex
                   v's local adjacency occupies row[offsets[s, v] :
                   offsets[s, v+1]]; empty for vertices outside the
                   shard's key range)
    src_c, dst_c : int32[S, cap] clipped endpoints per slot
    evalid       : bool[S, cap] slot holds a real edge with a real dst
    degrees      : int32[S, n] per-shard out-degree contribution
    deg_total    : int32[n] global out-degrees (the one cross-shard
                   reduction, done once per refresh, not per query)
    dst_sorted   : int32[S, cap] destinations ascending per row (pad n)
    src_by_dst   : int32[S, cap] sources permuted dst-major per row
    valid_by_dst : bool[S, cap]
    dst_offsets  : int32[S, n+1] segment bounds into dst_sorted per row
    w_by_dst     : float32[S, cap] values dst-major, or None
    """

    offsets: jax.Array
    src_c: jax.Array
    dst_c: jax.Array
    evalid: jax.Array
    degrees: jax.Array
    deg_total: jax.Array
    dst_sorted: jax.Array
    src_by_dst: jax.Array
    valid_by_dst: jax.Array
    dst_offsets: jax.Array
    w_by_dst: Optional[jax.Array] = None


@functools.partial(jax.jit, static_argnums=(1,))
def shard_aux(p: ShardedPool, n: int) -> ShardAux:
    """Derive the per-shard CSR aux from the pool: one fixed-shape jit
    call, vmapped over shard rows (each row's computation touches only
    that row, so under GSPMD it stays shard-local)."""
    cap = p.data.shape[1]
    bounds = jnp.arange(n + 1, dtype=jnp.int64) << 32

    def row(drow, nrow, vrow):
        src = (drow >> 32).astype(jnp.int32)
        dst = (drow & 0xFFFFFFFF).astype(jnp.int32)
        valid = jnp.arange(cap) < nrow
        evalid = valid & (dst >= 0) & (dst < n)
        src_c = jnp.clip(src, 0, max(n - 1, 0))
        dst_c = jnp.clip(dst, 0, max(n - 1, 0))
        offsets = jnp.searchsorted(drow, bounds).astype(jnp.int32)
        offsets = jnp.minimum(offsets, nrow.astype(jnp.int32))
        degrees = jnp.diff(offsets)
        dst_key = jnp.where(evalid, dst_c, jnp.int32(n))
        order = jnp.argsort(dst_key)  # stable in jax
        dst_sorted = dst_key[order]
        dst_offsets = jnp.searchsorted(
            dst_sorted, jnp.arange(n + 1, dtype=jnp.int32)
        ).astype(jnp.int32)
        w_by_dst = None if vrow is None else vrow[order]
        return (
            offsets, src_c, dst_c, evalid, degrees,
            dst_sorted, src_c[order], evalid[order], dst_offsets, w_by_dst,
        )

    if p.vals is None:
        outs = jax.vmap(lambda d, c: row(d, c, None))(p.data, p.n)
    else:
        outs = jax.vmap(row)(p.data, p.n, p.vals)
    (offsets, src_c, dst_c, evalid, degrees,
     dst_sorted, src_by_dst, valid_by_dst, dst_offsets, w_by_dst) = outs
    return ShardAux(
        offsets=offsets,
        src_c=src_c,
        dst_c=dst_c,
        evalid=evalid,
        degrees=degrees,
        deg_total=degrees.sum(axis=0),
        dst_sorted=dst_sorted,
        src_by_dst=src_by_dst,
        valid_by_dst=valid_by_dst,
        dst_offsets=dst_offsets,
        w_by_dst=w_by_dst,
    )


def default_n_shards() -> int:
    return jax.device_count()


def pool_mesh(n_shards: int) -> Mesh:
    """A 1-axis mesh whose size divides ``n_shards``: all devices when
    possible, else the largest divisor of n_shards that fits (a 1-device
    run degenerates to a single-chip mesh, which is still correct —
    every collective becomes a local no-op).  The axis is an automatic
    (GSPMD) axis: the explicit-sharding default of ``jax.make_mesh`` would
    type every vmapped row op of ``shard_aux`` with the shard axis."""
    nd = jax.device_count()
    size = 1
    for d in range(min(n_shards, nd), 0, -1):
        if n_shards % d == 0:
            size = d
            break
    return Mesh(np.array(jax.devices()[:size]), ("shard",))


def graph_from_edges(
    n: int,
    edges: np.ndarray,
    n_shards: int | None = None,
    weights: np.ndarray | None = None,
    cap_per: int | None = None,
) -> ShardedGraph:
    """Host build from a (k, 2) directed edge array (dedups; a
    duplicated edge keeps the FIRST occurrence's weight)."""
    if n_shards is None:
        n_shards = default_n_shards()
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    packed = (edges[:, 0] << 32) | edges[:, 1]
    w = None if weights is None else np.asarray(weights, np.float32).reshape(-1)
    return ShardedGraph(from_array(packed, n_shards, cap_per=cap_per, vals=w), n)


def graph_to_edge_array(sg: ShardedGraph) -> np.ndarray:
    k = to_array(sg.pool)
    return np.stack([k >> 32, k & 0xFFFFFFFF], axis=1)


def graph_to_weight_array(sg: ShardedGraph) -> np.ndarray | None:
    return to_val_array(sg.pool)


def graph_num_edges(sg) -> int:
    """Global edge count; works on both ShardedGraph and
    CompressedShardedGraph (both pools carry per-shard counts)."""
    return int(np.asarray(sg.pool.n).sum())


# ---------------------------------------------------------------------------
# compressed sharded pool: per-shard chunk-compressed dst lane (paper §3.2,
# sharded).  The per-shard variant of flat_graph.CompressedPool.
# ---------------------------------------------------------------------------


class CompressedShardedPool(NamedTuple):
    """ShardedPool with each shard row's dst lane chunk-compressed.

    Same range-sharding contract (``n`` counts, ``lo`` boundaries) but
    the packed-key rows are factored exactly like the flat
    ``CompressedPool``: src ids implied by a per-shard CSR ``offsets``
    row, dst ids delta-chunked per row (``ChunkedStream`` with
    (S, ...)-batched leaves; ``spill`` becomes bool[S]).  Every leaf is
    laid out (n_shards, ...) so a ``P('shard', ...)`` spec hands each
    device its own rows, same as the raw pool.

    offsets : int32[S, n+1] per-shard CSR over each row's valid prefix
    dst     : ChunkedStream, anchors (S, R) / deltas (S, R, CHUNK) /
              ovf_* (S, R, K) / spill (S,); row capacity = R * CHUNK
    n       : (S,) valid counts (the raw pool's counts, unchanged)
    lo      : (S,) inclusive lower key boundary per shard
    vals    : optional (S, cap) float32 value lane, uncompressed (pad 0)
    """

    offsets: jax.Array
    dst: cz.ChunkedStream
    n: jax.Array
    lo: jax.Array
    vals: Optional[jax.Array] = None

    @property
    def n_shards(self) -> int:
        return self.offsets.shape[0]

    @property
    def cap_per(self) -> int:
        return self.dst.length


class CompressedShardedGraph(NamedTuple):
    """ShardedGraph over a CompressedShardedPool; ``n`` is the STATIC
    vertex count, same contract as ``ShardedGraph``."""

    pool: CompressedShardedPool
    n: int

    @property
    def n_shards(self) -> int:
        return self.pool.n_shards

    @property
    def weighted(self) -> bool:
        return self.pool.vals is not None


def _compress_pool_impl(
    p: ShardedPool, n: int, width: int, k: int, hi_cap: int | None = None
) -> CompressedShardedPool:
    S, cap = p.data.shape
    bounds = jnp.arange(n + 1, dtype=jnp.int64) << 32

    def row(drow, nrow):
        offs = jnp.minimum(jnp.searchsorted(drow, bounds), nrow).astype(jnp.int32)
        dst = (drow & 0xFFFFFFFF).astype(jnp.int32)
        # Pad slots hold SENT (dst lane -1): carry the last valid dst
        # forward instead of encoding that cliff (same trick as the flat
        # ``_compress_impl``; decompress re-masks pad slots from ``n``).
        last = dst[jnp.maximum(nrow - 1, 0)]
        dst_enc = jnp.where(jnp.arange(cap) < nrow, dst, last)
        if hi_cap is not None:
            return offs, cz._encode_adaptive_impl(dst_enc, hi_cap, k)
        return offs, cz._encode_impl(dst_enc, width, k)

    offsets, stream = jax.vmap(row)(p.data, p.n)
    vals = p.vals
    if vals is not None and stream.length > cap:
        vals = jnp.pad(vals, ((0, 0), (0, stream.length - cap)))
    return CompressedShardedPool(offsets, stream, p.n, p.lo, vals)


compress_pool = functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))(
    _compress_pool_impl
)
compress_pool.__doc__ = (
    "jit ShardedPool -> CompressedShardedPool (static n / lane width /"
    " escape capacity); vmapped per-shard encode, shard-local under GSPMD."
)


def _decompress_pool_impl(cp: CompressedShardedPool) -> ShardedPool:
    capC = cp.cap_per
    dst = cz.decode_stream(cp.dst)  # (S, capC) int32, batched decode

    def row(offs, dst_row, nrow):
        slots = jnp.arange(capC, dtype=offs.dtype)
        src = (jnp.searchsorted(offs, slots, side="right") - 1).astype(jnp.int32)
        packed = (src.astype(jnp.int64) << 32) | (
            dst_row.astype(jnp.int64) & 0xFFFFFFFF
        )
        return jnp.where(jnp.arange(capC) < nrow, packed, SENT)

    data = jax.vmap(row)(cp.offsets, dst, cp.n)
    return ShardedPool(data, cp.n, cp.lo, cp.vals)


decompress_pool = jax.jit(_decompress_pool_impl)
decompress_pool.__doc__ = (
    "jit CompressedShardedPool -> ShardedPool (exact inverse of"
    " ``compress_pool`` for non-spilled rows; pad slots come back as SENT)."
    "  Row capacity is the chunked capacity, a CHUNK multiple >= the input"
    " pool's, so a compress/decompress round-trip is capacity-stable."
)


def compress_sharded(
    sg: ShardedGraph,
    width: int | None = None,
    k: int = cz.OVF_SLOTS,
    hi_headroom: float = 0.0,
) -> CompressedShardedGraph:
    """Host build mirroring ``flat_graph.compress_host``: the default is
    the ADAPTIVE per-chunk-width layout (one int8 lane + a compacted
    hi-byte plane sized by the widest shard's wide-chunk count, plus
    ``hi_headroom`` slack rows for streaming growth); pass an explicit
    ``width`` (1 or 2) for the fixed layouts.  Raises if any shard row
    spills even at the widest encoding (keep the raw layout)."""
    if width is None:
        S, cap = sg.pool.data.shape
        R = (max(cap, 1) + cz.CHUNK - 1) // cz.CHUNK
        cp = compress_pool(sg.pool, sg.n, 0, k, R)
        if bool(np.asarray(cp.dst.spill).any()):
            raise ValueError(
                f"sharded pool spills the k={k} escape lane even at "
                "adaptive (int16-wide) chunks; keep the raw layout"
            )
        # Exact-fit slice of the hi plane: the leaf is one (S, H, CHUNK)
        # array, so H is the max wide-chunk count over shards (+ slack).
        n_wide = int(np.asarray(cp.dst.wide).sum(axis=-1).max())
        slack = 0 if hi_headroom <= 0 else max(4, int(np.ceil(hi_headroom * R)))
        hc = min(R, n_wide + slack)
        hi = jnp.asarray(np.asarray(cp.dst.hi)[:, :hc])
        cp = cp._replace(dst=cp.dst._replace(hi=hi))
        return CompressedShardedGraph(cp, sg.n)
    cp = compress_pool(sg.pool, sg.n, width, k)
    if bool(np.asarray(cp.dst.spill).any()):
        raise ValueError(
            f"sharded pool spills the k={k} escape lane at the requested "
            "fixed width; keep the raw layout"
        )
    return CompressedShardedGraph(cp, sg.n)


def decompress_sharded(csg: CompressedShardedGraph) -> ShardedGraph:
    return ShardedGraph(decompress_pool(csg.pool), csg.n)


def _or_spill(out: CompressedShardedPool, cp: CompressedShardedPool):
    # once a row spills it stays flagged until the pool is rebuilt
    return out._replace(dst=out.dst._replace(spill=out.dst.spill | cp.dst.spill))


def make_insert_step_compressed(mesh: Mesh, axis_names: Tuple[str, ...]):
    """Compressed counterpart of ``make_insert_step``: decompress ->
    shard-local rank-merge -> recompress, ONE jit per (shapes, n).  The
    uncompressed rows exist only as a transient inside the step; the
    resident state stays compressed (the flat
    ``insert_edges_compressed`` contract, sharded).  ``n`` is static
    (the offsets rows are (n+1)-wide); lane width / escape capacity are
    inherited from the input stream's dtypes, so one compiled step
    serves a whole update stream."""
    raw_step = make_insert_step(mesh, axis_names)

    @functools.partial(jax.jit, static_argnames=("n",))
    def step(
        cpool: CompressedShardedPool,
        batch: jax.Array,
        batch_vals: jax.Array | None = None,
        *,
        n: int,
    ) -> CompressedShardedPool:
        p = _decompress_pool_impl(cpool)
        p2 = raw_step(p, batch, batch_vals)
        hi_cap = cpool.dst.hi.shape[-2] if cpool.dst.hi is not None else None
        out = _compress_pool_impl(p2, n, cpool.dst.width, cpool.dst.k, hi_cap)
        return _or_spill(out, cpool)

    return step


def make_delete_step_compressed(mesh: Mesh, axis_names: Tuple[str, ...]):
    """Compressed counterpart of ``make_delete_step`` (see
    ``make_insert_step_compressed``)."""
    raw_step = make_delete_step(mesh, axis_names)

    @functools.partial(jax.jit, static_argnames=("n",))
    def step(
        cpool: CompressedShardedPool, batch: jax.Array, *, n: int
    ) -> CompressedShardedPool:
        p = _decompress_pool_impl(cpool)
        p2 = raw_step(p, batch)
        hi_cap = cpool.dst.hi.shape[-2] if cpool.dst.hi is not None else None
        out = _compress_pool_impl(p2, n, cpool.dst.width, cpool.dst.k, hi_cap)
        return _or_spill(out, cpool)

    return step


def needs_rebalance_compressed(
    cp: CompressedShardedPool, slack: float = 0.9
) -> bool:
    return bool((np.asarray(cp.n) >= slack * cp.cap_per).any())


def rebalance_compressed(
    cp: CompressedShardedPool, n: int, cap_per: int | None = None
) -> CompressedShardedPool:
    """Host-side O(m) redistribution (decompress -> rebalance ->
    recompress).  Only sound on non-spilled streams — a spilled pool no
    longer round-trips and must be rebuilt from its source edges."""
    p = rebalance(decompress_pool(cp), cap_per=cap_per)
    hi_cap = None
    if cp.dst.hi is not None:
        # Capacity may have grown: re-derive the plane bound from the new
        # row capacity, keeping at least the old plane's slack.
        new_cap = p.data.shape[1]
        R = (max(new_cap, 1) + cz.CHUNK - 1) // cz.CHUNK
        hi_cap = min(R, max(cp.dst.hi.shape[-2], 1))
    return compress_pool(p, n, cp.dst.width, cp.dst.k, hi_cap)


# ---------------------------------------------------------------------------
# shard auto-tuning: imbalance stats -> rebalance policy + shard-count hint
# ---------------------------------------------------------------------------


def imbalance_stats(p) -> dict:
    """Shard occupancy skew summary from the counts the pool already
    tracks (``p.n``): max/mean ratio is the load-balance figure the
    range partition degrades toward under skewed key streams.  Accepts a
    ShardedPool, CompressedShardedPool, or a raw counts array."""
    counts = np.asarray(getattr(p, "n", p), dtype=np.float64).reshape(-1)
    if counts.size == 0 or counts.sum() == 0:
        return {"max": 0.0, "mean": 0.0, "imbalance": 1.0}
    mean = float(counts.mean())
    mx = float(counts.max())
    return {"max": mx, "mean": mean, "imbalance": mx / mean if mean else 1.0}


def recommend_n_shards(m_total: int, target_per_shard: int = 1 << 16) -> int:
    """Shard-count hint: enough shards to keep ~``target_per_shard``
    edges per shard, snapped to a mesh-friendly count (a multiple of the
    device count when more than one round is needed, so every device
    carries equal rows)."""
    nd = max(1, jax.device_count())
    want = max(1, -(-int(m_total) // int(target_per_shard)))
    if want <= nd:
        return want
    return -(-want // nd) * nd  # round up to a device-count multiple


def should_rebalance(
    p, *, imbalance_threshold: float = 2.0, slack: float = 0.9
) -> bool:
    """Auto-rebalance trigger: any shard nears capacity (the existing
    ``needs_rebalance`` criterion, capacity read off either layout) OR
    the max/mean occupancy ratio exceeds ``imbalance_threshold`` — skew
    wastes the per-shard compute budget long before capacity overflows.
    Works on both raw and compressed pools (counts + capacity are plain
    attributes of each)."""
    cap = p.cap_per if hasattr(p, "cap_per") else p.data.shape[1]
    near_cap = bool((np.asarray(p.n) >= slack * cap).any())
    return near_cap or imbalance_stats(p)["imbalance"] > imbalance_threshold


def maybe_rebalance(
    p: ShardedPool, *, imbalance_threshold: float = 2.0, slack: float = 0.9
):
    """``should_rebalance`` + the rebalance itself for raw pools; returns
    ``(pool, rebalanced)``.  Compressed pools go through
    ``rebalance_compressed`` (the caller holds the static ``n``)."""
    if not should_rebalance(
        p, imbalance_threshold=imbalance_threshold, slack=slack
    ):
        return p, False
    return rebalance(p), True
