"""Sharded traversal backend over the range-sharded pool (DESIGN.md §9).

Third backend of the unified edgeMap engine: the same algorithm text
that runs on ``NumpyEngine`` (FlatSnapshot) and ``JaxEngine``
(single-chip FlatGraph) runs here over ``sharded_pool.ShardedGraph`` —
the pool whose updates already scale with the mesh.  Every query step
is an EXPLICIT ``shard_map``: edge data never moves, and the only wire
traffic per edgeMap round is the frontier-sized vertex-state collective
(O(n) words, not O(pool) edges — the same O(batch)-not-O(pool)
argument the sharded update step makes, applied to queries).

How arbitrary F/C callbacks stay correct across shards
------------------------------------------------------
The backend contract (base.py) requires every state write to go
through the masked ``ops.scatter_*`` helpers.  ``ShardedOps`` exploits
exactly that: inside the shard_map'd step each shard runs F over its
OWN edge lanes, and each scatter helper merges its contribution with
one collective —

  scatter_add  ->  target + psum(local delta)
  scatter_max  ->  max(target, pmax(local candidates))
  scatter_min  ->  min(target, pmin(local candidates))
  scatter_or   ->  target | (pmax(local hits) > 0)

add/max/min/or are commutative and associative, so the merged result
is identical to one global scatter over the union of all shards' edges
(each edge lives in exactly one shard) — and after F returns, the
state and out-mask are REPLICATED on every device, which is what lets
the frontier loop iterate without ever gathering edge data.  The
Beamer direction rule runs on psum'd frontier degrees (each shard
knows only its local degree contribution), so push/pull decisions are
identical to the single-chip engines and the parity suite holds
exactly.

``edge_map_reduce(_batch)`` (PageRank's inner loop) is a shard-local
segmented row-sum over each shard's dst-major lanes followed by ONE
tiled ``psum_scatter`` over the padded vertex axis — O(B · n) words on
the wire, each device left holding exactly the output chunk the
out_spec reassembles.  The in-trace ``bfs_batch_sharded`` /
``sssp_batch_sharded`` drivers port the single-chip ``lax.while_loop``
drivers with a pmax/pmin/psum merge per round, preserving the
ONE-dispatch / O(1)-host-syncs contract.

``collective_operand_bytes`` is the collective-bytes spy tests use to
pin the O(frontier + batch)-not-O(pool) wire contract on the jaxpr.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import compressed as cz
from ..sharded_pool import (
    CompressedShardedGraph,
    CompressedShardedPool,
    ShardAux,
    ShardedGraph,
    _decompress_pool_impl,
    graph_num_edges,
    pool_mesh,
    shard_aux,
    shard_map,
)
from .base import DENSE_THRESHOLD_DENOM, TRACES, TraversalEngine
from .jax_backend import (
    JaxEngine,
    JaxOps,
    JaxVertexSubset,
    _round_up,
    _segmin_rows,
    _segsum_rows,
    _sparse_expand,
    _take_mask,
)

AXIS = "shard"

_SPEC2 = P(AXIS, None)


def _neutral_min(dtype):
    """Identity of max (the lowest representable value)."""
    d = np.dtype(dtype)
    if d == np.bool_:
        return False
    if np.issubdtype(d, np.floating):
        return -np.inf
    return np.iinfo(d).min


def _neutral_max(dtype):
    d = np.dtype(dtype)
    if d == np.bool_:
        return True
    if np.issubdtype(d, np.floating):
        return np.inf
    return np.iinfo(d).max


class ShardedOps(JaxOps):
    """JaxOps whose scatter helpers merge across the shard axis.

    The collective forms are only valid inside the backend's shard_map'd
    steps (they need the ``shard`` axis bound); F/C callbacks are the
    only contract call sites that scatter, and the engine runs them
    exactly there.  Instances hash/compare by dtype + axis so the jit
    step cache stays shared across engines."""

    def __init__(self, float_dtype=jnp.float32, axis_name: str = AXIS):
        super().__init__(float_dtype)
        self.axis_name = axis_name

    def __eq__(self, other):
        return (
            type(other) is type(self)
            and np.dtype(other.float_dtype) == np.dtype(self.float_dtype)
            and other.axis_name == self.axis_name
        )

    def __hash__(self):
        return hash((type(self), np.dtype(self.float_dtype).name, self.axis_name))

    def scatter_max(self, target, idx, vals, mask):
        neutral = jnp.asarray(_neutral_min(target.dtype), target.dtype)
        local = jnp.full_like(target, neutral).at[
            self._safe_idx(target, idx, mask)
        ].max(vals, mode="drop")
        return jnp.maximum(target, jax.lax.pmax(local, self.axis_name))

    def scatter_min(self, target, idx, vals, mask):
        neutral = jnp.asarray(_neutral_max(target.dtype), target.dtype)
        local = jnp.full_like(target, neutral).at[
            self._safe_idx(target, idx, mask)
        ].min(vals, mode="drop")
        return jnp.minimum(target, jax.lax.pmin(local, self.axis_name))

    def scatter_add(self, target, idx, vals, mask):
        vals = jnp.where(mask, vals, jnp.zeros((), target.dtype))
        delta = jnp.zeros_like(target).at[
            self._safe_idx(target, idx, mask)
        ].add(vals, mode="drop")
        return target + jax.lax.psum(delta, self.axis_name)

    def scatter_or(self, target, idx, mask):
        local = jnp.zeros(target.shape, jnp.int32).at[
            self._safe_idx(target, idx, mask)
        ].max(1, mode="drop")
        return target | (jax.lax.pmax(local, self.axis_name) > 0)


SHARDED_OPS = ShardedOps()


def _expand_block(offsets, keys, vals, U, n, ids_budget, edge_budget):
    """Sparse push expansion of one frontier over a BLOCK of shard rows:
    vmap the fixed-shape single-row expansion and flatten the edge lanes
    (each edge lives in exactly one row, so concatenation is the union)."""

    def one_row(off_row, key_row):
        return _sparse_expand(off_row, key_row, U, n, ids_budget, edge_budget)

    us, vs, ev, eidx = jax.vmap(one_row)(offsets, keys)
    ws = None if vals is None else jnp.take_along_axis(vals, eidx, axis=1).reshape(-1)
    return us.reshape(-1), vs.reshape(-1), ev.reshape(-1), ws


# ---------------------------------------------------------------------------
# the shard_map'd edgeMap step (module-level jit: cache shared across engines)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "F", "C", "mode", "n", "ids_budget", "edge_budget", "ops", "mesh", "weighted",
    ),
)
def _sharded_edge_map_step(
    offsets,  # int32[S, n+1] per-shard CSR
    keys,  # int64[S, cap]
    src_c,  # int32[S, cap]
    dst_c,  # int32[S, cap]
    evalid,  # bool[S, cap]
    degrees,  # int32[S, n] per-shard degree contributions
    m,  # int32 scalar: global edge count
    vals,  # float32[S, cap] per-edge values, or None (unweighted)
    U,  # bool[n] frontier (replicated)
    state,  # pytree (replicated)
    *,
    F: Callable,
    C: Callable,
    mode: str,
    n: int,
    ids_budget: int,
    edge_budget: int,
    ops: ShardedOps,
    mesh: Mesh,
    weighted: bool,
):
    def body(offsets, keys, src_c, dst_c, evalid, degrees, vals, m, U, state):
        src_f = src_c.reshape(-1)
        dst_f = dst_c.reshape(-1)
        ev_f = evalid.reshape(-1)
        w_f = None if vals is None else vals.reshape(-1)
        cmask = C(ops, state, jnp.arange(n, dtype=jnp.int32))

        def dense_branch(state):
            valid = ev_f & U[src_f] & cmask[dst_f]
            return F(ops, state, src_f, dst_f, w_f, valid)

        def sparse_branch(state):
            us, vs, ev, ws = _expand_block(
                offsets, keys, vals, U, n, ids_budget, edge_budget
            )
            return F(ops, state, us, vs, ws, ev & cmask[vs])

        if mode == "dense":
            return dense_branch(state)
        if mode == "sparse":
            return sparse_branch(state)
        # auto: Beamer rule on psum'd frontier degrees — one scalar psum
        # makes the direction decision globally consistent
        size = U.sum()
        deg_u = jax.lax.psum(jnp.where(U, degrees.sum(axis=0), 0).sum(), AXIS)
        use_dense = (size + deg_u) > jnp.maximum(1, m // DENSE_THRESHOLD_DENOM)
        return jax.lax.cond(use_dense, dense_branch, sparse_branch, state)

    if weighted:
        local = body
        args = (offsets, keys, src_c, dst_c, evalid, degrees, vals, m, U, state)
        specs = (_SPEC2,) * 7 + (P(), P(), P())
    else:
        def local(offsets, keys, src_c, dst_c, evalid, degrees, m, U, state):
            return body(offsets, keys, src_c, dst_c, evalid, degrees, None, m, U, state)

        args = (offsets, keys, src_c, dst_c, evalid, degrees, m, U, state)
        specs = (_SPEC2,) * 6 + (P(), P(), P())
    return shard_map(local, mesh=mesh, in_specs=specs, out_specs=(P(), P()))(*args)


# ---------------------------------------------------------------------------
# dense semiring reduce: shard-local segment-sum + ONE psum_scatter
# ---------------------------------------------------------------------------


def _reduce_partial(sbd, vbd, bounds, wbd, values_b, n_pad, dtype):
    """Per-device partial of the (+, x) reduce over a block of rows,
    psum_scatter'd so each device keeps its own vertex chunk."""

    def one(srow, vrow, brow, wrow):
        msg = jnp.where(vrow[None, :], values_b[:, srow], 0.0).astype(dtype)
        if wrow is not None:
            msg = msg * wrow[None, :].astype(dtype)
        return _segsum_rows(msg, brow)

    if wbd is None:
        parts = jax.vmap(lambda s, v, b: one(s, v, b, None))(sbd, vbd, bounds)
    else:
        parts = jax.vmap(one)(sbd, vbd, bounds, wbd)
    partial = parts.sum(axis=0)  # (B, n)
    padded = jnp.pad(partial, ((0, 0), (0, n_pad - partial.shape[1])))
    return jax.lax.psum_scatter(padded, AXIS, scatter_dimension=1, tiled=True)


@functools.partial(jax.jit, static_argnames=("n", "mesh", "weighted", "dtype"))
def _sharded_reduce_batch(
    src_by_dst,  # int32[S, cap]
    valid_by_dst,  # bool[S, cap]
    dst_offsets,  # int32[S, n+1]
    w_by_dst,  # float32[S, cap] or None
    values_b,  # (B, n) replicated value rows
    *,
    n: int,
    mesh: Mesh,
    weighted: bool,
    dtype,
):
    """out[b, v] = sum_{u->v} w(u, v) * values[b, u] over all shards."""
    n_pad = _round_up(max(n, 1), mesh.shape[AXIS])
    if weighted:
        out = shard_map(
            lambda s, v, b, w, x: _reduce_partial(s, v, b, w, x, n_pad, dtype),
            mesh=mesh,
            in_specs=(_SPEC2, _SPEC2, _SPEC2, _SPEC2, P()),
            out_specs=P(None, AXIS),
        )(src_by_dst, valid_by_dst, dst_offsets, w_by_dst, values_b)
    else:
        out = shard_map(
            lambda s, v, b, x: _reduce_partial(s, v, b, None, x, n_pad, dtype),
            mesh=mesh,
            in_specs=(_SPEC2, _SPEC2, _SPEC2, P()),
            out_specs=P(None, AXIS),
        )(src_by_dst, valid_by_dst, dst_offsets, values_b)
    return out[:, :n]


# ---------------------------------------------------------------------------
# in-trace batched drivers: whole multi-source traversals, ONE dispatch
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n", "ids_budget", "edge_budget", "mesh")
)
def bfs_batch_sharded(
    offsets,  # int32[S, n+1]
    keys,  # int64[S, cap]
    src_c,  # int32[S, cap]
    dst_c,  # int32[S, cap]
    evalid,  # bool[S, cap]
    degrees,  # int32[S, n]
    src_by_dst,  # int32[S, cap]
    valid_by_dst,  # bool[S, cap]
    dst_offsets,  # int32[S, n+1]
    m,  # int32 scalar: global edge count
    sources,  # int32[B]
    *,
    n: int,
    ids_budget: int,
    edge_budget: int,
    mesh: Mesh,
) -> Tuple[jax.Array, jax.Array]:
    """Multi-source direction-optimized BFS over the sharded pool, fully
    in-trace: the single-chip ``jax_backend.bfs_batch`` driver with a
    pmax/psum merge per round.  Returns ``(parents, depths)`` int32[B, n]
    — bit-identical to the single-chip driver (push is a per-shard
    budget-bounded expand OR-merged across shards; pull is the per-shard
    segmented row-cumsum psum-merged; parents are one final masked
    scatter-max pass pmax-merged, the same max-contention rule)."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body

    def local(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd, doff, m, sources):
        B = sources.shape[0]
        lane = jnp.arange(B)
        src = sources.astype(jnp.int32)
        depths = jnp.full((B, n), -1, jnp.int32).at[lane, src].set(0)
        frontier = jnp.zeros((B, n), bool).at[lane, src].set(True)
        thresh = jnp.maximum(1, m // DENSE_THRESHOLD_DENOM)
        deg_loc = degrees.sum(axis=0)  # (n,) this device's contribution

        def push(f_b):
            def one(U):
                def one_row(off_row, key_row):
                    us, vs, ev, _ = _sparse_expand(
                        off_row, key_row, U, n, ids_budget, edge_budget
                    )
                    return (
                        jnp.zeros(n, bool)
                        .at[jnp.where(ev, vs, n)]
                        .max(True, mode="drop")
                    )

                return jax.vmap(one_row)(offsets, keys).any(axis=0)

            loc = jax.vmap(one)(f_b)
            return jax.lax.pmax(loc.astype(jnp.int32), AXIS) > 0

        def pull(f_b):
            def one_row(srow, vrow, brow):
                msg = (_take_mask(f_b, srow) & vrow[None, :]).astype(jnp.int32)
                return _segsum_rows(msg, brow)

            loc = jax.vmap(one_row)(sbd, vbd, doff).sum(axis=0)
            return jax.lax.psum(loc, AXIS) > 0

        def cond(carry):
            return carry[0].any()

        def body(carry):
            f, dep, d = carry
            size_b = f.sum(axis=1)
            deg_b = jax.lax.psum(
                jnp.where(f, deg_loc[None, :], 0).sum(axis=1), AXIS
            )
            reached = jax.lax.cond(((size_b + deg_b) > thresh).any(), pull, push, f)
            newly = reached & (dep < 0)
            return newly, jnp.where(newly, d + 1, dep), d + 1

        _, depths, _ = jax.lax.while_loop(
            cond, body, (frontier, depths, jnp.int32(0))
        )

        src_f = src_c.reshape(-1)
        dst_f = dst_c.reshape(-1)
        ev_f = evalid.reshape(-1)
        du = depths[:, src_f]
        dv = depths[:, dst_f]
        ok = ev_f[None, :] & (du >= 0) & (dv == du + 1)
        safe = jnp.where(ok, dst_f[None, :], n)
        cand = jnp.full((B, n), -1, jnp.int32).at[lane[:, None], safe].max(
            jnp.broadcast_to(src_f[None, :], (B, src_f.shape[0])), mode="drop"
        )
        cand = jax.lax.pmax(cand, AXIS)
        vid = jnp.arange(n, dtype=jnp.int32)[None, :]
        parents = jnp.where(depths == 0, vid, jnp.where(depths > 0, cand, -1))
        return parents, depths

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(_SPEC2,) * 9 + (P(), P()),
        out_specs=(P(), P()),
    )(
        offsets, keys, src_c, dst_c, evalid, degrees,
        src_by_dst, valid_by_dst, dst_offsets, m, sources,
    )


@functools.partial(jax.jit, static_argnames=("n", "mesh", "float_dtype"))
def bc_batch_sharded(
    offsets,  # int32[S, n+1] CSR into each shard's own rows
    src_c,  # int32[S, cap]
    dst_c,  # int32[S, cap]
    evalid,  # bool[S, cap]
    src_by_dst,  # int32[S, cap]
    valid_by_dst,  # bool[S, cap]
    dst_offsets,  # int32[S, n+1]
    sources,  # int32[B]
    *,
    n: int,
    mesh: Mesh,
    float_dtype=jnp.float32,
) -> jax.Array:
    """Multi-source Brandes dependency scores over the sharded pool,
    fully in-trace — the sharded analogue of ``jax_backend.bc_batch``.

    All per-lane state (sigma, depth, dep_acc) is replicated; each round
    every device computes its shards' partial of the (+, x) segmented
    row-sum and ONE psum merges it, in both the forward
    (shortest-path-count) pass over the dst-major pool and the backward
    (dependency) pass over the src-major CSR.  The round structure — one
    collective per BFS level instead of one per edge_map sub-step — is
    what the generic edge_map fallback cannot express."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body

    def local(offsets, src_c, dst_c, evalid, sbd, vbd, doff, sources):
        B = sources.shape[0]
        lane = jnp.arange(B)
        src = sources.astype(jnp.int32)
        sigma = jnp.zeros((B, n), float_dtype).at[lane, src].set(1.0)
        depth = jnp.full((B, n), -1, jnp.int32).at[lane, src].set(0)
        frontier = jnp.zeros((B, n), bool).at[lane, src].set(True)

        def fcond(carry):
            return carry[0].any()

        def fbody(carry):
            f, sig, dep, d = carry

            def one_row(srow, vrow, brow):
                w = jnp.where(
                    _take_mask(f, srow) & vrow[None, :],
                    sig[:, srow],
                    jnp.zeros((), float_dtype),
                )
                return _segsum_rows(w, brow)

            contrib = jax.lax.psum(
                jax.vmap(one_row)(sbd, vbd, doff).sum(axis=0), AXIS
            )
            newly = (contrib > 0) & (dep < 0)
            sig = sig + jnp.where(newly, contrib, 0)
            return newly, sig, jnp.where(newly, d + 1, dep), d + 1

        _, sigma, depth, d_final = jax.lax.while_loop(
            fcond, fbody, (frontier, sigma, depth, jnp.int32(0))
        )

        def bcond(carry):
            return carry[1] >= 0

        def bbody(carry):
            dep_acc, dd = carry

            def one_row(off_row, srow, drow, ev):
                du = depth[:, srow]
                dv = depth[:, drow]
                ok = ev[None, :] & (du == dd) & (dv == dd + 1)
                ratio = sigma[:, srow] / jnp.maximum(sigma[:, drow], 1e-30)
                contrib = jnp.where(ok, ratio * (1.0 + dep_acc[:, drow]), 0)
                return _segsum_rows(contrib, off_row)

            loc = jax.vmap(one_row)(offsets, src_c, dst_c, evalid).sum(axis=0)
            return dep_acc + jax.lax.psum(loc, AXIS), dd - 1

        dep, _ = jax.lax.while_loop(
            bcond, bbody, (jnp.zeros((B, n), float_dtype), d_final - 2)
        )
        return dep.at[lane, src].set(0.0)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(_SPEC2,) * 7 + (P(),),
        out_specs=P(),
    )(offsets, src_c, dst_c, evalid, src_by_dst, valid_by_dst, dst_offsets, sources)


def _sharded_bellman_ford(
    offsets, keys, degrees, sbd, vbd, doff, vals, wbd, m,
    dist, frontier,
    *, n, ids_budget, edge_budget, float_dtype, unit=False,
):
    """The per-device (min, +) relaxation loop shared by
    ``sssp_batch_sharded`` (point sources) and
    ``sssp_batch_sharded_from`` (warm start): runs INSIDE the callers'
    shard_map from whatever replicated (dist, frontier) it is seeded
    with, pmin-merging each round across shards.  ``unit=True`` forces
    unit weights — the hop metric, how incremental BFS rides this
    driver on a weighted pool."""
    inf = jnp.asarray(jnp.inf, float_dtype)
    w_pool = (
        jnp.ones(keys.shape, float_dtype)
        if (unit or vals is None)
        else vals.astype(float_dtype)
    )
    w_dst = (
        jnp.ones(keys.shape, float_dtype)
        if (unit or wbd is None)
        else wbd.astype(float_dtype)
    )
    thresh = jnp.maximum(1, m // DENSE_THRESHOLD_DENOM)
    deg_loc = degrees.sum(axis=0)

    def push(args):
        f_b, d_b = args

        def one(U, d):
            def one_row(off_row, key_row, w_row):
                us, vs, ev, eidx = _sparse_expand(
                    off_row, key_row, U, n, ids_budget, edge_budget
                )
                cand = d[us] + w_row[eidx]
                return (
                    jnp.full(n, inf, float_dtype)
                    .at[jnp.where(ev, vs, n)]
                    .min(cand, mode="drop")
                )

            return jax.vmap(one_row)(offsets, keys, w_pool).min(axis=0)

        loc = jax.vmap(one)(f_b, d_b)
        return jax.lax.pmin(loc, AXIS)

    def pull(args):
        f_b, d_b = args

        def one_row(srow, vrow, brow, wrow):
            msg = jnp.where(
                _take_mask(f_b, srow) & vrow[None, :],
                d_b[:, srow] + wrow[None, :],
                inf,
            )
            return _segmin_rows(msg, brow)

        loc = jax.vmap(one_row)(sbd, vbd, doff, w_dst).min(axis=0)
        return jax.lax.pmin(loc, AXIS)

    def cond(carry):
        return carry[0].any()

    def step(carry):
        f, d = carry
        size_b = f.sum(axis=1)
        deg_b = jax.lax.psum(
            jnp.where(f, deg_loc[None, :], 0).sum(axis=1), AXIS
        )
        cand = jax.lax.cond(
            ((size_b + deg_b) > thresh).any(), pull, push, (f, d)
        )
        newly = cand < d
        return newly, jnp.where(newly, cand, d)

    _, dist = jax.lax.while_loop(cond, step, (frontier, dist))
    return dist


@functools.partial(
    jax.jit,
    static_argnames=("n", "ids_budget", "edge_budget", "mesh", "weighted", "float_dtype"),
)
def sssp_batch_sharded(
    offsets,
    keys,
    src_c,
    dst_c,
    evalid,
    degrees,
    src_by_dst,
    valid_by_dst,
    dst_offsets,
    vals,  # float32[S, cap] pool-order values, or None
    w_by_dst,  # float32[S, cap] dst-major values, or None
    m,
    sources,
    *,
    n: int,
    ids_budget: int,
    edge_budget: int,
    mesh: Mesh,
    weighted: bool,
    float_dtype=jnp.float32,
) -> jax.Array:
    """Multi-source Bellman–Ford over the sharded pool, fully in-trace:
    the (min, +) driver of ``jax_backend.sssp_batch`` with a pmin merge
    per round.  Distances are EXACT matches of the single-chip driver:
    every candidate path sum d[u] + w is computed identically and min is
    order-insensitive."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body

    def body(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd, doff,
             vals, wbd, m, sources):
        B = sources.shape[0]
        lane = jnp.arange(B)
        src = sources.astype(jnp.int32)
        inf = jnp.asarray(jnp.inf, float_dtype)
        dist = jnp.full((B, n), inf, float_dtype).at[lane, src].set(0.0)
        frontier = jnp.zeros((B, n), bool).at[lane, src].set(True)
        return _sharded_bellman_ford(
            offsets, keys, degrees, sbd, vbd, doff, vals, wbd, m,
            dist, frontier,
            n=n, ids_budget=ids_budget, edge_budget=edge_budget,
            float_dtype=float_dtype,
        )

    if weighted:
        local = body
        args = (offsets, keys, src_c, dst_c, evalid, degrees, src_by_dst,
                valid_by_dst, dst_offsets, vals, w_by_dst, m, sources)
        specs = (_SPEC2,) * 11 + (P(), P())
    else:
        def local(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd, doff,
                  m, sources):
            return body(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd,
                        doff, None, None, m, sources)

        args = (offsets, keys, src_c, dst_c, evalid, degrees, src_by_dst,
                valid_by_dst, dst_offsets, m, sources)
        specs = (_SPEC2,) * 9 + (P(), P())
    return shard_map(local, mesh=mesh, in_specs=specs, out_specs=P())(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n", "ids_budget", "edge_budget", "mesh", "weighted", "unit", "float_dtype"
    ),
)
def sssp_batch_sharded_from(
    offsets,
    keys,
    src_c,
    dst_c,
    evalid,
    degrees,
    src_by_dst,
    valid_by_dst,
    dst_offsets,
    vals,  # float32[S, cap] pool-order values, or None
    w_by_dst,  # float32[S, cap] dst-major values, or None
    m,
    dist0,  # float[B, n] replicated (+inf = unknown)
    frontier0,  # bool[B, n] replicated initial relax frontier
    *,
    n: int,
    ids_budget: int,
    edge_budget: int,
    mesh: Mesh,
    weighted: bool,
    unit: bool = False,
    float_dtype=jnp.float32,
) -> jax.Array:
    """``sssp_batch_sharded`` seeded from arbitrary replicated state
    instead of point sources — the sharded warm-start entry point of
    the incremental BFS/SSSP path.  Distance/frontier state is
    vertex-shaped and replicated (``P()``), exactly like the in-loop
    carry, so per-round collective traffic stays O(frontier + batch)."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body

    def body(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd, doff,
             vals, wbd, m, dist0, frontier0):
        return _sharded_bellman_ford(
            offsets, keys, degrees, sbd, vbd, doff, vals, wbd, m,
            dist0.astype(float_dtype), frontier0,
            n=n, ids_budget=ids_budget, edge_budget=edge_budget,
            float_dtype=float_dtype, unit=unit,
        )

    if weighted and not unit:
        local = body
        args = (offsets, keys, src_c, dst_c, evalid, degrees, src_by_dst,
                valid_by_dst, dst_offsets, vals, w_by_dst, m, dist0, frontier0)
        specs = (_SPEC2,) * 11 + (P(), P(), P())
    else:
        def local(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd, doff,
                  m, dist0, frontier0):
            return body(offsets, keys, src_c, dst_c, evalid, degrees, sbd, vbd,
                        doff, None, None, m, dist0, frontier0)

        args = (offsets, keys, src_c, dst_c, evalid, degrees, src_by_dst,
                valid_by_dst, dst_offsets, m, dist0, frontier0)
        specs = (_SPEC2,) * 9 + (P(), P(), P())
    return shard_map(local, mesh=mesh, in_specs=specs, out_specs=P())(*args)


# ---------------------------------------------------------------------------
# weighted degrees (one fixed-shape jit over the sharded aux)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dtype",))
def _sharded_weighted_degrees(offsets, evalid, vals, dtype):
    def one_row(off_row, ev_row, v_row):
        msg = jnp.where(ev_row, v_row.astype(dtype), 0.0)
        return _segsum_rows(msg[None, :], off_row)[0]

    return jax.vmap(one_row)(offsets, evalid, vals).sum(axis=0)


# ---------------------------------------------------------------------------
# the collective-bytes spy (tests pin the wire contract on the jaxpr)
# ---------------------------------------------------------------------------

COLLECTIVE_PRIMS = frozenset(
    {
        "psum", "pmax", "pmin", "all_gather", "all_to_all",
        "reduce_scatter", "psum_scatter", "ppermute", "pgather",
    }
)


def _walk_jaxpr(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            nbytes = sum(
                int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                for v in eqn.invars
                if hasattr(v, "aval") and hasattr(v.aval, "shape")
            )
            out.append((eqn.primitive.name, nbytes))
        for v in eqn.params.values():
            for item in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, out)
    return out


def collective_operand_bytes(fn, *args, **kwargs):
    """Trace ``fn(*args)`` and return ``[(collective_name, operand_bytes),
    ...]`` over every collective in the jaxpr (recursing through cond /
    while / shard_map sub-jaxprs).  Operand byte-sizes are per-device
    logical shapes — the quantity that goes on the wire per round.  The
    O(frontier + batch)-not-O(pool) acceptance tests assert every entry
    is vertex-state-sized, never pool-sized."""
    closed = jax.make_jaxpr(fn, **kwargs)(*args)
    return _walk_jaxpr(closed.jaxpr, [])


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ShardedEngine(TraversalEngine):
    """Engine over an (immutable) ``ShardedGraph``.

    The full backend contract of ``base.py`` — BFS / CC / PageRank /
    SSSP / BC in ``algorithms.py`` run unchanged — plus the in-trace
    ``bfs_batch`` / ``sssp_batch`` drivers ``bfs_multi`` / ``sssp_multi``
    dispatch to.  ``aux`` may be passed in pre-refreshed by a
    version-pinned caller (AspenStream's engine cache)."""

    def __init__(
        self,
        sg: ShardedGraph,
        aux: Optional[ShardAux] = None,
        mesh: Optional[Mesh] = None,
        float_dtype=None,
    ):
        self.sg = sg
        self._n = sg.n
        self.mesh = pool_mesh(sg.n_shards) if mesh is None else mesh
        if sg.n_shards % self.mesh.shape[AXIS] != 0:
            raise ValueError(
                f"n_shards={sg.n_shards} must be a multiple of the mesh "
                f"size {self.mesh.shape[AXIS]}"
            )
        self._m = graph_num_edges(sg)  # one device read per engine build
        self.ops = ShardedOps(jnp.float32 if float_dtype is None else float_dtype)
        self.aux = shard_aux(sg.pool, sg.n) if aux is None else aux
        self._wdeg = None  # lazy weighted out-degree cache

        # static sparse budgets: a frontier routed sparse obeys
        # |U| + deg(U) <= m/20 <= pool_cap/20 globally; the per-row edge
        # budget additionally caps at the row capacity.
        S, cap = sg.pool.data.shape
        total_cap = S * cap
        self._auto_ids_budget = min(
            self._n, _round_up(total_cap // DENSE_THRESHOLD_DENOM + 1, 64)
        )
        self._auto_edge_budget = min(
            cap, _round_up(total_cap // DENSE_THRESHOLD_DENOM + 1, 64)
        )
        self._full_ids_budget = self._n
        self._full_edge_budget = max(cap, 1)

    # -- graph shape --------------------------------------------------------
    @property
    def n(self) -> int:
        return self._n

    @property
    def m(self) -> int:
        return self._m

    @property
    def degrees(self) -> jax.Array:
        return self.aux.deg_total

    @property
    def weights(self) -> Optional[jax.Array]:
        """The pool's value lane ((S, cap) float32), or None."""
        return self.sg.pool.vals

    @property
    def weighted_degrees(self) -> jax.Array:
        if self.sg.pool.vals is None:
            return self.aux.deg_total.astype(self.ops.float_dtype)
        if self._wdeg is None:
            self._wdeg = _sharded_weighted_degrees(
                self.aux.offsets, self.aux.evalid, self.sg.pool.vals,
                dtype=self.ops.float_dtype,
            )
        return self._wdeg

    @property
    def resident_nbytes(self) -> int:
        """Device bytes held per snapshot (pool + aux) — the raw side of
        the BYTES bench comparison."""
        return cz.pytree_nbytes(self.sg.pool) + cz.pytree_nbytes(self.aux)

    # -- frontiers ----------------------------------------------------------
    def frontier_from_ids(self, ids) -> JaxVertexSubset:
        mask = jnp.zeros(self._n, dtype=bool).at[jnp.asarray(ids)].set(True)
        return JaxVertexSubset(mask)

    def frontier_from_dense(self, mask) -> JaxVertexSubset:
        return JaxVertexSubset(jnp.asarray(mask, dtype=bool))

    def _budgets(self, mode: str) -> Tuple[int, int]:
        if mode == "sparse":
            return self._full_ids_budget, self._full_edge_budget
        return self._auto_ids_budget, self._auto_edge_budget

    # -- edgeMap ------------------------------------------------------------
    def edge_map(
        self,
        U: JaxVertexSubset,
        F: Callable,
        C: Callable,
        state,
        direction_optimize: bool = True,
        mode: str = "auto",
    ) -> Tuple[JaxVertexSubset, object]:
        if mode == "auto" and not direction_optimize:
            mode = "sparse"
        ids_b, edge_b = self._budgets(mode)
        state, out = _sharded_edge_map_step(
            self.aux.offsets,
            self.sg.pool.data,
            self.aux.src_c,
            self.aux.dst_c,
            self.aux.evalid,
            self.aux.degrees,
            jnp.int32(self._m),
            self.sg.pool.vals,
            U.dense,
            state,
            F=F,
            C=C,
            mode=mode,
            n=self._n,
            ids_budget=ids_b,
            edge_budget=edge_b,
            ops=self.ops,
            mesh=self.mesh,
            weighted=self.sg.pool.vals is not None,
        )
        return JaxVertexSubset(out), state

    # -- dense semiring reduce ---------------------------------------------
    def edge_map_reduce(self, values: jax.Array) -> jax.Array:
        return self.edge_map_reduce_batch(values[None, :])[0]

    def edge_map_reduce_batch(self, values: jax.Array) -> jax.Array:
        out = _sharded_reduce_batch(
            self.aux.src_by_dst,
            self.aux.valid_by_dst,
            self.aux.dst_offsets,
            self.aux.w_by_dst,
            jnp.asarray(values),
            n=self._n,
            mesh=self.mesh,
            weighted=self.aux.w_by_dst is not None,
            dtype=self.ops.float_dtype,
        )
        return out.astype(jnp.asarray(values).dtype)

    # -- in-trace batched drivers ------------------------------------------
    def bfs_batch(self, sources) -> Tuple[jax.Array, jax.Array]:
        padded, B = JaxEngine._quantized_sources(sources)
        parents, depths = bfs_batch_sharded(
            self.aux.offsets,
            self.sg.pool.data,
            self.aux.src_c,
            self.aux.dst_c,
            self.aux.evalid,
            self.aux.degrees,
            self.aux.src_by_dst,
            self.aux.valid_by_dst,
            self.aux.dst_offsets,
            jnp.int32(self._m),
            padded,
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
        )
        return parents[:B], depths[:B]

    def bc_batch(self, sources) -> jax.Array:
        """Multi-source Brandes dependencies, one in-trace sharded driver
        (``algorithms.bc_multi`` dispatches here instead of running
        generic edge_map rounds)."""
        padded, B = JaxEngine._quantized_sources(sources)
        dep = bc_batch_sharded(
            self.aux.offsets,
            self.aux.src_c,
            self.aux.dst_c,
            self.aux.evalid,
            self.aux.src_by_dst,
            self.aux.valid_by_dst,
            self.aux.dst_offsets,
            padded,
            n=self._n,
            mesh=self.mesh,
            float_dtype=self.ops.float_dtype,
        )
        return dep[:B]

    def sssp_batch(self, sources) -> jax.Array:
        padded, B = JaxEngine._quantized_sources(sources)
        weighted = self.sg.pool.vals is not None
        dist = sssp_batch_sharded(
            self.aux.offsets,
            self.sg.pool.data,
            self.aux.src_c,
            self.aux.dst_c,
            self.aux.evalid,
            self.aux.degrees,
            self.aux.src_by_dst,
            self.aux.valid_by_dst,
            self.aux.dst_offsets,
            self.sg.pool.vals if weighted else None,
            self.aux.w_by_dst if weighted else None,
            jnp.int32(self._m),
            padded,
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
            weighted=weighted,
            float_dtype=self.ops.float_dtype,
        )
        return dist[:B]

    def sssp_batch_from(self, dist0, frontier0, unit: bool = False) -> jax.Array:
        """Warm-start (min, +) relaxation from arbitrary initial state
        (see ``sssp_batch_sharded_from``) — the incremental BFS/SSSP
        driver on the sharded pool."""
        dist0, frontier0, B = JaxEngine._quantized_state(dist0, frontier0)
        weighted = self.sg.pool.vals is not None and not unit
        dist = sssp_batch_sharded_from(
            self.aux.offsets,
            self.sg.pool.data,
            self.aux.src_c,
            self.aux.dst_c,
            self.aux.evalid,
            self.aux.degrees,
            self.aux.src_by_dst,
            self.aux.valid_by_dst,
            self.aux.dst_offsets,
            self.sg.pool.vals if weighted else None,
            self.aux.w_by_dst if weighted else None,
            jnp.int32(self._m),
            jnp.asarray(dist0, self.ops.float_dtype),
            jnp.asarray(frontier0),
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
            weighted=weighted,
            unit=unit,
            float_dtype=self.ops.float_dtype,
        )
        return dist[:B]

    # -- vertexMap ----------------------------------------------------------
    def vertex_map(self, U: JaxVertexSubset, Pred: Callable, state) -> JaxVertexSubset:
        keep = Pred(self.ops, state, jnp.arange(self._n, dtype=jnp.int32))
        return JaxVertexSubset(U.dense & keep)

    def to_host(self, x) -> np.ndarray:
        from .base import HOST_SYNCS

        HOST_SYNCS.bump()
        return np.asarray(x)


# ---------------------------------------------------------------------------
# compressed sharded backend: queries over CompressedShardedGraph
# ---------------------------------------------------------------------------


class CompressedShardAux(NamedTuple):
    """Per-shard derived state for ``CompressedShardedEngine`` — the
    sharded counterpart of ``jax_backend.CompressedAux``.

    The two O(cap) int lanes of ``ShardAux`` that dominate its footprint
    (``dst_sorted``, ``src_by_dst``) are chunk-compressed per shard row;
    ``valid_by_dst`` collapses to one count per row (valid slots are the
    sorted prefix).  The O(S·n) arrays stay raw.  Every leaf keeps the
    (n_shards, ...) layout so ``P('shard', ...)`` specs still apply.
    """

    dst_sorted_c: cz.ChunkedStream  # (S, ...) destinations ascending
    srcbd_c: cz.ChunkedStream  # (S, ...) sources permuted dst-major
    dst_offsets: jax.Array  # int32[S, n+1]
    degrees: jax.Array  # int32[S, n]
    deg_total: jax.Array  # int32[n]
    m_valid: jax.Array  # int32[S] valid slots per shard row
    w_by_dst: Optional[jax.Array] = None  # float32[S, cap] dst-major


@functools.partial(jax.jit, static_argnums=(1, 2))
def shard_aux_compressed(
    cp: CompressedShardedPool, n: int, aux_hi_cap: Optional[int] = None
) -> CompressedShardAux:
    """One jit: decompress -> ``shard_aux`` -> re-compress the big int
    lanes (vmapped per shard row, so GSPMD keeps the encode shard-local).
    The uncompressed aux is a transient of this trace.  An adaptive pool
    gets adaptive aux lanes with the pool's hi capacity, overridable via
    ``aux_hi_cap`` (the engine retries at full capacity when only the
    aux permutation lanes overflow the inherited plane)."""
    p = _decompress_pool_impl(cp)
    aux = shard_aux(p, n)
    width, k = cp.dst.width, cp.dst.k
    if cp.dst.hi is not None:
        hc = cp.dst.hi.shape[-2] if aux_hi_cap is None else aux_hi_cap
        enc = jax.vmap(lambda v: cz._encode_adaptive_impl(v, hc, k))
    else:
        enc = jax.vmap(lambda v: cz._encode_impl(v, width, k))
    return CompressedShardAux(
        dst_sorted_c=enc(aux.dst_sorted),
        srcbd_c=enc(aux.src_by_dst),
        dst_offsets=aux.dst_offsets,
        degrees=aux.degrees,
        deg_total=aux.deg_total,
        m_valid=aux.evalid.sum(axis=1).astype(jnp.int32),
        w_by_dst=aux.w_by_dst,
    )


def _inflate_sharded(cp: CompressedShardedPool, caux: CompressedShardAux, n: int):
    """Trace-level inflate: (pool, aux) -> (ShardedPool, ShardAux) inside
    the caller's jit — the sharded analogue of ``jax_backend._inflate``.
    Forward lanes (clipped endpoints, validity) are recomputed from the
    decoded keys (cheaper than storing them); the dst-major permutation
    lanes decode from their streams (recomputing them would redo the
    per-row sort the aux exists to amortize).  All per-row, so the decode
    stays shard-local under GSPMD."""
    p = _decompress_pool_impl(cp)
    cap = p.data.shape[1]

    def row(drow, nrow):
        src = (drow >> 32).astype(jnp.int32)
        dst = (drow & 0xFFFFFFFF).astype(jnp.int32)
        valid = jnp.arange(cap) < nrow
        evalid = valid & (dst >= 0) & (dst < n)
        return (
            jnp.clip(src, 0, max(n - 1, 0)),
            jnp.clip(dst, 0, max(n - 1, 0)),
            evalid,
        )

    src_c, dst_c, evalid = jax.vmap(row)(p.data, p.n)
    aux = ShardAux(
        offsets=cp.offsets,
        src_c=src_c,
        dst_c=dst_c,
        evalid=evalid,
        degrees=caux.degrees,
        deg_total=caux.deg_total,
        dst_sorted=cz.decode_stream(caux.dst_sorted_c),
        src_by_dst=cz.decode_stream(caux.srcbd_c),
        valid_by_dst=jnp.arange(cap)[None, :] < caux.m_valid[:, None],
        dst_offsets=caux.dst_offsets,
        w_by_dst=caux.w_by_dst,
    )
    return p, aux


@functools.partial(
    jax.jit,
    static_argnames=(
        "F", "C", "mode", "n", "ids_budget", "edge_budget", "ops", "mesh", "weighted",
    ),
)
def _sharded_edge_map_step_compressed(
    cp, caux, m, U, state, *,
    F, C, mode, n, ids_budget, edge_budget, ops, mesh, weighted,
):
    p, aux = _inflate_sharded(cp, caux, n)
    return _sharded_edge_map_step(
        aux.offsets, p.data, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        m, p.vals if weighted else None, U, state,
        F=F, C=C, mode=mode, n=n,
        ids_budget=ids_budget, edge_budget=edge_budget,
        ops=ops, mesh=mesh, weighted=weighted,
    )


@functools.partial(
    jax.jit, static_argnames=("n", "ids_budget", "edge_budget", "mesh")
)
def bfs_batch_sharded_compressed(
    cp, caux, m, sources, *, n, ids_budget, edge_budget, mesh
):
    p, aux = _inflate_sharded(cp, caux, n)
    return bfs_batch_sharded(
        aux.offsets, p.data, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        aux.src_by_dst, aux.valid_by_dst, aux.dst_offsets, m, sources,
        n=n, ids_budget=ids_budget, edge_budget=edge_budget, mesh=mesh,
    )


@functools.partial(jax.jit, static_argnames=("n", "mesh", "float_dtype"))
def bc_batch_sharded_compressed(
    cp, caux, sources, *, n, mesh, float_dtype=jnp.float32
):
    p, aux = _inflate_sharded(cp, caux, n)
    return bc_batch_sharded(
        aux.offsets, aux.src_c, aux.dst_c, aux.evalid,
        aux.src_by_dst, aux.valid_by_dst, aux.dst_offsets, sources,
        n=n, mesh=mesh, float_dtype=float_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "ids_budget", "edge_budget", "mesh", "weighted", "float_dtype"),
)
def sssp_batch_sharded_compressed(
    cp, caux, m, sources, *,
    n, ids_budget, edge_budget, mesh, weighted, float_dtype=jnp.float32,
):
    p, aux = _inflate_sharded(cp, caux, n)
    return sssp_batch_sharded(
        aux.offsets, p.data, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        aux.src_by_dst, aux.valid_by_dst, aux.dst_offsets,
        p.vals if weighted else None,
        aux.w_by_dst if weighted else None,
        m, sources,
        n=n, ids_budget=ids_budget, edge_budget=edge_budget, mesh=mesh,
        weighted=weighted, float_dtype=float_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n", "ids_budget", "edge_budget", "mesh", "weighted", "unit", "float_dtype"
    ),
)
def sssp_batch_sharded_from_compressed(
    cp, caux, m, dist0, frontier0, *,
    n, ids_budget, edge_budget, mesh, weighted, unit=False,
    float_dtype=jnp.float32,
):
    p, aux = _inflate_sharded(cp, caux, n)
    return sssp_batch_sharded_from(
        aux.offsets, p.data, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        aux.src_by_dst, aux.valid_by_dst, aux.dst_offsets,
        p.vals if weighted else None,
        aux.w_by_dst if weighted else None,
        m, dist0, frontier0,
        n=n, ids_budget=ids_budget, edge_budget=edge_budget, mesh=mesh,
        weighted=weighted, unit=unit, float_dtype=float_dtype,
    )


def _reduce_partial_compressed(
    anch, dl, pos, add, hi, wide, mv, bounds, wbd, values_b, n_pad, dtype
):
    """Per-device partial of the (+, x) reduce with the src gather lane
    decoded INSIDE the shard-local function — the sharded half of the
    fused-decode contract (the sharded reduce is a segmented row-sum, not
    the Pallas kernel, so 'inside the kernel' here means inside the
    shard_map body where the operand never exists uncompressed outside
    this trace).  ``hi``/``wide`` are the adaptive-width leaves (None on
    fixed-width streams); the per-row decode handles the width select."""
    no_spill = jnp.zeros((), bool)

    def one(anch_r, dl_r, pos_r, add_r, hi_r, wide_r, mv_r, brow, wrow):
        srow = cz.decode_rows(
            cz.ChunkedStream(
                anch_r, dl_r, pos_r, add_r, no_spill, hi=hi_r, wide=wide_r
            )
        ).reshape(-1)
        vrow = jnp.arange(srow.shape[0]) < mv_r
        msg = jnp.where(vrow[None, :], values_b[:, srow], 0.0).astype(dtype)
        if wrow is not None:
            msg = msg * wrow[None, :].astype(dtype)
        return _segsum_rows(msg, brow)

    def vone(a, d, p, v, h, wd, c, b, w=None):
        return one(a, d, p, v, h, wd, c, b, w)

    if hi is None:
        fone = lambda a, d, p, v, c, b, w=None: one(a, d, p, v, None, None, c, b, w)
        if wbd is None:
            parts = jax.vmap(lambda a, d, p, v, c, b: fone(a, d, p, v, c, b))(
                anch, dl, pos, add, mv, bounds
            )
        else:
            parts = jax.vmap(fone)(anch, dl, pos, add, mv, bounds, wbd)
    else:
        if wbd is None:
            parts = jax.vmap(
                lambda a, d, p, v, h, wd, c, b: vone(a, d, p, v, h, wd, c, b)
            )(anch, dl, pos, add, hi, wide, mv, bounds)
        else:
            parts = jax.vmap(vone)(anch, dl, pos, add, hi, wide, mv, bounds, wbd)
    partial = parts.sum(axis=0)  # (B, n)
    padded = jnp.pad(partial, ((0, 0), (0, n_pad - partial.shape[1])))
    return jax.lax.psum_scatter(padded, AXIS, scatter_dimension=1, tiled=True)


@functools.partial(jax.jit, static_argnames=("n", "mesh", "weighted", "dtype"))
def _sharded_reduce_batch_compressed(
    srcbd_c,  # cz.ChunkedStream, (S, ...) leaves
    m_valid,  # int32[S]
    dst_offsets,  # int32[S, n+1]
    w_by_dst,  # float32[S, cap] or None
    values_b,  # (B, n) replicated value rows
    *,
    n: int,
    mesh: Mesh,
    weighted: bool,
    dtype,
):
    n_pad = _round_up(max(n, 1), mesh.shape[AXIS])
    adaptive = srcbd_c.hi is not None
    if adaptive:
        # hi is (S, H, CHUNK): shard axis leads, rest replicated per row
        stream = (
            srcbd_c.anchors, srcbd_c.deltas, srcbd_c.ovf_pos, srcbd_c.ovf_add,
            srcbd_c.hi, srcbd_c.wide,
        )
        stream_specs = (_SPEC2,) * 4 + (P(AXIS, None, None), _SPEC2)
    else:
        stream = (srcbd_c.anchors, srcbd_c.deltas, srcbd_c.ovf_pos, srcbd_c.ovf_add)
        stream_specs = (_SPEC2,) * 4
    ns = len(stream)

    def local(*args):
        s, rest = args[:ns], args[ns:]
        hi_l, wide_l = (s[4], s[5]) if adaptive else (None, None)
        if weighted:
            c, b, w, x = rest
        else:
            (c, b, x), w = rest, None
        return _reduce_partial_compressed(
            s[0], s[1], s[2], s[3], hi_l, wide_l, c, b, w, x, n_pad, dtype
        )

    if weighted:
        out = shard_map(
            local,
            mesh=mesh,
            in_specs=stream_specs + (P(AXIS), _SPEC2, _SPEC2, P()),
            out_specs=P(None, AXIS),
        )(*stream, m_valid, dst_offsets, w_by_dst, values_b)
    else:
        out = shard_map(
            local,
            mesh=mesh,
            in_specs=stream_specs + (P(AXIS), _SPEC2, P()),
            out_specs=P(None, AXIS),
        )(*stream, m_valid, dst_offsets, values_b)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _sharded_weighted_degrees_compressed(cp, *, n, dtype):
    p = _decompress_pool_impl(cp)
    cap = p.data.shape[1]

    def row(drow, nrow):
        dst = (drow & 0xFFFFFFFF).astype(jnp.int32)
        return (jnp.arange(cap) < nrow) & (dst >= 0) & (dst < n)

    evalid = jax.vmap(row)(p.data, p.n)
    return _sharded_weighted_degrees(cp.offsets, evalid, p.vals, dtype)


class CompressedShardedEngine(ShardedEngine):
    """``ShardedEngine`` served from a chunk-compressed resident pool.

    Holds a ``CompressedShardedPool`` + ``CompressedShardAux``; every
    query step inflates per shard row inside its own jit (decoded rows
    are transients of the trace) and then runs the exact raw shard_map
    step — same collective schedule, same wire contract, compressed HBM
    residency.  Frontier helpers / budgets / vertexMap are inherited;
    only the data-touching dispatch targets differ.
    """

    def __init__(
        self,
        csg: CompressedShardedGraph,
        aux: Optional[CompressedShardAux] = None,
        mesh: Optional[Mesh] = None,
        float_dtype=None,
    ):
        self.csg = csg
        self._n = csg.n
        self.mesh = pool_mesh(csg.n_shards) if mesh is None else mesh
        if csg.n_shards % self.mesh.shape[AXIS] != 0:
            raise ValueError(
                f"n_shards={csg.n_shards} must be a multiple of the mesh "
                f"size {self.mesh.shape[AXIS]}"
            )
        self._m = graph_num_edges(csg)  # one device read per engine build
        self.ops = ShardedOps(jnp.float32 if float_dtype is None else float_dtype)
        self.caux = (
            shard_aux_compressed(csg.pool, csg.n) if aux is None else aux
        )
        self._wdeg = None
        # Spill check: construction already syncs (graph_num_edges), so
        # reading the flag rows here is free — a spilled stream would
        # silently mis-decode every query.
        pool_spilled = bool(np.asarray(csg.pool.dst.spill).any())
        aux_spilled = bool(
            np.asarray(self.caux.dst_sorted_c.spill).any()
        ) or bool(np.asarray(self.caux.srcbd_c.spill).any())
        if (
            not pool_spilled and aux_spilled and aux is None
            and csg.pool.dst.hi is not None
        ):
            # Adaptive aux lanes inherited the pool's (exact-fit) hi
            # capacity but need more wide chunks; retry once at full
            # capacity before declaring a genuine escape-lane spill.
            R = csg.pool.dst.deltas.shape[-2]
            self.caux = shard_aux_compressed(csg.pool, csg.n, R)
            aux_spilled = bool(
                np.asarray(self.caux.dst_sorted_c.spill).any()
            ) or bool(np.asarray(self.caux.srcbd_c.spill).any())
        if pool_spilled or aux_spilled:
            raise ValueError(
                "compressed sharded stream spilled its escape lane; "
                "rebuild with a wider delta lane or keep the raw engine"
            )

        S = csg.n_shards
        cap = csg.pool.cap_per
        total_cap = S * cap
        self._auto_ids_budget = min(
            self._n, _round_up(total_cap // DENSE_THRESHOLD_DENOM + 1, 64)
        )
        self._auto_edge_budget = min(
            cap, _round_up(total_cap // DENSE_THRESHOLD_DENOM + 1, 64)
        )
        self._full_ids_budget = self._n
        self._full_edge_budget = max(cap, 1)

    @property
    def degrees(self) -> jax.Array:
        return self.caux.deg_total

    @property
    def weights(self) -> Optional[jax.Array]:
        return self.csg.pool.vals

    @property
    def weighted_degrees(self) -> jax.Array:
        if self.csg.pool.vals is None:
            return self.caux.deg_total.astype(self.ops.float_dtype)
        if self._wdeg is None:
            self._wdeg = _sharded_weighted_degrees_compressed(
                self.csg.pool, n=self._n, dtype=self.ops.float_dtype
            )
        return self._wdeg

    @property
    def resident_nbytes(self) -> int:
        return cz.pytree_nbytes(self.csg.pool) + cz.pytree_nbytes(self.caux)

    def edge_map(self, U, F, C, state, direction_optimize=True, mode="auto"):
        if mode == "auto" and not direction_optimize:
            mode = "sparse"
        ids_b, edge_b = self._budgets(mode)
        state, out = _sharded_edge_map_step_compressed(
            self.csg.pool, self.caux, jnp.int32(self._m), U.dense, state,
            F=F, C=C, mode=mode, n=self._n,
            ids_budget=ids_b, edge_budget=edge_b,
            ops=self.ops, mesh=self.mesh,
            weighted=self.csg.pool.vals is not None,
        )
        return JaxVertexSubset(out), state

    def edge_map_reduce_batch(self, values: jax.Array) -> jax.Array:
        out = _sharded_reduce_batch_compressed(
            self.caux.srcbd_c,
            self.caux.m_valid,
            self.caux.dst_offsets,
            self.caux.w_by_dst,
            jnp.asarray(values),
            n=self._n,
            mesh=self.mesh,
            weighted=self.caux.w_by_dst is not None,
            dtype=self.ops.float_dtype,
        )
        return out.astype(jnp.asarray(values).dtype)

    def bfs_batch(self, sources) -> Tuple[jax.Array, jax.Array]:
        padded, B = JaxEngine._quantized_sources(sources)
        parents, depths = bfs_batch_sharded_compressed(
            self.csg.pool, self.caux, jnp.int32(self._m), padded,
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
        )
        return parents[:B], depths[:B]

    def bc_batch(self, sources) -> jax.Array:
        padded, B = JaxEngine._quantized_sources(sources)
        dep = bc_batch_sharded_compressed(
            self.csg.pool, self.caux, padded,
            n=self._n, mesh=self.mesh, float_dtype=self.ops.float_dtype,
        )
        return dep[:B]

    def sssp_batch(self, sources) -> jax.Array:
        padded, B = JaxEngine._quantized_sources(sources)
        dist = sssp_batch_sharded_compressed(
            self.csg.pool, self.caux, jnp.int32(self._m), padded,
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
            weighted=self.csg.pool.vals is not None,
            float_dtype=self.ops.float_dtype,
        )
        return dist[:B]

    def sssp_batch_from(self, dist0, frontier0, unit: bool = False) -> jax.Array:
        dist0, frontier0, B = JaxEngine._quantized_state(dist0, frontier0)
        weighted = self.csg.pool.vals is not None and not unit
        dist = sssp_batch_sharded_from_compressed(
            self.csg.pool, self.caux, jnp.int32(self._m),
            jnp.asarray(dist0, self.ops.float_dtype), jnp.asarray(frontier0),
            n=self._n,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            mesh=self.mesh,
            weighted=weighted,
            unit=unit,
            float_dtype=self.ops.float_dtype,
        )
        return dist[:B]
