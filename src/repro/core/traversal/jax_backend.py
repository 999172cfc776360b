"""JAX/TPU traversal backend over ``FlatGraph`` (the packed-key pool).

Maps Ligra's edgeMap onto the flat C-tree pool (flat_graph.py):

  * dense ("pull"/whole-pool) direction: every pool slot looks up
    whether its source is in the frontier — one gather + one masked
    scatter, the same shape as GNN aggregation.  The (+, x) semiring
    specialization ``edge_map_reduce`` (PageRank's inner loop) lowers
    to the Pallas one-hot-matmul segment sum in
    ``repro.kernels.segment_reduce`` via ``repro.kernels.ops`` (so it
    runs compiled on TPU and interpret-mode on CPU).

  * sparse ("push") direction: the frontier's adjacency lists are
    contiguous key ranges of the sorted pool, so expansion is a
    fixed-shape ragged gather: nonzero(size=K) frontier ids ->
    searchsorted over per-id degree prefix sums -> pool indices.  No
    dynamic shapes, so the whole push/pull step jits once per
    (F, C, mode) and is reused across iterations and engines.

Direction optimization (|U| + deg(U) > m/20, paper §5.1) runs inside
the jit step as a ``lax.cond``, so one compiled step serves both
directions; the sparse branch's static budgets are sized from the
threshold (a frontier routed sparse can never exceed cap/20 ids or
pool-capacity/20 edges).

Batched multi-source queries (DESIGN.md §7)
-------------------------------------------
``_edge_map_step_batch`` generalizes the step over a ``(B, n)``
frontier batch: the per-lane Beamer rule feeds a *batched* ``lax.cond``
(any over-threshold lane routes the whole round dense — dense is
correct for every frontier size, while the sparse budgets only hold for
under-threshold lanes), so exactly one branch executes per round.  The
in-trace drivers ``bfs_batch`` / ``bc_batch`` fuse whole frontier loops
into one ``lax.while_loop`` — a multi-source traversal is ONE device
dispatch with ONE final sync instead of D·B round-trip-synced steps —
and their pull rounds are the (or, and)/(+, x) semiring
specializations of the dense direction: a segmented row-cumsum over the
dst-major pool (scatter-free; the batched analogue of
``edge_map_reduce``).

Weighted graphs (contract v2, DESIGN.md §8)
-------------------------------------------
A ``FlatGraph`` carrying a value array threads it through every path:
the sparse branch gathers ``weights[eidx]`` alongside the expanded
edge lanes, the dense branch hands F the pool-parallel array directly,
``edge_map_reduce`` dispatches the WEIGHTED Pallas segment-sum
(``out[v] = sum w(u,v) * values[u]``), and the in-trace ``sssp_batch``
driver runs the (min, +) semiring via a segmented row-min scan over
the dst-major pool.  When ``g.weights is None`` every one of these
branches folds away at trace time: no value array is allocated or
read, and the compiled steps are byte-identical to the unweighted
engine's (tests spy on the kernel dispatch to pin this).

Precision contract: the engine computes in ``float32`` by default —
the TPU-native dtype, and what the kernel reduce always accumulated in
anyway (the old ``float_dtype = jnp.float64`` default contradicted the
hardcoded f32 cast in ``_reduce_msgs``, and outside this repo — which
enables ``jax_enable_x64`` globally for the packed int64 keys — it
would silently downcast to f32).  Pass ``float_dtype=jnp.float64`` to
``JaxEngine`` for double-precision state arrays AND reduce
accumulation (requires x64; repro enables it).  Cross-backend parity
versus the float64 numpy engine is to float32 tolerance by default.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

from .. import compressed as cz
from .. import flat_graph as _fg
from ..flat_graph import CompressedPool, FlatGraph, unpack
from .base import DENSE_THRESHOLD_DENOM, HOST_SYNCS, TRACES, ArrayOps, TraversalEngine


class JaxOps(ArrayOps):
    """Functional array helpers for jit-traced F/C callbacks.

    ``float_dtype`` defaults to float32 — the engine's explicit compute
    dtype (see the module docstring's precision contract).  Instances
    hash/compare by dtype so they can be jit-static arguments without
    fragmenting the trace cache across engines.
    """

    xp = jnp
    int_dtype = jnp.int32

    def __init__(self, float_dtype=jnp.float32):
        self.float_dtype = float_dtype

    def __eq__(self, other):
        return type(other) is type(self) and (
            np.dtype(other.float_dtype) == np.dtype(self.float_dtype)
        )

    def __hash__(self):
        return hash((type(self), np.dtype(self.float_dtype).name))

    def set_at(self, arr, idx, vals):
        return arr.at[idx].set(vals)

    def _safe_idx(self, target, idx, mask):
        # OOB indices are dropped by mode="drop": masking = index escape
        return jnp.where(mask, idx, target.shape[0])

    def scatter_max(self, target, idx, vals, mask):
        return target.at[self._safe_idx(target, idx, mask)].max(vals, mode="drop")

    def scatter_min(self, target, idx, vals, mask):
        return target.at[self._safe_idx(target, idx, mask)].min(vals, mode="drop")

    def scatter_add(self, target, idx, vals, mask):
        vals = jnp.where(mask, vals, jnp.zeros((), target.dtype))
        return target.at[self._safe_idx(target, idx, mask)].add(vals, mode="drop")

    def scatter_or(self, target, idx, mask):
        return target.at[self._safe_idx(target, idx, mask)].max(True, mode="drop")


JAX_OPS = JaxOps()


class JaxVertexSubset:
    """Dense bool[n] frontier.  ``size``/``empty`` force a device→host
    sync (python-level loop control); the count is computed ONCE per
    subset and cached — algorithms probe ``U.empty`` every round, and a
    per-access sync was a measurable serial cost inside traversal loops.
    """

    __slots__ = ("dense", "_size")

    def __init__(self, dense: jax.Array):
        self.dense = dense  # bool[n]
        self._size: Optional[int] = None

    @property
    def n(self) -> int:
        return self.dense.shape[0]

    @property
    def size(self) -> int:
        if self._size is None:
            HOST_SYNCS.bump()
            self._size = int(self.dense.sum())
        return self._size

    @property
    def empty(self) -> bool:
        return self.size == 0

    def to_dense(self) -> jax.Array:
        return self.dense

    def to_sparse(self) -> np.ndarray:
        return np.flatnonzero(np.asarray(self.dense))


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# per-snapshot engine auxiliary state (one jit pytree, device-resident)
# ---------------------------------------------------------------------------


class EngineAux(NamedTuple):
    """Everything ``JaxEngine`` derives from a snapshot, as one pytree.

    Refreshing it is ONE fixed-shape jit call — no host loops, no host
    argsort — so an engine over a freshly-merged mirror costs O(cap)
    device work instead of the old O(m log m) host precompute, and the
    pytree itself can be version-pinned and reused across queries (the
    whole-graph loops and batched drivers below all accept it
    prebuilt).  ``w_by_dst`` is the per-edge value array permuted
    dst-major (for weighted pull rounds and the weighted kernel
    reduce); it is None — no array, no extra leaves, identical traces —
    on unweighted graphs.
    """

    src_c: jax.Array  # int32[cap] clipped sources
    dst_c: jax.Array  # int32[cap] clipped destinations
    evalid: jax.Array  # bool[cap] slot < m
    degrees: jax.Array  # int32[n]
    dst_sorted: jax.Array  # int32[cap] destinations ascending (pad=n)
    src_by_dst: jax.Array  # int32[cap] sources permuted dst-major
    valid_by_dst: jax.Array  # bool[cap]
    dst_offsets: jax.Array  # int32[n+1] segment bounds into dst_sorted
    w_by_dst: Optional[jax.Array] = None  # float32[cap] values dst-major


def _pool_endpoints(g: FlatGraph):
    """(src_c, dst_c, evalid): the clipped-endpoint subset of
    ``EngineAux`` (shared by ``engine_aux`` and, as a fallback when no
    prebuilt aux is supplied, by the whole-graph loops).  A slot is
    usable iff it holds a real edge AND its destination is a real
    vertex: an asymmetric stream can store an edge naming a
    never-source vertex id >= n, and every query direction must DROP it
    (not fold it into the clipped n-1)."""
    n = g.offsets.shape[0] - 1
    src, dst = unpack(g.keys)
    evalid = (jnp.arange(g.keys.shape[0]) < g.m) & (dst >= 0) & (dst < n)
    return (
        jnp.clip(src, 0, max(n - 1, 0)),
        jnp.clip(dst, 0, max(n - 1, 0)),
        evalid,
    )


@jax.jit
def engine_aux(g: FlatGraph) -> EngineAux:
    """Device scopes, one per phase: ``engine_aux.endpoints`` (unpack
    and clip the pool), ``engine_aux.sort`` (the dst-major argsort),
    ``engine_aux.permute`` (the gathers into dst-major order) and
    ``engine_aux.offsets`` (degrees and the dst segment bounds)."""
    n = g.offsets.shape[0] - 1
    with jax.named_scope("engine_aux.endpoints"):
        src_c, dst_c, evalid = _pool_endpoints(g)
    # dst-major permutation for the Pallas segment-sum and the batched
    # pull rounds (the pool is src-major): on-device sort-by-key
    # replaces the old host argsort.  valid => dst == dst_c, so the
    # clipped endpoints are exact here.
    with jax.named_scope("engine_aux.sort"):
        dst_key = jnp.where(evalid, dst_c, jnp.int32(n))
        order = jnp.argsort(dst_key, stable=True)
    with jax.named_scope("engine_aux.permute"):
        dst_sorted = dst_key[order]
        src_by_dst = src_c[order]
        valid_by_dst = evalid[order]
        w_by_dst = None if g.weights is None else g.weights[order]
    with jax.named_scope("engine_aux.offsets"):
        degrees = jnp.diff(g.offsets)
        dst_offsets = jnp.searchsorted(
            dst_sorted, jnp.arange(n + 1, dtype=jnp.int32)
        ).astype(jnp.int32)
    return EngineAux(
        src_c=src_c,
        dst_c=dst_c,
        evalid=evalid,
        degrees=degrees,
        dst_sorted=dst_sorted,
        src_by_dst=src_by_dst,
        valid_by_dst=valid_by_dst,
        dst_offsets=dst_offsets,
        w_by_dst=w_by_dst,
    )


# ---------------------------------------------------------------------------
# the jit-compiled edgeMap step (module-level: cache shared across engines)
# ---------------------------------------------------------------------------


_SCAN_BLOCK = 128


def _shift_right(x: jax.Array, s: int, fill) -> jax.Array:
    """``x`` moved ``s`` places along the last axis; ``fill`` enters."""
    pad = jnp.full(x.shape[:-1] + (s,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., : x.shape[-1] - s]], axis=-1)


def _hs_scan(v, f, combine, ident):
    """Hillis–Steele segmented inclusive scan along the last axis:
    (value, start-flag) pairs under the resetting operator
    ``(x, y) -> (y.f ? y.v : combine(x.v, y.v), x.f | y.f)``.  Returns
    the scanned values and the prefix-OR of the flags (``f=None``: an
    unsegmented scan)."""
    s = 1
    while s < v.shape[-1]:
        shifted = combine(_shift_right(v, s, ident), v)
        if f is None:
            v = shifted
        else:
            v = jnp.where(f, v, shifted)
            f = f | _shift_right(f, s, False)
        s *= 2
    return v, f


def _blocked_scan(x, flags, combine, ident):
    """Inclusive scan along the last axis, segmented when ``flags`` (a
    bool vector of segment starts over that axis) is given.  Two levels:
    a scan inside each ``_SCAN_BLOCK``-lane block, then one over the
    block carries.  Shifted adds compile in seconds at any size, where
    ``jnp.cumsum`` (a reduce-window) and ``lax.associative_scan`` take the
    TPU compiler tens of seconds to minutes on a 2^20..2^22 axis.  A
    segmented sum never runs across a segment boundary, so integer and
    small-float segment sums are exact."""
    *lead, L = x.shape
    blk = math.gcd(L, _SCAN_BLOCK)
    f = None if flags is None else flags.reshape(L // blk, blk)
    v, f = _hs_scan(x.reshape(*lead, L // blk, blk), f, combine, ident)
    carry, _ = _hs_scan(v[..., -1], None if f is None else f[..., -1], combine, ident)
    carry = _shift_right(carry, 1, ident)[..., None]  # exclusive over blocks
    out = combine(carry, v)
    return (out if f is None else jnp.where(f, v, out)).reshape(x.shape)


def _cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis in ``x``'s own dtype."""
    return _blocked_scan(x, None, jnp.add, jnp.zeros((), x.dtype))


def _take_mask(f_b: jax.Array, idx: jax.Array) -> jax.Array:
    """``f_b[:, idx]`` for a (B, n) bool mask, gathered as int32: the TPU
    compiler gives a bool gather of a 2^23-slot index over a gigabyte of
    temporaries (9 GiB in a B=4 pull round), an int32 one none."""
    return f_b.astype(jnp.int32)[:, idx] != 0


def _nonzero_i32(mask: jax.Array, size: int, fill_value: int) -> jax.Array:
    """``jnp.nonzero(mask, size=size, fill_value=fill_value)[0]`` for a
    1-D mask, computed in int32 (the same rank/bincount/cumsum method).
    Under x64, ``jnp.nonzero`` scans in int64, which the TPU emulates on
    pairs of 32-bit words and which outgrows its scoped VMEM at n=2^17."""
    rank = _cumsum(mask.astype(jnp.int32))
    counts = jnp.zeros(size, jnp.int32).at[rank].add(1, mode="drop")
    ids = _cumsum(counts)
    return jnp.where(jnp.arange(size, dtype=jnp.int32) < rank[-1], ids, fill_value)


def _sparse_expand(offsets, keys, U, n: int, ids_budget: int, edge_budget: int):
    """Fixed-shape push expansion of one bool[n] frontier:
    (us, vs, ev, eidx) edge lanes where ``ev`` masks the padded tail
    and edges naming nonexistent destination vertices; ``eidx`` is each
    lane's pool slot (for gathering per-edge values alongside)."""
    ids_raw = _nonzero_i32(U, ids_budget, n)
    vid = ids_raw < n
    ids = jnp.where(vid, ids_raw, 0).astype(jnp.int32)
    # int32 edge arithmetic: slot ids stay below the pool capacity, and
    # int64 scans are emulated on the TPU (and outgrow its scoped VMEM)
    starts = offsets[ids].astype(jnp.int32)
    degs = jnp.where(vid, (offsets[ids + 1] - offsets[ids]), 0).astype(jnp.int32)
    cum = _cumsum(degs)
    j = jnp.arange(edge_budget, dtype=jnp.int32)
    seg = jnp.searchsorted(cum, j, side="right")
    seg = jnp.clip(seg, 0, ids_budget - 1)
    prev = jnp.where(seg > 0, cum[jnp.maximum(seg - 1, 0)], 0)
    eidx = starts[seg] + (j - prev)
    ev = j < cum[-1]
    eidx = jnp.where(ev, eidx, 0)
    vs_raw = keys[eidx] & 0xFFFFFFFF  # int64: no wraparound
    ev = ev & (vs_raw < n)  # drop edges naming nonexistent vertices
    vs = jnp.clip(vs_raw.astype(jnp.int32), 0, n - 1)
    us = ids[seg]
    return us, vs, ev, eidx


@functools.partial(
    jax.jit,
    static_argnames=("F", "C", "mode", "n", "ids_budget", "edge_budget", "ops"),
)
def _edge_map_step(
    offsets,  # int32[n+1]
    keys,  # int64[cap] sorted packed (src<<32|dst)
    src_c,  # int32[cap] clipped sources
    dst_c,  # int32[cap] clipped destinations
    evalid,  # bool[cap] slot < m
    degrees,  # int32[n]
    m,  # int32 scalar
    weights,  # float32[cap] per-edge values, or None (unweighted)
    U,  # bool[n] frontier
    state,  # pytree
    *,
    F: Callable,
    C: Callable,
    mode: str,
    n: int,
    ids_budget: int,
    edge_budget: int,
    ops: JaxOps = JAX_OPS,
):
    cmask = C(ops, state, jnp.arange(n, dtype=jnp.int32))

    def dense_branch(state):
        valid = evalid & U[src_c] & cmask[dst_c]
        return F(ops, state, src_c, dst_c, weights, valid)

    def sparse_branch(state):
        us, vs, ev, eidx = _sparse_expand(offsets, keys, U, n, ids_budget, edge_budget)
        ws = None if weights is None else weights[eidx]
        return F(ops, state, us, vs, ws, ev & cmask[vs])

    if mode == "dense":
        state, out = dense_branch(state)
    elif mode == "sparse":
        state, out = sparse_branch(state)
    else:  # auto: Ligra/Beamer direction optimization, traced
        size = U.sum()
        deg_u = jnp.where(U, degrees, 0).sum()
        use_dense = (size + deg_u) > jnp.maximum(1, m // DENSE_THRESHOLD_DENOM)
        state, out = jax.lax.cond(use_dense, dense_branch, sparse_branch, state)
    return state, out


@functools.partial(
    jax.jit,
    static_argnames=("F", "C", "mode", "n", "ids_budget", "edge_budget", "ops"),
)
def _edge_map_step_batch(
    offsets,
    keys,
    src_c,
    dst_c,
    evalid,
    degrees,
    m,
    weights,  # float32[cap] per-edge values, or None (unweighted)
    U_b,  # bool[B, n] frontier batch (one lane per query)
    state_b,  # pytree with (B, ...) leaves
    *,
    F: Callable,
    C: Callable,
    mode: str,
    n: int,
    ids_budget: int,
    edge_budget: int,
    ops: JaxOps = JAX_OPS,
):
    """The edgeMap step vmapped over a (B, n) frontier batch.

    Direction optimization becomes a *batched* cond: the per-lane
    Beamer rule is evaluated for every lane, and the round routes dense
    iff ANY lane is over threshold — dense is correct for any frontier
    size, while the sparse budgets only bound under-threshold lanes, so
    this is the exact aggregate of the per-lane rule that still
    executes exactly one branch (a per-lane select would pay for both
    branches on every round)."""

    def dense_lane(U, state):
        cmask = C(ops, state, jnp.arange(n, dtype=jnp.int32))
        valid = evalid & U[src_c] & cmask[dst_c]
        return F(ops, state, src_c, dst_c, weights, valid)

    def sparse_lane(U, state):
        cmask = C(ops, state, jnp.arange(n, dtype=jnp.int32))
        us, vs, ev, eidx = _sparse_expand(offsets, keys, U, n, ids_budget, edge_budget)
        ws = None if weights is None else weights[eidx]
        return F(ops, state, us, vs, ws, ev & cmask[vs])

    if mode == "dense":
        return jax.vmap(dense_lane)(U_b, state_b)
    if mode == "sparse":
        return jax.vmap(sparse_lane)(U_b, state_b)
    size_b = U_b.sum(axis=1)
    deg_b = jnp.where(U_b, degrees[None, :], 0).sum(axis=1)
    use_dense = (size_b + deg_b) > jnp.maximum(1, m // DENSE_THRESHOLD_DENOM)
    return jax.lax.cond(
        use_dense.any(),
        lambda s: jax.vmap(dense_lane)(U_b, s),
        lambda s: jax.vmap(sparse_lane)(U_b, s),
        state_b,
    )


@functools.partial(jax.jit, static_argnames=("dtype",))
def _reduce_msgs(values, src_by_dst, valid_by_dst, dtype=jnp.float32):
    return jnp.where(valid_by_dst, values[src_by_dst], 0.0).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _reduce_msgs_batch(values_b, src_by_dst, valid_by_dst, dtype=jnp.float32):
    # (B, n) value rows -> (cap, B) dst-major message columns
    return jnp.where(valid_by_dst[None, :], values_b[:, src_by_dst], 0.0).T.astype(dtype)


# ---------------------------------------------------------------------------
# in-trace batched drivers: whole multi-source traversals, ONE dispatch
# ---------------------------------------------------------------------------


def _seg_reduce(msg_b, bounds, combine, ident, empty):
    """(B, cap) messages + int32[S+1] segment bounds -> (B, S): each
    segment's scan value at its last slot, ``empty`` for empty ones."""
    cap = msg_b.shape[1]
    starts = jnp.zeros(cap, dtype=bool).at[bounds[:-1]].set(True, mode="drop")
    scanned = _blocked_scan(msg_b, starts, combine, ident)
    ends = jnp.clip(bounds[1:] - 1, 0, cap - 1)
    return jnp.where(bounds[1:] > bounds[:-1], scanned[:, ends], empty)


def _lowest(dtype):
    return -jnp.inf if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).min


def _highest(dtype):
    return jnp.inf if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).max


def _segsum_rows(msg_b: jax.Array, bounds: jax.Array) -> jax.Array:
    """Row-wise segmented sum over a contiguously-segmented axis:
    (B, cap) messages + int32[S+1] segment bounds -> (B, S) sums.

    A segmented scan and one gather instead of a scatter: XLA scatters
    serialize per element (they are the batched drivers' bottleneck on
    CPU).  The pool IS the segmentation: src-major segments are
    ``g.offsets``, dst-major segments are ``aux.dst_offsets``."""
    zero = jnp.zeros((), msg_b.dtype)
    return _seg_reduce(msg_b, bounds, jnp.add, zero, zero)


def _segmin_rows(msg_b: jax.Array, bounds: jax.Array) -> jax.Array:
    """Row-wise segmented MIN over a contiguously-segmented axis:
    (B, cap) messages + int32[S+1] segment bounds -> (B, S) minima
    (+inf for empty segments) — the (min, +) analogue of the pull
    rounds' segmented sum, used by ``sssp_batch``."""
    hi = jnp.asarray(_highest(msg_b.dtype), msg_b.dtype)
    return _seg_reduce(msg_b, bounds, jnp.minimum, hi, hi)


@functools.partial(jax.jit, static_argnames=("ids_budget", "edge_budget"))
def bfs_batch(
    g: FlatGraph,
    aux: EngineAux,
    sources: jax.Array,  # int32[B], each in [0, n)
    *,
    ids_budget: int,
    edge_budget: int,
) -> Tuple[jax.Array, jax.Array]:
    """Multi-source direction-optimized BFS, fully in-trace.

    Returns ``(parents, depths)`` int32[B, n] (-1 = unreached; a
    source's parent is itself).  The whole frontier loop of all B lanes
    is one ``lax.while_loop`` — one device dispatch, zero per-round
    host syncs.  Per round the batched Beamer rule picks push
    (budget-bounded vmapped expand) or pull; the pull round is the
    (or, and) semiring specialization of the dense direction — a
    segmented row-cumsum over the dst-major pool, no scatter.  Parents
    are assigned in ONE masked scatter-max pass at the end
    (parent(v) = max u with depth(u) = depth(v) - 1 and u->v — exactly
    the per-round max-contention rule of ``_bfs_relax``), instead of a
    cap-sized scatter per round."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body
    n = g.offsets.shape[0] - 1
    cap = g.keys.shape[0]
    B = sources.shape[0]
    lane = jnp.arange(B)
    sources = sources.astype(jnp.int32)
    depths = jnp.full((B, n), -1, jnp.int32).at[lane, sources].set(0)
    frontier = jnp.zeros((B, n), bool).at[lane, sources].set(True)
    thresh = jnp.maximum(1, g.m // DENSE_THRESHOLD_DENOM)

    def push(f_b):
        def one(U):
            us, vs, ev, _ = _sparse_expand(g.offsets, g.keys, U, n, ids_budget, edge_budget)
            return jnp.zeros(n, bool).at[jnp.where(ev, vs, n)].max(True, mode="drop")

        return jax.vmap(one)(f_b)

    def pull(f_b):
        msg = (_take_mask(f_b, aux.src_by_dst) & aux.valid_by_dst[None, :]).astype(jnp.int32)
        return _segsum_rows(msg, aux.dst_offsets) > 0

    def cond(carry):
        return carry[0].any()

    def body(carry):
        f, dep, d = carry
        size_b = f.sum(axis=1)
        deg_b = jnp.where(f, aux.degrees[None, :], 0).sum(axis=1)
        reached = jax.lax.cond(((size_b + deg_b) > thresh).any(), pull, push, f)
        newly = reached & (dep < 0)
        return newly, jnp.where(newly, d + 1, dep), d + 1

    _, depths, _ = jax.lax.while_loop(cond, body, (frontier, depths, jnp.int32(0)))
    return _parents_pass(g, aux, depths), depths


def _segmax_rows(msg_b: jax.Array, bounds: jax.Array) -> jax.Array:
    """Row-wise segmented MAX over a contiguously-segmented axis:
    (B, cap) messages + int32[S+1] segment bounds -> (B, S) maxima
    (-1 for empty segments).  The (max) twin of ``_segmin_rows``."""
    lo = jnp.asarray(_lowest(msg_b.dtype), msg_b.dtype)
    return _seg_reduce(msg_b, bounds, jnp.maximum, lo, jnp.asarray(-1, msg_b.dtype))


def _parents_pass(g: FlatGraph, aux: EngineAux, depths: jax.Array) -> jax.Array:
    """Assign BFS parents from final depths in ONE pass: parent(v) =
    max u with depth(u) = depth(v) - 1 and u->v — exactly the
    max-contention rule of the numpy backend.  Computed as a segmented
    max over the dst-major pool (each segment IS one vertex's in-edge
    list), because an XLA scatter-max serializes per element on CPU
    while the segmented scan vectorizes like the pull rounds.  Also the
    jitted ``parents_from_depths`` entry point, so incremental BFS
    (which recomputes depths through the warm ``sssp_batch_from`` path)
    derives parents bit-identical to a full ``bfs_batch``."""
    n = g.offsets.shape[0] - 1
    depths = depths.astype(jnp.int32)
    du = depths[:, aux.src_by_dst]
    dv = depths[:, aux.dst_sorted]  # pad slots (dst_sorted == n) clip; masked
    ok = aux.valid_by_dst[None, :] & (du >= 0) & (dv == du + 1)
    msg = jnp.where(ok, jnp.broadcast_to(aux.src_by_dst[None, :], du.shape), -1)
    cand = _segmax_rows(msg, aux.dst_offsets)
    vid = jnp.arange(n, dtype=jnp.int32)[None, :]
    return jnp.where(depths == 0, vid, jnp.where(depths > 0, cand, -1))


parents_from_depths = jax.jit(_parents_pass)


@functools.partial(jax.jit, static_argnames=("float_dtype",))
def bc_batch(
    g: FlatGraph,
    aux: EngineAux,
    sources: jax.Array,  # int32[B], each in [0, n)
    *,
    float_dtype=jnp.float32,
) -> jax.Array:
    """Multi-source Brandes betweenness contributions, fully in-trace.

    Returns dependency scores float[B, n].  Forward pass: sigma
    accumulates per-round shortest-path counts via the (+, x) segmented
    row-cumsum over the dst-major pool; backward pass walks depths from
    the deepest round down, accumulating dependencies per SOURCE — the
    src-major pool is already the CSR segmentation, so that reduce is
    scatter-free too.  Lanes with shallower BFS trees see empty
    frontiers on the extra rounds (no-ops), which keeps both loops as
    single ``lax.while_loop``s over the whole batch."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body
    n = g.offsets.shape[0] - 1
    B = sources.shape[0]
    lane = jnp.arange(B)
    sources = sources.astype(jnp.int32)
    sigma = jnp.zeros((B, n), float_dtype).at[lane, sources].set(1.0)
    depth = jnp.full((B, n), -1, jnp.int32).at[lane, sources].set(0)
    frontier = jnp.zeros((B, n), bool).at[lane, sources].set(True)

    def fcond(carry):
        return carry[0].any()

    def fbody(carry):
        f, sig, dep, d = carry
        w = jnp.where(
            _take_mask(f, aux.src_by_dst) & aux.valid_by_dst[None, :],
            sig[:, aux.src_by_dst],
            jnp.zeros((), float_dtype),
        )
        contrib = _segsum_rows(w, aux.dst_offsets)
        newly = (contrib > 0) & (dep < 0)
        sig = sig + jnp.where(newly, contrib, 0)
        return newly, sig, jnp.where(newly, d + 1, dep), d + 1

    _, sigma, depth, d_final = jax.lax.while_loop(
        fcond, fbody, (frontier, sigma, depth, jnp.int32(0))
    )

    du = depth[:, aux.src_c]
    dv = depth[:, aux.dst_c]

    def bcond(carry):
        return carry[1] >= 0

    def bbody(carry):
        dep_acc, dd = carry
        ok = aux.evalid[None, :] & (du == dd) & (dv == dd + 1)
        ratio = sigma[:, aux.src_c] / jnp.maximum(sigma[:, aux.dst_c], 1e-30)
        contrib = jnp.where(ok, ratio * (1.0 + dep_acc[:, aux.dst_c]), 0)
        return dep_acc + _segsum_rows(contrib, g.offsets), dd - 1

    dep, _ = jax.lax.while_loop(
        bcond, bbody, (jnp.zeros((B, n), float_dtype), d_final - 2)
    )
    return dep.at[lane, sources].set(0.0)


def _bellman_ford(
    g: FlatGraph,
    aux: EngineAux,
    dist: jax.Array,  # float[B, n] initial distances (+inf = unknown)
    frontier: jax.Array,  # bool[B, n] initial relax frontier
    *,
    ids_budget: int,
    edge_budget: int,
    float_dtype=jnp.float32,
    unit: bool = False,
) -> jax.Array:
    """The (min, +) relaxation loop shared by ``sssp_batch`` (point
    sources) and ``sssp_batch_from`` (warm start from a previous
    version's distances): one ``lax.while_loop`` to fixpoint from
    whatever (dist, frontier) it is seeded with.  ``unit=True`` forces
    unit weights — the hop metric on a weighted pool, which is how
    incremental BFS rides this driver."""
    n = g.offsets.shape[0] - 1
    cap = g.keys.shape[0]
    inf = jnp.asarray(jnp.inf, float_dtype)
    w_pool = (
        jnp.ones(cap, float_dtype)
        if (unit or g.weights is None)
        else g.weights.astype(float_dtype)
    )
    w_by_dst = (
        jnp.ones(cap, float_dtype)
        if (unit or aux.w_by_dst is None)
        else aux.w_by_dst.astype(float_dtype)
    )
    thresh = jnp.maximum(1, g.m // DENSE_THRESHOLD_DENOM)

    def push(args):
        f_b, d_b = args

        def one(U, d):
            us, vs, ev, eidx = _sparse_expand(
                g.offsets, g.keys, U, n, ids_budget, edge_budget
            )
            vals = d[us] + w_pool[eidx]
            return (
                jnp.full(n, inf, float_dtype)
                .at[jnp.where(ev, vs, n)]
                .min(vals, mode="drop")
            )

        return jax.vmap(one)(f_b, d_b)

    def pull(args):
        f_b, d_b = args
        msg = jnp.where(
            _take_mask(f_b, aux.src_by_dst) & aux.valid_by_dst[None, :],
            d_b[:, aux.src_by_dst] + w_by_dst[None, :],
            inf,
        )
        return _segmin_rows(msg, aux.dst_offsets)

    def cond(carry):
        return carry[0].any()

    def body(carry):
        f, d = carry
        size_b = f.sum(axis=1)
        deg_b = jnp.where(f, aux.degrees[None, :], 0).sum(axis=1)
        cand = jax.lax.cond(((size_b + deg_b) > thresh).any(), pull, push, (f, d))
        newly = cand < d
        return newly, jnp.where(newly, cand, d)

    _, dist = jax.lax.while_loop(cond, body, (frontier, dist))
    return dist


@functools.partial(
    jax.jit, static_argnames=("ids_budget", "edge_budget", "float_dtype")
)
def sssp_batch(
    g: FlatGraph,
    aux: EngineAux,
    sources: jax.Array,  # int32[B], each in [0, n)
    *,
    ids_budget: int,
    edge_budget: int,
    float_dtype=jnp.float32,
) -> jax.Array:
    """Multi-source Bellman–Ford over the weighted (min, +) semiring,
    fully in-trace: returns distances float[B, n] (+inf = unreached).

    The whole frontier loop (frontier = vertices whose distance
    improved last round) of all B lanes is one ``lax.while_loop`` —
    one device dispatch, zero per-round host syncs, exactly the
    ``bfs_batch`` contract.  Per round the batched Beamer rule picks
    push (budget-bounded vmapped expand + masked scatter-min) or pull;
    the pull round is the (min, +) semiring specialization of the
    dense direction — a segmented row-MIN scan over the dst-major pool
    (``_segmin_rows``), the weighted analogue of the BFS pull's
    row-cumsum.  An unweighted graph runs the same driver with unit
    weights (hop distances), so ``sssp_batch`` never changes what an
    unweighted stream compiles for BFS/BC/PageRank.
    """
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body
    n = g.offsets.shape[0] - 1
    B = sources.shape[0]
    lane = jnp.arange(B)
    sources = sources.astype(jnp.int32)
    inf = jnp.asarray(jnp.inf, float_dtype)
    dist = jnp.full((B, n), inf, float_dtype).at[lane, sources].set(0.0)
    frontier = jnp.zeros((B, n), bool).at[lane, sources].set(True)
    return _bellman_ford(
        g,
        aux,
        dist,
        frontier,
        ids_budget=ids_budget,
        edge_budget=edge_budget,
        float_dtype=float_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=("ids_budget", "edge_budget", "float_dtype", "unit"),
)
def sssp_batch_from(
    g: FlatGraph,
    aux: EngineAux,
    dist0: jax.Array,  # float[B, n] (+inf = unknown/unreached)
    frontier0: jax.Array,  # bool[B, n] initial relax frontier
    *,
    ids_budget: int,
    edge_budget: int,
    float_dtype=jnp.float32,
    unit: bool = False,
) -> jax.Array:
    """``sssp_batch`` seeded from ARBITRARY initial state instead of
    point sources — the warm-start entry point of the incremental
    BFS/SSSP path (``traversal.algorithms.warm_distances``): the
    previous version's still-valid distances come in as ``dist0``, the
    clean reached set as ``frontier0``, and the same in-trace loop
    relaxes only what the update batch can have changed.  ``unit=True``
    runs the hop metric (incremental BFS) on a weighted pool."""
    TRACES.bump()  # trace-time only: a jit cache hit never runs this body
    return _bellman_ford(
        g,
        aux,
        dist0.astype(float_dtype),
        frontier0,
        ids_budget=ids_budget,
        edge_budget=edge_budget,
        float_dtype=float_dtype,
        unit=unit,
    )


class JaxEngine(TraversalEngine):
    """Engine over an (immutable) ``FlatGraph`` snapshot."""

    def __init__(
        self,
        g: FlatGraph,
        aux: Optional[EngineAux] = None,
        float_dtype=None,
    ):
        self.g = g
        self._n = g.n
        self._m = int(g.m)
        cap = g.edge_capacity
        # explicit compute dtype (float32 default — see the module
        # docstring's precision contract)
        self.ops = JAX_OPS if float_dtype is None else JaxOps(float_dtype)

        # all per-snapshot derived state is one jit call (device-resident;
        # no host loops / argsort) — or passed in, pre-refreshed, by a
        # version-pinned caller (AspenStream's engine cache).
        self.aux = engine_aux(g) if aux is None else aux
        self._src_c = self.aux.src_c
        self._dst_c = self.aux.dst_c
        self._evalid = self.aux.evalid
        self._degrees = self.aux.degrees
        self._dst_sorted = self.aux.dst_sorted
        self._src_by_dst = self.aux.src_by_dst
        self._valid_by_dst = self.aux.valid_by_dst
        self._dst_offsets = self.aux.dst_offsets
        self._w_by_dst = self.aux.w_by_dst  # None on unweighted graphs
        self._wdeg = None  # lazy weighted out-degree cache

        # static sparse budgets: a frontier routed sparse obeys
        # |U| + deg(U) <= m/20 <= cap/20, so cap-derived budgets bound
        # any runtime threshold.  Forced-sparse mode needs full budgets.
        self._auto_ids_budget = min(self._n, _round_up(cap // DENSE_THRESHOLD_DENOM + 1, 64))
        self._auto_edge_budget = min(cap, _round_up(cap // DENSE_THRESHOLD_DENOM + 1, 64))
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
        return self._degrees

    @property
    def weights(self) -> Optional[jax.Array]:
        """The pool-parallel per-edge value array (float32[cap]), or
        None on unweighted graphs."""
        return self.g.weights

    @property
    def weighted_degrees(self) -> jax.Array:
        """Sum of out-edge weights per vertex.  The src-major pool is
        its own CSR segmentation, so this is one scatter-free segmented
        row-cumsum over ``g.offsets`` (cached per engine)."""
        if self.g.weights is None:
            return self._degrees.astype(self.ops.float_dtype)
        if self._wdeg is None:
            msg = jnp.where(
                self._evalid, self.g.weights.astype(self.ops.float_dtype), 0.0
            )
            self._wdeg = _segsum_rows(msg[None, :], self.g.offsets)[0]
        return self._wdeg

    @property
    def resident_nbytes(self) -> int:
        """Device bytes held per snapshot: raw pool + ``EngineAux`` (the
        BYTES bench's baseline numerator)."""
        return cz.pytree_nbytes(self.g) + cz.pytree_nbytes(self.aux)

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
        state, out = _edge_map_step(
            self.g.offsets,
            self.g.keys,
            self._src_c,
            self._dst_c,
            self._evalid,
            self._degrees,
            self.g.m,
            self.g.weights,
            U.dense,
            state,
            F=F,
            C=C,
            mode=mode,
            n=self._n,
            ids_budget=ids_b,
            edge_budget=edge_b,
            ops=self.ops,
        )
        return JaxVertexSubset(out), state

    def edge_map_batch(
        self,
        U_b,  # bool[B, n] frontier batch
        F: Callable,
        C: Callable,
        state_b,  # pytree with (B, ...) leaves
        direction_optimize: bool = True,
        mode: str = "auto",
    ):
        """One edgeMap round for B independent frontier lanes: returns
        ``(out_b, state_b')`` where ``out_b`` is the bool[B, n] next
        frontier batch.  Frontiers and state are raw batched arrays
        (not VertexSubsets): batched callers thread them through
        in-trace loops and sync once at the end."""
        if mode == "auto" and not direction_optimize:
            mode = "sparse"
        ids_b, edge_b = self._budgets(mode)
        state_b, out = _edge_map_step_batch(
            self.g.offsets,
            self.g.keys,
            self._src_c,
            self._dst_c,
            self._evalid,
            self._degrees,
            self.g.m,
            self.g.weights,
            jnp.asarray(U_b, dtype=bool),
            state_b,
            F=F,
            C=C,
            mode=mode,
            n=self._n,
            ids_budget=ids_b,
            edge_budget=edge_b,
            ops=self.ops,
        )
        return out, state_b

    # -- in-trace batched drivers ------------------------------------------
    @staticmethod
    def _quantized_sources(sources) -> Tuple[jax.Array, int]:
        """Pad a source batch to power-of-two length (duplicating the
        first source into the pad lanes, whose rows the caller slices
        off) so a serving path with varying batch sizes shares
        O(log max_B) jit traces instead of recompiling the whole
        while_loop driver per distinct B — the same quantization the
        streaming write path applies to update batches."""
        sources = np.asarray(sources).reshape(-1)
        B = sources.size
        pad = max(1, int(2 ** np.ceil(np.log2(max(B, 1)))))
        padded = np.full(pad, sources[0] if B else 0, dtype=np.int32)
        padded[:B] = sources
        return jnp.asarray(padded), B

    def bfs_batch(self, sources) -> Tuple[jax.Array, jax.Array]:
        """(parents, depths) int32[B, n]; ONE dispatch for the whole
        multi-source traversal (see module-level ``bfs_batch``)."""
        padded, B = self._quantized_sources(sources)
        parents, depths = bfs_batch(
            self.g,
            self.aux,
            padded,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
        )
        return parents[:B], depths[:B]

    def bc_batch(self, sources) -> jax.Array:
        """Dependency scores float[B, n]; ONE dispatch per phase (see
        module-level ``bc_batch``)."""
        padded, B = self._quantized_sources(sources)
        return bc_batch(
            self.g, self.aux, padded, float_dtype=self.ops.float_dtype
        )[:B]

    def sssp_batch(self, sources) -> jax.Array:
        """Shortest-path distances float[B, n] (+inf = unreached); ONE
        dispatch for the whole multi-source Bellman–Ford (see
        module-level ``sssp_batch``)."""
        padded, B = self._quantized_sources(sources)
        return sssp_batch(
            self.g,
            self.aux,
            padded,
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            float_dtype=self.ops.float_dtype,
        )[:B]

    @staticmethod
    def _quantized_state(dist0, frontier0):
        """Row-pad warm-start state to power-of-two B (inf distances,
        empty frontiers: pad lanes are fixpoints the loop never
        touches) — the state analogue of ``_quantized_sources``."""
        dist0 = np.asarray(dist0, np.float64)
        frontier0 = np.asarray(frontier0, bool)
        B, n = dist0.shape
        pad = max(1, int(2 ** np.ceil(np.log2(max(B, 1)))))
        if pad != B:
            dist0 = np.concatenate([dist0, np.full((pad - B, n), np.inf)])
            frontier0 = np.concatenate(
                [frontier0, np.zeros((pad - B, n), bool)]
            )
        return dist0, frontier0, B

    def sssp_batch_from(self, dist0, frontier0, unit: bool = False) -> jax.Array:
        """Warm-start (min, +) relaxation from arbitrary initial state
        (see module-level ``sssp_batch_from``) — the incremental
        BFS/SSSP driver."""
        dist0, frontier0, B = self._quantized_state(dist0, frontier0)
        return sssp_batch_from(
            self.g,
            self.aux,
            jnp.asarray(dist0, self.ops.float_dtype),
            jnp.asarray(frontier0),
            ids_budget=self._auto_ids_budget,
            edge_budget=self._auto_edge_budget,
            float_dtype=self.ops.float_dtype,
            unit=unit,
        )[:B]

    def parents_from_depths(self, depths) -> jax.Array:
        """BFS parents from depth rows via the driver's one-pass
        scatter-max rule (see ``_parents_pass``)."""
        return parents_from_depths(
            self.g, self.aux, jnp.asarray(np.asarray(depths, np.int32))
        )

    def cc_labels(self) -> jax.Array:
        """Whole-graph min-label CC, fully in-trace over the prebuilt
        aux (the unified entry point for the jit fixpoint loop)."""
        return cc_labels(self.g, aux=self.aux)

    # -- dense semiring reduce (Pallas segment-sum) -------------------------
    # Weighted graphs dispatch the WEIGHTED kernel (out[v] = sum w(u,v)
    # * values[u], the per-edge weight multiplied on the MXU inside the
    # one-hot matmul); unweighted graphs compile exactly the pre-v2
    # trace — no value array is read, no weighted kernel is built.
    def edge_map_reduce(self, values: jax.Array) -> jax.Array:
        msg = _reduce_msgs(
            values, self._src_by_dst, self._valid_by_dst, dtype=self.ops.float_dtype
        )
        if self._w_by_dst is None:
            out = kops.segment_sum(self._dst_sorted, msg[:, None], self._n)
        else:
            out = kops.segment_sum_weighted(
                self._dst_sorted, self._w_by_dst, msg[:, None], self._n
            )
        return out[:, 0].astype(values.dtype)

    def edge_map_reduce_batch(self, values: jax.Array) -> jax.Array:
        """(B, n) value rows through ONE Pallas segment-sum call: the
        kernel's message feature dim carries the B query lanes."""
        msg = _reduce_msgs_batch(
            values, self._src_by_dst, self._valid_by_dst, dtype=self.ops.float_dtype
        )
        if self._w_by_dst is None:
            out = kops.segment_sum(self._dst_sorted, msg, self._n)
        else:
            out = kops.segment_sum_weighted(
                self._dst_sorted, self._w_by_dst, msg, self._n
            )
        return out.T.astype(values.dtype)

    # -- vertexMap ----------------------------------------------------------
    def vertex_map(self, U: JaxVertexSubset, P: Callable, state) -> JaxVertexSubset:
        keep = P(self.ops, state, jnp.arange(self._n, dtype=jnp.int32))
        return JaxVertexSubset(U.dense & keep)

    def to_host(self, x) -> np.ndarray:
        HOST_SYNCS.bump()
        return np.asarray(x)


# ---------------------------------------------------------------------------
# whole-graph jit traversals (single compiled step, no host round-trips) —
# the device-side counterparts of algorithms.py, used where the entire
# frontier loop must live inside one trace (launch cells, sharded pool).
# All accept a prebuilt ``EngineAux`` (version-pinned, from the stream's
# mirror cache) so repeated calls stop re-deriving the endpoint clipping.
# ---------------------------------------------------------------------------


def _ensure_flat(g):
    """Trace-time dispatch for chunked operands: the whole-graph loops
    accept a ``CompressedPool`` wherever they accept a ``FlatGraph``; the
    decode happens once inside the same trace (jit re-specializes per
    input pytree structure, so the raw path compiles exactly as before)."""
    return _fg.decompress(g) if isinstance(g, CompressedPool) else g


def _endpoints(g: FlatGraph, aux):
    if isinstance(aux, EngineAux):
        return aux.src_c, aux.dst_c, aux.evalid
    return _pool_endpoints(g)


@jax.jit
def dense_expand(g, frontier: jax.Array, aux: Optional[EngineAux] = None) -> jax.Array:
    """One dense edgeMap expansion: bool[n] frontier -> bool[n] reached.

    Every pool slot looks up whether its source is in the frontier; a
    segment-or over destinations (one gather + one masked scatter).
    ``g`` may be a ``CompressedPool`` (chunked operand): the dst decode
    fuses into this trace."""
    g = _ensure_flat(g)
    src_c, dst_c, evalid = _endpoints(g, aux)
    n = g.offsets.shape[0] - 1
    msg = frontier[src_c] & evalid
    return jnp.zeros(n, dtype=bool).at[dst_c].max(msg, mode="drop")


@jax.jit
def bfs_levels(g, source: jax.Array, aux: Optional[EngineAux] = None) -> jax.Array:
    """Full BFS levels via lax.while_loop (fixed-shape iterations).
    Accepts a ``CompressedPool`` (decode fused into the trace)."""
    g = _ensure_flat(g)
    endpoints = _endpoints(g, aux)
    n = g.offsets.shape[0] - 1
    levels = jnp.full(n, jnp.int32(-1))
    levels = levels.at[source].set(0)
    frontier = jnp.zeros(n, dtype=bool).at[source].set(True)

    def cond(state):
        frontier, levels, d = state
        return frontier.any()

    def body(state):
        frontier, levels, d = state
        src_c, dst_c, evalid = endpoints
        msg = frontier[src_c] & evalid
        nxt = jnp.zeros(n, dtype=bool).at[dst_c].max(msg, mode="drop")
        nxt = nxt & (levels < 0)
        levels = jnp.where(nxt, d + 1, levels)
        return nxt, levels, d + 1

    _, levels, _ = jax.lax.while_loop(cond, body, (frontier, levels, jnp.int32(0)))
    return levels


@jax.jit
def cc_labels(g, aux: Optional[EngineAux] = None) -> jax.Array:
    """Min-label propagation to fixpoint (jit while_loop).
    Accepts a ``CompressedPool`` (decode fused into the trace)."""
    g = _ensure_flat(g)
    src_c, dst_c, evalid = _endpoints(g, aux)
    n = g.offsets.shape[0] - 1
    labels0 = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        labels, changed = state
        return changed

    def body(state):
        labels, _ = state
        msg = jnp.where(evalid, labels[src_c], jnp.int32(np.iinfo(np.int32).max))
        new = labels.at[dst_c].min(msg, mode="drop")
        return new, (new != labels).any()

    labels, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return labels


# ---------------------------------------------------------------------------
# compressed engine: queries served from a chunk-compressed resident pool
# ---------------------------------------------------------------------------


class CompressedAux(NamedTuple):
    """Per-snapshot derived state for ``CompressedEngine`` — the
    compressed counterpart of ``EngineAux``.

    The two O(cap) int lanes of ``EngineAux`` (``dst_sorted``,
    ``src_by_dst``) are themselves chunk-compressed: ``dst_sorted`` is
    ascending (ideal delta profile), ``src_by_dst`` is ascending within
    each dst segment.  The O(n) arrays (degrees, segment bounds) and the
    float value lane stay raw — they are small, respectively not
    delta-friendly.  ``valid_by_dst`` collapses to one scalar: valid
    slots are exactly the sorted prefix ``[:m_valid]``.
    """

    dst_sorted_c: cz.ChunkedStream  # destinations ascending (pad = n)
    srcbd_c: cz.ChunkedStream  # sources permuted dst-major
    dst_offsets: jax.Array  # int32[n+1] segment bounds into dst_sorted
    degrees: jax.Array  # int32[n]
    m_valid: jax.Array  # int32 scalar: count of valid pool slots
    w_by_dst: Optional[jax.Array] = None  # float32[capC] values dst-major


@functools.partial(jax.jit, static_argnames=("aux_hi_cap",))
def engine_aux_compressed(
    cg: CompressedPool, aux_hi_cap: Optional[int] = None
) -> CompressedAux:
    """One jit: decompress -> ``engine_aux`` -> re-compress the big int
    lanes.  The uncompressed aux is a transient of this trace; resident
    state is the compressed pytree.  Lane width / escape capacity are
    inherited from the pool stream (static via dtypes/shapes): an
    adaptive pool gets adaptive aux lanes, with hi capacity inherited
    from the pool's plane unless ``aux_hi_cap`` overrides it (the engine
    retries at full capacity when only the aux lanes overflow — the aux
    permutations need not share the pool's wide-chunk profile)."""
    g = _fg.decompress(cg)
    aux = engine_aux(g)
    k = cg.dst.k
    if cg.dst.hi is not None:
        hi_cap = cg.dst.hi.shape[-2] if aux_hi_cap is None else aux_hi_cap
        dst_sorted_c = cz.encode_stream_adaptive(aux.dst_sorted, hi_cap=hi_cap, k=k)
        srcbd_c = cz.encode_stream_adaptive(aux.src_by_dst, hi_cap=hi_cap, k=k)
    else:
        width = cg.dst.width
        dst_sorted_c = cz.encode_stream(aux.dst_sorted, width=width, k=k)
        srcbd_c = cz.encode_stream(aux.src_by_dst, width=width, k=k)
    w = aux.w_by_dst
    if w is not None and dst_sorted_c.length > w.shape[0]:
        w = jnp.pad(w, (0, dst_sorted_c.length - w.shape[0]))
    return CompressedAux(
        dst_sorted_c=dst_sorted_c,
        srcbd_c=srcbd_c,
        dst_offsets=aux.dst_offsets,
        degrees=aux.degrees,
        m_valid=aux.evalid.sum().astype(jnp.int32),
        w_by_dst=w,
    )


def _inflate(cg: CompressedPool, caux: CompressedAux):
    """Trace-level inflate: (CompressedPool, CompressedAux) ->
    (FlatGraph, EngineAux) inside the caller's jit.  Every compressed
    query step is `inflate + the existing module-level step` in ONE
    trace: decoded arrays are transients XLA fuses into their consumers,
    the resident state stays compressed, and the raw steps' compiled
    logic is reused verbatim rather than forked."""
    g = _fg.decompress(cg)
    cap = g.edge_capacity
    src_c, dst_c, evalid = _pool_endpoints(g)
    dst_sorted = cz.decode_stream(caux.dst_sorted_c, cap)
    src_by_dst = cz.decode_stream(caux.srcbd_c, cap)
    valid_by_dst = jnp.arange(cap) < caux.m_valid
    w_by_dst = None if caux.w_by_dst is None else caux.w_by_dst[:cap]
    aux = EngineAux(
        src_c=src_c,
        dst_c=dst_c,
        evalid=evalid,
        degrees=caux.degrees,
        dst_sorted=dst_sorted,
        src_by_dst=src_by_dst,
        valid_by_dst=valid_by_dst,
        dst_offsets=caux.dst_offsets,
        w_by_dst=w_by_dst,
    )
    return g, aux


@functools.partial(
    jax.jit,
    static_argnames=("F", "C", "mode", "n", "ids_budget", "edge_budget", "ops"),
)
def _edge_map_step_compressed(cg, caux, U, state, *, F, C, mode, n, ids_budget, edge_budget, ops=JAX_OPS):
    g, aux = _inflate(cg, caux)
    return _edge_map_step(
        g.offsets, g.keys, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        g.m, g.weights, U, state,
        F=F, C=C, mode=mode, n=n,
        ids_budget=ids_budget, edge_budget=edge_budget, ops=ops,
    )


@functools.partial(
    jax.jit,
    static_argnames=("F", "C", "mode", "n", "ids_budget", "edge_budget", "ops"),
)
def _edge_map_step_batch_compressed(cg, caux, U_b, state_b, *, F, C, mode, n, ids_budget, edge_budget, ops=JAX_OPS):
    g, aux = _inflate(cg, caux)
    return _edge_map_step_batch(
        g.offsets, g.keys, aux.src_c, aux.dst_c, aux.evalid, aux.degrees,
        g.m, g.weights, U_b, state_b,
        F=F, C=C, mode=mode, n=n,
        ids_budget=ids_budget, edge_budget=edge_budget, ops=ops,
    )


@functools.partial(jax.jit, static_argnames=("ids_budget", "edge_budget"))
def bfs_batch_compressed(cg, caux, sources, *, ids_budget, edge_budget):
    g, aux = _inflate(cg, caux)
    return bfs_batch(g, aux, sources, ids_budget=ids_budget, edge_budget=edge_budget)


@functools.partial(jax.jit, static_argnames=("float_dtype",))
def bc_batch_compressed(cg, caux, sources, *, float_dtype=jnp.float32):
    g, aux = _inflate(cg, caux)
    return bc_batch(g, aux, sources, float_dtype=float_dtype)


@functools.partial(jax.jit, static_argnames=("ids_budget", "edge_budget", "float_dtype"))
def sssp_batch_compressed(cg, caux, sources, *, ids_budget, edge_budget, float_dtype=jnp.float32):
    g, aux = _inflate(cg, caux)
    return sssp_batch(
        g, aux, sources,
        ids_budget=ids_budget, edge_budget=edge_budget, float_dtype=float_dtype,
    )


@functools.partial(
    jax.jit, static_argnames=("ids_budget", "edge_budget", "float_dtype", "unit")
)
def sssp_batch_from_compressed(
    cg, caux, dist0, frontier0, *, ids_budget, edge_budget,
    float_dtype=jnp.float32, unit=False,
):
    g, aux = _inflate(cg, caux)
    return sssp_batch_from(
        g, aux, dist0, frontier0,
        ids_budget=ids_budget, edge_budget=edge_budget,
        float_dtype=float_dtype, unit=unit,
    )


@jax.jit
def parents_from_depths_compressed(cg, caux, depths):
    g, aux = _inflate(cg, caux)
    return _parents_pass(g, aux, depths)


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _edge_map_reduce_compressed(caux: CompressedAux, values_b, *, n, dtype):
    """The (+, x) semiring reduce on fully compressed operands — the one
    path where decode runs INSIDE the Pallas kernel itself: the chunked
    ``dst_sorted`` lane feeds ``segment_sum_*_chunked`` undecoded and the
    kernel's prologue decodes each tile next to the one-hot matmul.  The
    src gather lane still decodes in-trace (a gather needs materialized
    indices), fused by XLA with the message build."""
    src_by_dst = cz.decode_stream(caux.srcbd_c)  # int32[capC]
    valid = jnp.arange(src_by_dst.shape[0]) < caux.m_valid
    msg = jnp.where(valid[None, :], values_b[:, src_by_dst], 0.0).T.astype(dtype)
    s = caux.dst_sorted_c
    if caux.w_by_dst is None:
        return kops.segment_sum_chunked(
            s.anchors, s.deltas, s.ovf_pos, s.ovf_add, msg, n, hi=s.hi, wide=s.wide
        )
    return kops.segment_sum_weighted_chunked(
        s.anchors, s.deltas, s.ovf_pos, s.ovf_add, caux.w_by_dst, msg, n,
        hi=s.hi, wide=s.wide,
    )


@functools.partial(jax.jit, static_argnames=("dtype",))
def _weighted_degrees_compressed(cg: CompressedPool, *, dtype=jnp.float32):
    g = _fg.decompress(cg)
    _, _, evalid = _pool_endpoints(g)
    msg = jnp.where(evalid, g.weights.astype(dtype), 0.0)
    return _segsum_rows(msg[None, :], g.offsets)[0]


class CompressedEngine(JaxEngine):
    """``JaxEngine`` served from a chunk-compressed resident snapshot.

    Holds a ``CompressedPool`` + ``CompressedAux`` instead of the raw
    pool + ``EngineAux`` — the HBM-resident state is the compressed
    layout, and every query dispatches a jit whose prologue inflates (or,
    for ``edge_map_reduce``, a Pallas kernel that decodes in-tile).  The
    method surface, budgets, frontier helpers and batched-driver
    quantization are inherited; only the dispatch targets differ.
    """

    def __init__(
        self,
        cg: CompressedPool,
        aux: Optional[CompressedAux] = None,
        float_dtype=None,
    ):
        self.cg = cg
        self._n = cg.n
        self._m = int(cg.m)
        cap = cg.edge_capacity
        self.ops = JAX_OPS if float_dtype is None else JaxOps(float_dtype)
        self.caux = engine_aux_compressed(cg) if aux is None else aux
        self._degrees = self.caux.degrees
        self._wdeg = None
        # Aux spill check: engine construction already syncs (int(cg.m)
        # above), so reading three flag bytes here is free — and a
        # spilled aux stream would silently mis-decode every query.
        pool_spilled = bool(np.asarray(cg.dst.spill))
        aux_spilled = bool(np.asarray(self.caux.dst_sorted_c.spill)) or bool(
            np.asarray(self.caux.srcbd_c.spill)
        )
        if not pool_spilled and aux_spilled and aux is None and cg.dst.hi is not None:
            # Adaptive aux lanes inherited the pool's (exact-fit) hi
            # capacity but need more wide chunks than the pool did —
            # retry once at full capacity before declaring a genuine
            # escape-lane spill.
            R = cg.dst.deltas.shape[-2]
            self.caux = engine_aux_compressed(cg, aux_hi_cap=R)
            self._degrees = self.caux.degrees
            aux_spilled = bool(np.asarray(self.caux.dst_sorted_c.spill)) or bool(
                np.asarray(self.caux.srcbd_c.spill)
            )
        if pool_spilled or aux_spilled:
            raise ValueError(
                "compressed stream spilled its escape lane; rebuild the "
                "snapshot with a wider delta lane or keep the raw engine"
            )
        self._auto_ids_budget = min(self._n, _round_up(cap // DENSE_THRESHOLD_DENOM + 1, 64))
        self._auto_edge_budget = min(cap, _round_up(cap // DENSE_THRESHOLD_DENOM + 1, 64))
        self._full_ids_budget = self._n
        self._full_edge_budget = max(cap, 1)

    @property
    def weights(self) -> Optional[jax.Array]:
        return self.cg.weights

    @property
    def weighted_degrees(self) -> jax.Array:
        if self.cg.weights is None:
            return self._degrees.astype(self.ops.float_dtype)
        if self._wdeg is None:
            self._wdeg = _weighted_degrees_compressed(
                self.cg, dtype=self.ops.float_dtype
            )
        return self._wdeg

    @property
    def resident_nbytes(self) -> int:
        """Device bytes held per snapshot: compressed pool + compressed
        aux (the BYTES bench's numerator for this engine)."""
        return cz.pytree_nbytes(self.cg) + cz.pytree_nbytes(self.caux)

    def edge_map(self, U, F, C, state, direction_optimize=True, mode="auto"):
        if mode == "auto" and not direction_optimize:
            mode = "sparse"
        ids_b, edge_b = self._budgets(mode)
        state, out = _edge_map_step_compressed(
            self.cg, self.caux, U.dense, state,
            F=F, C=C, mode=mode, n=self._n,
            ids_budget=ids_b, edge_budget=edge_b, ops=self.ops,
        )
        return JaxVertexSubset(out), state

    def edge_map_batch(self, U_b, F, C, state_b, direction_optimize=True, mode="auto"):
        if mode == "auto" and not direction_optimize:
            mode = "sparse"
        ids_b, edge_b = self._budgets(mode)
        state_b, out = _edge_map_step_batch_compressed(
            self.cg, self.caux, jnp.asarray(U_b, dtype=bool), state_b,
            F=F, C=C, mode=mode, n=self._n,
            ids_budget=ids_b, edge_budget=edge_b, ops=self.ops,
        )
        return out, state_b

    def bfs_batch(self, sources):
        padded, B = self._quantized_sources(sources)
        parents, depths = bfs_batch_compressed(
            self.cg, self.caux, padded,
            ids_budget=self._auto_ids_budget, edge_budget=self._auto_edge_budget,
        )
        return parents[:B], depths[:B]

    def bc_batch(self, sources):
        padded, B = self._quantized_sources(sources)
        return bc_batch_compressed(
            self.cg, self.caux, padded, float_dtype=self.ops.float_dtype
        )[:B]

    def sssp_batch(self, sources):
        padded, B = self._quantized_sources(sources)
        return sssp_batch_compressed(
            self.cg, self.caux, padded,
            ids_budget=self._auto_ids_budget, edge_budget=self._auto_edge_budget,
            float_dtype=self.ops.float_dtype,
        )[:B]

    def sssp_batch_from(self, dist0, frontier0, unit: bool = False):
        dist0, frontier0, B = self._quantized_state(dist0, frontier0)
        return sssp_batch_from_compressed(
            self.cg, self.caux,
            jnp.asarray(dist0, self.ops.float_dtype), jnp.asarray(frontier0),
            ids_budget=self._auto_ids_budget, edge_budget=self._auto_edge_budget,
            float_dtype=self.ops.float_dtype, unit=unit,
        )[:B]

    def parents_from_depths(self, depths):
        return parents_from_depths_compressed(
            self.cg, self.caux, jnp.asarray(np.asarray(depths, np.int32))
        )

    def cc_labels(self) -> jax.Array:
        return cc_labels(self.cg)

    def edge_map_reduce(self, values: jax.Array) -> jax.Array:
        out = _edge_map_reduce_compressed(
            self.caux, values[None, :], n=self._n, dtype=self.ops.float_dtype
        )
        return out[:, 0].astype(values.dtype)

    def edge_map_reduce_batch(self, values: jax.Array) -> jax.Array:
        out = _edge_map_reduce_compressed(
            self.caux, values, n=self._n, dtype=self.ops.float_dtype
        )
        return out.T.astype(values.dtype)
