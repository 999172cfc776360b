"""Pallas TPU kernel: sorted segment-sum as a one-hot MXU matmul.

GNN aggregation / Aspen edgeMap reduce over CSR-sorted edges:
``out[d] = sum_{e: dst[e]=d} msg[e]``.  Random scatter is hostile to the
TPU; but with edges sorted by destination (which the C-tree pool
guarantees — the pool IS sorted by (dst-major) key), the scatter becomes
a *block-banded* matmul: for an edge block E and a destination-row block
R, ``out[R] += M @ msg[E]`` where ``M[r, e] = 1[dst[e] == r]`` is built
in-register from an iota comparison.  The MXU multiplies the one-hot
matrix at full throughput — this is the TPU-native scatter.

Band schedule: because dst is sorted, edge block ``j`` only meets the
dst blocks between its first and last destination.  ``band_schedule``
lists exactly the intersecting (dst block, edge block) pairs, dst-block
major, padded to the static bound ``n_dst_blocks + n_edge_blocks``; the
kernels run a 1-D grid over that list with the pair indices in scalar
prefetch.  Work is O(n_dst_blocks + n_edge_blocks) grid steps, not their
product (2^13 x 2^13 steps at 2^20 vertices and 2^22 edges otherwise).
Each dst block is visited by consecutive steps, so its output tile stays
resident and is written back once.

Every integer constant that reaches Mosaic is int32: the package runs
with ``jax_enable_x64`` on (pool keys), under which a bare Python ``0``
in an index map lowers to i64, which Mosaic cannot return.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_decode import escape_steps, lane_cumsum, widen

EDGE_BLOCK = 512
DST_BLOCK = 128
CHUNK_EDGE_BLOCK = 4096  # 32 chunk rows: the int8 sublane tile of the lanes

_I0 = np.int32(0)
_FIRST, _VALID = np.int32(1), np.int32(2)  # schedule flag bits


def band_schedule(starts, ends, n_dst_blocks: int, dst_block: int):
    """(dst block, edge block) pairs that can hold a contribution.

    ``starts[j]``/``ends[j]`` bound the (sorted) destinations of edge
    block ``j``, inclusive.  Returns int32 ``(blk_i, blk_j, flags)`` of
    static length ``n_dst_blocks + n_edge_blocks``: the pairs in dst-block
    major order, every dst block at least once (an empty block meets one
    edge block and accumulates zeros), then padding steps that repeat the
    last pair with the VALID bit clear.  FIRST marks a dst block's first
    step (its tile is zeroed there)."""
    nb_e = starts.shape[0]
    T = n_dst_blocks + nb_e
    lo_dst = jnp.arange(n_dst_blocks, dtype=jnp.int32) * dst_block
    lo = jnp.searchsorted(ends, lo_dst, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(starts, lo_dst + (dst_block - 1), side="right") - 1
    lo = jnp.minimum(lo, nb_e - 1)
    count = jnp.maximum(hi.astype(jnp.int32) - lo + 1, 1)
    first = jnp.cumsum(count, dtype=jnp.int32) - count
    total = first[-1] + count[-1]
    w = jnp.arange(T, dtype=jnp.int32)
    bi = (jnp.searchsorted(first, w, side="right") - 1).astype(jnp.int32)
    off = w - first[bi]
    bj = lo[bi] + jnp.minimum(off, count[bi] - 1)
    valid = w < total
    flags = jnp.where(valid, _VALID, 0) + jnp.where(valid & (off == 0), _FIRST, 0)
    return bi, bj.astype(jnp.int32), flags.astype(jnp.int32)


def _onehot_dot(dst, w, msg, d0, dst_block: int):
    """(dst_block, D) partial sums of ``msg`` rows onto dst rows d0.. .

    ``dst`` is a (1, L) int32 lane, ``w`` a (1, L) weight lane or None,
    ``msg`` (L, D).  The (weighted) one-hot selection rides the MXU."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (dst_block, dst.shape[1]), 0)
    hit = dst - d0 == rows
    sel = jnp.where(hit, w, 0.0) if w is not None else hit
    return jax.lax.dot(
        sel.astype(msg.dtype), msg, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _decode_tile(anch, lane, pos, add, hi=None, wide=None):
    """Decode (rows, CHUNK) chunk tiles to absolute int32 destinations:
    width select on adaptive streams, then anchor + lane prefix sum +
    escape-step corrections.  Escape positions are per-chunk columns, and
    every chunk row sits whole inside the tile, so the corrections use the
    local column iota."""
    d = widen(lane, hi, wide) if hi is not None else lane.astype(jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    return anch + lane_cumsum(d) + escape_steps(cols, pos, add)


def _make_kernel(chunked: bool, adaptive: bool, weighted: bool):
    """Band-scheduled segment-sum kernel over one (dst block, edge block)
    pair per grid step.  Operand order: the raw dst lane, or the chunk
    tiles (anchors, lane, [hi, wide], pos, add); then [w]; then msg."""

    def kernel(bi_ref, bj_ref, fl_ref, *refs):
        *ins, msg_ref, out_ref = refs
        w_ref = ins.pop() if weighted else None
        step = pl.program_id(0)
        flag = fl_ref[step]
        db = out_ref.shape[0]

        @pl.when((flag & _FIRST) != 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when((flag & _VALID) != 0)
        def _accumulate():
            d0 = bi_ref[step] * np.int32(db)
            if not chunked:
                w = w_ref[...] if weighted else None
                out_ref[...] += _onehot_dot(ins[0][...], w, msg_ref[...], d0, db)
                return
            if adaptive:
                anch, lane, hi, wide, pos, add = (r[...] for r in ins)
                dec = _decode_tile(anch, lane, pos, add, hi, wide)
            else:
                anch, lane, pos, add = (r[...] for r in ins)
                dec = _decode_tile(anch, lane, pos, add)
            C = dec.shape[1]
            acc = jnp.zeros(out_ref.shape, jnp.float32)
            for r in range(dec.shape[0]):  # static chunk rows of the block
                w = w_ref[:, r * C : (r + 1) * C] if weighted else None
                acc += _onehot_dot(
                    dec[r : r + 1, :], w, msg_ref[r * C : (r + 1) * C, :], d0, db
                )
            out_ref[...] += acc

    return kernel


def _banded_call(kernel, operands, specs, msg, sched, n_out, edge_block, dst_block,
                 interpret):
    """Run ``kernel`` over the band schedule ``sched`` (scalar prefetch);
    ``operands``/``specs`` precede ``msg``, whose (edge_block, D) blocks
    follow the schedule's edge block like every other edge operand."""
    D = msg.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(sched[0].shape[0],),
        in_specs=specs + [
            pl.BlockSpec((edge_block, D), lambda w, bi, bj, fl: (bj[w], _I0))
        ],
        out_specs=pl.BlockSpec((dst_block, D), lambda w, bi, bj, fl: (bi[w], _I0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, D), jnp.float32),
        interpret=interpret,
    )(*sched, *operands, msg).astype(msg.dtype)


def _lane_spec(edge_block: int):
    """(1, edge_block) block of a per-edge lane (raw dst, weights)."""
    return pl.BlockSpec((1, edge_block), lambda w, bi, bj, fl: (_I0, bj[w]))


def _raw_schedule(dst, n_out, edge_block, dst_block):
    d = dst.reshape(-1, edge_block)
    return band_schedule(d[:, 0], d[:, -1], n_out // dst_block, dst_block)


@functools.partial(
    jax.jit, static_argnames=("n_out", "edge_block", "dst_block", "interpret")
)
def segment_sum_sorted(
    dst: jax.Array,  # int32 (E,) sorted ascending; pad with n_out (OOB)
    msg: jax.Array,  # (E, D) messages
    n_out: int,
    edge_block: int = EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """out[d, :] = sum of msg rows with dst == d.  E, D, n_out must be
    multiples of the block sizes (ops.py pads)."""
    E, D = msg.shape
    assert E % edge_block == 0 and n_out % dst_block == 0
    dst = dst.astype(jnp.int32)
    return _banded_call(
        _make_kernel(False, False, False),
        [dst.reshape(1, -1)], [_lane_spec(edge_block)], msg,
        _raw_schedule(dst, n_out, edge_block, dst_block),
        n_out, edge_block, dst_block, interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("n_out", "edge_block", "dst_block", "interpret")
)
def segment_sum_weighted_sorted(
    dst: jax.Array,  # int32 (E,) sorted ascending; pad with n_out (OOB)
    w: jax.Array,  # float (E,) per-edge weights; pad 0
    msg: jax.Array,  # (E, D) messages
    n_out: int,
    edge_block: int = EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """out[d, :] = sum of w[e] * msg[e, :] over edges with dst == d.
    The weight folds into the one-hot selection matrix
    (``M[r, e] = w[e] * 1[dst[e] == r]``), so it rides the same matmul.
    Same layout contract as ``segment_sum_sorted`` (ops.py pads)."""
    E, D = msg.shape
    assert E % edge_block == 0 and n_out % dst_block == 0
    dst = dst.astype(jnp.int32)
    return _banded_call(
        _make_kernel(False, False, True),
        [dst.reshape(1, -1), w.reshape(1, -1).astype(msg.dtype)],
        [_lane_spec(edge_block), _lane_spec(edge_block)], msg,
        _raw_schedule(dst, n_out, edge_block, dst_block),
        n_out, edge_block, dst_block, interpret,
    )


# ---------------------------------------------------------------------------
# chunk-compressed operands: delta decode fused as an in-kernel prologue
# ---------------------------------------------------------------------------
#
# The compressed pool (core/compressed.py) stores the dst-sorted edge ids
# as (anchor, narrow fixed-width deltas, escape lane) chunks of CHUNK=128
# slots.  CHUNK divides the edge block, so one edge block is exactly
# edge_block // CHUNK whole chunk rows and the decode never needs a
# cross-block carry: each chunk row decodes self-contained (anchor + lane
# prefix sum + escape-step corrections) and feeds its own 128-lane slice
# of the one-hot MXU matmul.  Compressed dst ids therefore never
# round-trip through HBM decoded — the decode lives in the same kernel as
# the reduce.  The chunk-row block must meet the sublane tile of its
# narrowest operand (int8: 32 rows), hence ``CHUNK_EDGE_BLOCK``.
#
# Adaptive streams store ONE int8 lane plus a compacted hi-byte plane of
# the wide chunks' rows (DESIGN.md §12).  The compaction index
# (cumsum(wide) - 1) is a data-dependent gather that block specs cannot
# express, so the ops.py wrapper pre-gathers the hi plane to a per-chunk
# transient ``hi_g`` in-trace and the kernel gets aligned blocks of it
# plus (rows, 1) width tags.


def _chunk_specs(rpb: int, C: int, K: int, adaptive: bool):
    def rows(width):
        return pl.BlockSpec((rpb, width), lambda w, bi, bj, fl: (bj[w], _I0))

    if adaptive:  # anchors, lane, hi_g, wide, pos, add
        return [rows(1), rows(C), rows(C), rows(1), rows(K), rows(K)]
    return [rows(1), rows(C), rows(K), rows(K)]


def _chunked(anchors, deltas, hi_g, wide, ovf_pos, ovf_add, w, msg, n_out,
             edge_block, dst_block, interpret):
    R, C = deltas.shape
    E, D = msg.shape
    K = ovf_pos.shape[1]
    assert E == R * C
    assert edge_block % C == 0 and E % edge_block == 0
    assert n_out % dst_block == 0
    rpb = edge_block // C
    anchors = anchors.astype(jnp.int32)
    starts = anchors[::rpb]
    # sorted stream: block j ends no later than block j + 1 starts
    ends = jnp.concatenate([starts[1:], jnp.full((1,), n_out - 1, jnp.int32)])
    sched = band_schedule(starts, ends, n_out // dst_block, dst_block)
    adaptive = hi_g is not None
    ops_ = [anchors.reshape(-1, 1), deltas]
    if adaptive:
        ops_ += [hi_g, wide.reshape(-1, 1).astype(jnp.int32)]
    ops_ += [ovf_pos.astype(jnp.int32), ovf_add.astype(jnp.int32)]
    specs = _chunk_specs(rpb, C, K, adaptive)
    if w is not None:
        ops_.append(w.reshape(1, -1).astype(msg.dtype))
        specs.append(_lane_spec(edge_block))
    return _banded_call(
        _make_kernel(True, adaptive, w is not None), ops_, specs, msg, sched,
        n_out, edge_block, dst_block, interpret,
    )


_CHUNKED_STATIC = ("n_out", "edge_block", "dst_block", "interpret")


@functools.partial(jax.jit, static_argnames=_CHUNKED_STATIC)
def segment_sum_sorted_chunked(
    anchors: jax.Array,  # int32 (R,) chunk anchors of the sorted dst lane
    deltas: jax.Array,  # int8|int16 (R, CHUNK); col 0 == 0
    ovf_pos: jax.Array,  # int32 (R, K) escape columns (CHUNK = unused)
    ovf_add: jax.Array,  # int32 (R, K) escaped deltas
    msg: jax.Array,  # (R * CHUNK, D) messages, edge order
    n_out: int,
    edge_block: int = CHUNK_EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """``segment_sum_sorted`` with the dst operand chunk-compressed; the
    delta decode runs as a prologue inside the same kernel.  R * CHUNK
    must be a multiple of edge_block and CHUNK must divide edge_block
    (kernels/ops.py pads; padding chunks decode to OOB dst)."""
    return _chunked(anchors, deltas, None, None, ovf_pos, ovf_add, None, msg,
                    n_out, edge_block, dst_block, interpret)


@functools.partial(jax.jit, static_argnames=_CHUNKED_STATIC)
def segment_sum_weighted_chunked(
    anchors: jax.Array,
    deltas: jax.Array,
    ovf_pos: jax.Array,
    ovf_add: jax.Array,
    w: jax.Array,  # float (R * CHUNK,) per-edge weights; pad 0
    msg: jax.Array,
    n_out: int,
    edge_block: int = CHUNK_EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Weighted variant of ``segment_sum_sorted_chunked`` (same fused
    in-kernel decode; weights fold into the one-hot as in the raw path)."""
    return _chunked(anchors, deltas, None, None, ovf_pos, ovf_add, w, msg,
                    n_out, edge_block, dst_block, interpret)


@functools.partial(jax.jit, static_argnames=_CHUNKED_STATIC)
def segment_sum_sorted_chunked_adaptive(
    anchors: jax.Array,  # int32 (R,)
    deltas: jax.Array,  # int8 (R, CHUNK) lane (low bytes on wide chunks)
    hi_g: jax.Array,  # int8 (R, CHUNK) pre-gathered hi plane (0 on narrow)
    wide: jax.Array,  # int32 (R, 1) per-chunk width tag
    ovf_pos: jax.Array,  # int32 (R, K)
    ovf_add: jax.Array,  # int32 (R, K)
    msg: jax.Array,  # (R * CHUNK, D)
    n_out: int,
    edge_block: int = CHUNK_EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """``segment_sum_sorted_chunked`` over the adaptive-width layout; the
    per-chunk width select + delta decode fuse into the reduce kernel."""
    return _chunked(anchors, deltas, hi_g, wide, ovf_pos, ovf_add, None, msg,
                    n_out, edge_block, dst_block, interpret)


@functools.partial(jax.jit, static_argnames=_CHUNKED_STATIC)
def segment_sum_weighted_chunked_adaptive(
    anchors: jax.Array,
    deltas: jax.Array,
    hi_g: jax.Array,
    wide: jax.Array,
    ovf_pos: jax.Array,
    ovf_add: jax.Array,
    w: jax.Array,  # float (R * CHUNK,); pad 0
    msg: jax.Array,
    n_out: int,
    edge_block: int = CHUNK_EDGE_BLOCK,
    dst_block: int = DST_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Weighted adaptive chunked segment-sum (weights fold into the
    one-hot as in every other variant)."""
    return _chunked(anchors, deltas, hi_g, wide, ovf_pos, ovf_add, w, msg,
                    n_out, edge_block, dst_block, interpret)


# ---------------------------------------------------------------------------
# fixed-fanout aggregation (sampled GNN regime: GraphSAGE minibatch)
# ---------------------------------------------------------------------------


def _fanout_kernel(feats_ref, mask_ref, out_ref, *, op):
    """(B_blk, K, D) neighbor features -> (B_blk, D) masked reduce."""
    f = feats_ref[...]
    m = mask_ref[...].astype(f.dtype)  # (B, K, 1)
    if op == "mean":
        s = jnp.sum(f * m, axis=1)
        cnt = jnp.maximum(jnp.sum(m, axis=1), 1.0)
        out_ref[...] = s / cnt
    elif op == "sum":
        out_ref[...] = jnp.sum(f * m, axis=1)
    else:  # max
        neg = jnp.finfo(f.dtype).min
        out_ref[...] = jnp.max(jnp.where(m > 0, f, neg), axis=1)


@functools.partial(jax.jit, static_argnames=("op", "batch_block", "interpret"))
def fanout_aggregate(
    feats: jax.Array,  # (B, K, D) gathered neighbor features
    mask: jax.Array,  # (B, K) validity (sampled < degree)
    op: str = "mean",
    batch_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    B, K, D = feats.shape
    assert B % batch_block == 0
    grid = (B // batch_block,)
    return pl.pallas_call(
        functools.partial(_fanout_kernel, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch_block, K, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((batch_block, K, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((batch_block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), feats.dtype),
        interpret=interpret,
    )(feats, mask[..., None])
