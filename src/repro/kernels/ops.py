"""jit'd public wrappers around the Pallas kernels.

Handles: interpret-mode selection (CPU -> interpret=True; TPU ->
compiled; any other backend is an error, never a silent interpreter),
padding to block multiples, and the ragged->padded layout conversions
the kernels require.  Models and the Aspen flat level
call these, never pl.pallas_call directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune, csr_spmm, delta_decode, flash_decode, segment_reduce


def _interpret() -> bool:
    """Pallas interpret mode on the CPU backend, compiled kernels on TPU.

    Any other backend raises: running the interpreter there would hide
    the device behind an emulator."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


def _traced(x) -> bool:
    """True while dispatch runs under a trace (autotune must not sweep)."""
    return isinstance(x, jax.core.Tracer)


def _gather_hi(deltas: jax.Array, hi: jax.Array | None, wide: jax.Array | None):
    """Resolve the compacted hi-byte plane to a per-chunk-aligned plane.

    Adaptive streams store hi bytes only for wide chunks (compacted to
    ``hi[cumsum(wide) - 1]``); Pallas block specs cannot express that
    data-dependent gather, so the wrapper materialises the aligned
    ``(R, C)`` plane as an XLA temporary before the kernel launch — the
    resident operand stays the compacted plane.  Narrow rows gather
    zeros, so the kernel's width select is safe without masking."""
    if hi is None:
        return jnp.zeros_like(deltas, dtype=jnp.int8)
    H = hi.shape[-2]
    if H == 0:
        return jnp.zeros_like(deltas, dtype=jnp.int8)
    idx = jnp.clip(jnp.cumsum(wide, dtype=jnp.int32) - 1, 0, H - 1)
    return jnp.where(wide[:, None], hi[idx], jnp.int8(0))


def _pad_to(x: np.ndarray | jax.Array, mult: int, axis: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# delta decode (C-tree chunk decompression)
# ---------------------------------------------------------------------------


def decode_chunks(anchors: jax.Array, deltas: jax.Array) -> jax.Array:
    """Decode padded chunk deltas -> absolute values.

    anchors: (n_chunks,) int32; deltas: (n_chunks, max_len) int32.  The
    kernel's chunk layout defines column 0 as the anchor position, i.e.
    ``deltas[:, 0] == 0`` so that ``out[:, 0] == anchors``.  Rather than
    silently assuming it, this boundary NORMALIZES column 0 to zero:
    whatever a caller left in that slot (e.g. a scatter artifact from a
    ragged->padded conversion) is dropped, and the decode of well-formed
    inputs is unchanged.  Pads both axes to kernel tiles.
    """
    n, L = deltas.shape
    deltas = deltas.at[:, 0].set(0)  # enforce the anchor-column invariant
    a = _pad_to(anchors, delta_decode.DEFAULT_ROW_BLOCK, 0)
    d = _pad_to(
        _pad_to(deltas, delta_decode.DEFAULT_ROW_BLOCK, 0),
        delta_decode.DEFAULT_COL_BLOCK,
        1,
    )
    out = delta_decode.delta_decode_padded(a, d, interpret=_interpret())
    return out[:n, :L]


def decode_chunked_stream(
    anchors: jax.Array,
    deltas: jax.Array,
    ovf_pos: jax.Array,
    ovf_add: jax.Array,
    hi: jax.Array | None = None,
    wide: jax.Array | None = None,
) -> jax.Array:
    """Decode escape-lane chunks (core/compressed.ChunkedStream arrays)
    via the Pallas kernel; pads chunk rows to the dtype-aware row block.

    Kernels take the raw arrays, not the ChunkedStream NamedTuple, so
    this package never imports from ``repro.core`` (no cycle); engine
    callers unpack the stream.  Pass ``hi``/``wide`` for adaptive-width
    streams; the compacted hi plane is pre-gathered in-trace
    (``_gather_hi``) and the width select runs inside the kernel.  Row
    padding uses anchor 0 / empty escape slots (pos = chunk_len), which
    decode to benign zeros and are sliced off."""
    n, L = deltas.shape
    rb = delta_decode._row_block_for(deltas.dtype)
    a = _pad_to(anchors, rb, 0)
    d = _pad_to(deltas, rb, 0)
    p = _pad_to(ovf_pos, rb, 0, value=L)
    v = _pad_to(ovf_add, rb, 0)
    if hi is not None:
        hg = _pad_to(_gather_hi(deltas, hi, wide), rb, 0)
        wp = _pad_to(wide.astype(jnp.int32), rb, 0)
        out = delta_decode.delta_decode_chunked_adaptive(
            a, d, hg, wp, p, v, interpret=_interpret()
        )
    else:
        out = delta_decode.delta_decode_chunked(a, d, p, v, interpret=_interpret())
    return out[:n]


def decode_pool(packed, total_len: int | None = None) -> np.ndarray:
    """Decode a chunks.PackedDeltas pool via the kernel (host convenience).

    Converts the ragged chunk layout to padded rows, runs the kernel,
    scatters rows back into the flat pool order.
    """
    from repro.core.chunks import PackedDeltas  # local import, avoids cycle

    assert isinstance(packed, PackedDeltas)
    offs = np.asarray(packed.chunk_off)
    lens = np.diff(offs)
    n_chunks = lens.size
    if n_chunks == 0:
        return np.empty(0, dtype=np.int64)
    L = int(lens.max())
    esc = np.iinfo(np.dtype(packed.dtype)).max
    d = np.asarray(packed.deltas, dtype=np.int64)
    d_full = d.copy()
    d_full[d == esc] = packed.overflow
    rows = np.zeros((n_chunks, L), dtype=np.int32)
    idx = np.arange(offs[-1])
    chunk_of = np.repeat(np.arange(n_chunks), lens)
    col_of = idx - offs[chunk_of]
    rows[chunk_of, col_of] = d_full
    rows[:, 0] = 0
    out = np.asarray(decode_chunks(jnp.asarray(packed.anchors, jnp.int32), jnp.asarray(rows)))
    flat = out[chunk_of, col_of].astype(np.int64)
    return flat


# ---------------------------------------------------------------------------
# segment reduce
# ---------------------------------------------------------------------------


def _sweep_segment_sum(E: int, n_out: int, weighted: bool):
    """sweep_fn factory: synthetic sorted segment-sum of the real shape.

    The thunk passes explicit block params, so candidate timings bypass
    the autotune consult (no recursion) and each candidate compiles its
    own specialization."""
    kernel = "segment_sum_weighted" if weighted else "segment_sum"

    def make(params):
        dst = jnp.sort(
            jax.random.randint(
                jax.random.PRNGKey(0), (max(E, 1),), 0, max(n_out, 1), dtype=jnp.int32
            )
        )
        msg = jnp.ones((max(E, 1), 8), jnp.float32)
        w = jnp.ones((max(E, 1),), jnp.float32)

        def thunk():
            if weighted:
                return segment_sum_weighted(dst, w, msg, n_out, **params)
            return segment_sum(dst, msg, n_out, **params)

        return thunk

    return kernel, make


def segment_sum(
    dst: jax.Array,
    msg: jax.Array,
    n_out: int,
    edge_block: int | None = None,
    dst_block: int | None = None,
) -> jax.Array:
    """Sorted segment-sum; pads edges with OOB dst and n_out to tile.

    Block shapes default to the autotuned winner for this (backend,
    shape-bucket) — consult happens at Python trace time since blocks
    are static kernel arguments."""
    E = dst.shape[0]
    if edge_block is None or dst_block is None:
        kernel, make = _sweep_segment_sum(E, n_out, weighted=False)
        tuned = autotune.get_params(
            "segment_sum", {"E": E, "n": n_out}, sweep_fn=make, traced=_traced(msg)
        )
        edge_block = edge_block or tuned["edge_block"]
        dst_block = dst_block or tuned["dst_block"]
    n_pad = n_out + (-n_out) % dst_block
    d = _pad_to(dst, edge_block, 0, value=n_pad)
    m = _pad_to(msg, edge_block, 0)
    # one extra dst block swallows padding edges
    n_with_pad = n_pad + dst_block
    out = segment_reduce.segment_sum_sorted(
        d, m, n_with_pad, edge_block=edge_block, dst_block=dst_block,
        interpret=_interpret(),
    )
    return out[:n_out]


def segment_sum_weighted(
    dst: jax.Array,
    w: jax.Array,
    msg: jax.Array,
    n_out: int,
    edge_block: int | None = None,
    dst_block: int | None = None,
) -> jax.Array:
    """Weighted sorted segment-sum (out[d] = sum w[e] * msg[e]); same
    padding contract as ``segment_sum`` (weight pads are 0, so padding
    edges contribute nothing even before the OOB dst drop)."""
    E = dst.shape[0]
    if edge_block is None or dst_block is None:
        _, make = _sweep_segment_sum(E, n_out, weighted=True)
        tuned = autotune.get_params(
            "segment_sum_weighted", {"E": E, "n": n_out}, sweep_fn=make,
            traced=_traced(msg),
        )
        edge_block = edge_block or tuned["edge_block"]
        dst_block = dst_block or tuned["dst_block"]
    n_pad = n_out + (-n_out) % dst_block
    d = _pad_to(dst, edge_block, 0, value=n_pad)
    wp = _pad_to(w, edge_block, 0)
    m = _pad_to(msg, edge_block, 0)
    n_with_pad = n_pad + dst_block
    out = segment_reduce.segment_sum_weighted_sorted(
        d, wp, m, n_with_pad, edge_block=edge_block, dst_block=dst_block,
        interpret=_interpret(),
    )
    return out[:n_out]


def _pad_chunked_dst(
    anchors, deltas, ovf_pos, ovf_add, msg, w, n_out,
    hi=None, wide=None, edge_block=None, dst_block=None,
):
    """Shared padding for the chunked segment sums.

    Pads chunk rows to whole edge blocks; padding chunks carry anchor
    ``n_pad`` with zero deltas and empty escape slots, so every padded
    slot decodes to the same OOB dst that the raw path pads with — the
    extra DST_BLOCK swallows them identically.  Adaptive streams
    additionally carry the pre-gathered hi plane and the wide tag; pad
    rows are narrow (wide=0, hi=0), decoding identically to fixed pads."""
    edge_block = edge_block or segment_reduce.CHUNK_EDGE_BLOCK
    dst_block = dst_block or segment_reduce.DST_BLOCK
    R, C = deltas.shape
    rpb = edge_block // C
    n_pad = n_out + (-n_out) % dst_block
    a = _pad_to(anchors, rpb, 0, value=n_pad)
    d = _pad_to(deltas, rpb, 0)
    p = _pad_to(ovf_pos, rpb, 0, value=C)
    v = _pad_to(ovf_add, rpb, 0)
    m = _pad_to(msg, edge_block, 0)
    wp = None if w is None else _pad_to(w, edge_block, 0)
    if hi is not None:
        hg = _pad_to(_gather_hi(deltas, hi, wide), rpb, 0)
        wd = _pad_to(wide.astype(jnp.int32), rpb, 0)
    else:
        hg = wd = None
    assert m.shape[0] == a.shape[0] * C, "msg rows must cover the padded stream"
    n_with_pad = n_pad + dst_block
    return a, d, p, v, m, wp, hg, wd, n_with_pad


def _sweep_segment_sum_chunked(R: int, C: int, n_out: int, weighted: bool, adaptive: bool):
    """sweep_fn factory for the chunked reduces (synthetic stream of the
    real chunk geometry; explicit block params bypass the consult)."""

    def make(params):
        anch = jnp.arange(max(R, 1), dtype=jnp.int32) % max(n_out, 1)
        lane = jnp.zeros((max(R, 1), C), jnp.int8)
        pos = jnp.full((max(R, 1), 8), C, jnp.int32)
        add = jnp.zeros((max(R, 1), 8), jnp.int32)
        msg = jnp.ones((max(R, 1) * C, 8), jnp.float32)
        w = jnp.ones((max(R, 1) * C,), jnp.float32)
        hi = jnp.zeros((1, C), jnp.int8) if adaptive else None
        wd = jnp.zeros((max(R, 1),), bool) if adaptive else None

        def thunk():
            if weighted:
                return segment_sum_weighted_chunked(
                    anch, lane, pos, add, w, msg, n_out, hi=hi, wide=wd, **params
                )
            return segment_sum_chunked(
                anch, lane, pos, add, msg, n_out, hi=hi, wide=wd, **params
            )

        return thunk

    return make


def segment_sum_chunked(
    anchors: jax.Array,
    deltas: jax.Array,
    ovf_pos: jax.Array,
    ovf_add: jax.Array,
    msg: jax.Array,
    n_out: int,
    hi: jax.Array | None = None,
    wide: jax.Array | None = None,
    edge_block: int | None = None,
    dst_block: int | None = None,
) -> jax.Array:
    """``segment_sum`` with a chunk-compressed dst operand; the delta
    decode fuses into the reduce kernel.  msg row ``r*CHUNK + c`` pairs
    with chunk ``r`` column ``c``; msg rows past the valid prefix must be
    zero (the compressed aux masks them).  Pass ``hi``/``wide`` for
    adaptive-width streams (branch-free width select inside the grid)."""
    R, C = deltas.shape
    if edge_block is None or dst_block is None:
        make = _sweep_segment_sum_chunked(R, C, n_out, False, hi is not None)
        tuned = autotune.get_params(
            "segment_sum_chunked", {"R": R, "n": n_out}, sweep_fn=make,
            traced=_traced(msg),
        )
        edge_block = edge_block or tuned["edge_block"]
        dst_block = dst_block or tuned["dst_block"]
    a, d, p, v, m, _, hg, wd, n_with_pad = _pad_chunked_dst(
        anchors, deltas, ovf_pos, ovf_add, msg, None, n_out,
        hi=hi, wide=wide, edge_block=edge_block, dst_block=dst_block,
    )
    if hg is not None:
        out = segment_reduce.segment_sum_sorted_chunked_adaptive(
            a, d, hg, wd, p, v, m, n_with_pad,
            edge_block=edge_block, dst_block=dst_block, interpret=_interpret(),
        )
    else:
        out = segment_reduce.segment_sum_sorted_chunked(
            a, d, p, v, m, n_with_pad,
            edge_block=edge_block, dst_block=dst_block, interpret=_interpret(),
        )
    return out[:n_out]


def segment_sum_weighted_chunked(
    anchors: jax.Array,
    deltas: jax.Array,
    ovf_pos: jax.Array,
    ovf_add: jax.Array,
    w: jax.Array,
    msg: jax.Array,
    n_out: int,
    hi: jax.Array | None = None,
    wide: jax.Array | None = None,
    edge_block: int | None = None,
    dst_block: int | None = None,
) -> jax.Array:
    """Weighted chunked segment-sum; same contract as ``segment_sum_chunked``
    (weight pads are 0)."""
    R, C = deltas.shape
    if edge_block is None or dst_block is None:
        make = _sweep_segment_sum_chunked(R, C, n_out, True, hi is not None)
        tuned = autotune.get_params(
            "segment_sum_weighted_chunked", {"R": R, "n": n_out}, sweep_fn=make,
            traced=_traced(msg),
        )
        edge_block = edge_block or tuned["edge_block"]
        dst_block = dst_block or tuned["dst_block"]
    a, d, p, v, m, wp, hg, wd, n_with_pad = _pad_chunked_dst(
        anchors, deltas, ovf_pos, ovf_add, msg, w, n_out,
        hi=hi, wide=wide, edge_block=edge_block, dst_block=dst_block,
    )
    if hg is not None:
        out = segment_reduce.segment_sum_weighted_chunked_adaptive(
            a, d, hg, wd, p, v, wp, m, n_with_pad,
            edge_block=edge_block, dst_block=dst_block, interpret=_interpret(),
        )
    else:
        out = segment_reduce.segment_sum_weighted_chunked(
            a, d, p, v, wp, m, n_with_pad,
            edge_block=edge_block, dst_block=dst_block, interpret=_interpret(),
        )
    return out[:n_out]


def fanout_aggregate(feats: jax.Array, mask: jax.Array, op: str = "mean") -> jax.Array:
    B = feats.shape[0]
    f = _pad_to(feats, 8, 0)
    m = _pad_to(mask, 8, 0)
    out = segment_reduce.fanout_aggregate(f, m, op=op, interpret=_interpret())
    return out[:B]


# ---------------------------------------------------------------------------
# attention decode
# ---------------------------------------------------------------------------


def flash_decode_attn(q, k, v, lengths, seq_block: int = flash_decode.SEQ_BLOCK):
    S = k.shape[1]
    kp = _pad_to(k, seq_block, 1)
    vp = _pad_to(v, seq_block, 1)
    return flash_decode.flash_decode(
        q, kp, vp, lengths, seq_block=seq_block, interpret=_interpret()
    )


# ---------------------------------------------------------------------------
# block SpMM
# ---------------------------------------------------------------------------


def spmm(tile_mask, a_tiles, x):
    C = a_tiles.shape[3]
    xp = _pad_to(x, C, 0)
    return csr_spmm.block_spmm(tile_mask, a_tiles, xp, interpret=_interpret())


def _sweep_spmm(n: int, m: int):
    """sweep_fn factory for the block-dense SpMM tiles."""

    def make(params):
        rng = np.random.default_rng(0)
        src = rng.integers(0, max(n, 1), size=max(m, 1))
        dst = rng.integers(0, max(n, 1), size=max(m, 1))
        x = jnp.ones((n, 8), jnp.float32)

        def thunk():
            return spmm_from_edges(n, src, dst, x, **params)

        return thunk

    return make


def spmm_from_edges(
    n: int, src, dst, x, vals=None,
    row_tile: int | None = None, col_tile: int | None = None,
):
    if row_tile is None or col_tile is None:
        m = int(np.asarray(src).shape[0])
        tuned = autotune.get_params(
            "spmm", {"n": n, "m": m}, sweep_fn=_sweep_spmm(n, m), traced=_traced(x)
        )
        row_tile = row_tile or tuned["row_tile"]
        col_tile = col_tile or tuned["col_tile"]
    mask, tiles, n_pad = csr_spmm.tiles_from_edges(
        n, src, dst, vals, row_tile=row_tile, col_tile=col_tile
    )
    out = spmm(mask, tiles, x)
    return out[:n]
