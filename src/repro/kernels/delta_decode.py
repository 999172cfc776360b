"""Pallas TPU kernel: segmented delta-decode (C-tree chunk decompression).

The C-tree stores chunks as (anchor, fixed-width deltas).  Decoding chunk
``i`` is ``anchor[i] + inclusive_cumsum(deltas[i, :])`` — after the
ragged->padded layout change (ops.py), the whole decode is a batched row
prefix sum: the TPU-native replacement for the paper's sequential
per-chunk byte-code decode (§3.2).  The paper already traded compression
ratio for decode speed (byte codes over bit codes); we take the same
trade one step further (fixed-width deltas over byte codes) to make
decode a pure vector op with *zero* serial dependence between chunks.

Tiling: grid = (row_blocks, col_blocks); the column dimension is the
sequential minor axis, carrying each row-block's running sum in a VMEM
scratch accumulator of shape (ROWS, 1) — the standard TPU scan-carry
pattern.  Within a tile the prefix sum is ``lane_cumsum`` (log-step
``pltpu.roll`` adds; Mosaic has no ``cumsum`` lowering).  Block shapes
are (8k, 128k) multiples to match the VPU (8, 128) vector registers, with
taller row blocks for narrow delta lanes.  Integer constants are int32
throughout: the package runs with x64 on, and Mosaic rejects i64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ROW_BLOCK = 8
DEFAULT_COL_BLOCK = 128

_I0 = np.int32(0)


def lane_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum along the last (lane) axis.

    Log-step shifted adds (``pltpu.roll`` + mask), exact for any int32
    input."""
    axis = x.ndim - 1
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < x.shape[axis]:
        x = x + jnp.where(cols >= s, pltpu.roll(x, np.int32(s), axis), _I0)
        s *= 2
    return x


def widen(lane: jax.Array, hi: jax.Array, wide: jax.Array) -> jax.Array:
    """Adaptive-width select: ``wide ? hi * 256 + (lane & 0xFF) : lane``
    (int32; ``wide`` is a (rows, 1) tag, nonzero = wide chunk)."""
    lane = lane.astype(jnp.int32)
    return jnp.where(
        wide > 0, hi.astype(jnp.int32) * np.int32(256) + (lane & np.int32(0xFF)), lane
    )


def escape_steps(cols: jax.Array, pos: jax.Array, add: jax.Array) -> jax.Array:
    """Escape corrections: escape ``k`` of a row adds ``add[r, k]`` to
    every column >= ``pos[r, k]`` (static K, unrolled)."""
    out = jnp.zeros(cols.shape, jnp.int32)
    for k in range(pos.shape[1]):
        out = out + jnp.where(cols >= pos[:, k : k + 1], add[:, k : k + 1], _I0)
    return out


def _row_block_for(deltas_dtype) -> int:
    """Dtype-aware default row block: narrow delta lanes need taller tiles
    to meet the TPU minimum sublane counts (int8 -> (32, 128), int16 ->
    (16, 128) per the Mosaic tiling table); interpret mode accepts any."""
    return {1: 32, 2: 16}.get(jnp.dtype(deltas_dtype).itemsize, DEFAULT_ROW_BLOCK)


def _make_decode_kernel(escapes: bool, adaptive: bool):
    """One (R, C) tile: out = carry + lane_cumsum(deltas) [+ escapes].

    Anchors fold into the carry at the first column block.  Escape steps
    are functions of the GLOBAL column, so they are applied per tile from
    global column indices and the carry tracks only the lane prefix sum —
    corrections never enter the carry, keeping it branch-free.  Adaptive
    streams select each chunk's width per element before the prefix sum
    (``hi`` is the pre-gathered hi-byte plane, ``wide`` a (R, 1) tag)."""

    def kernel(*refs):
        anchors_ref, deltas_ref, *rest = refs
        if adaptive:
            hi_ref, wide_ref, *rest = rest
        if escapes:
            pos_ref, add_ref, *rest = rest
        out_ref, carry_ref = rest
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            carry_ref[...] = anchors_ref[...]  # (R, 1) absolute anchors

        if adaptive:
            d = widen(deltas_ref[...], hi_ref[...], wide_ref[...])
        else:
            d = deltas_ref[...].astype(jnp.int32)
        c = lane_cumsum(d)
        out = carry_ref[...] + c
        if escapes:
            R, C = d.shape
            cols = j * np.int32(C) + jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
            out = out + escape_steps(cols, pos_ref[...], add_ref[...])
        out_ref[...] = out
        carry_ref[...] = carry_ref[...] + c[:, -1:]

    return kernel


def _decode_call(kernel, operands, row_block, col_block, interpret):
    """Run a decode kernel over (rows, cols) tiles.  ``operands`` are
    (array, kind) pairs: kind "row" blocks (row_block, width) riding every
    column block of its rows, kind "tile" blocks (row_block, col_block)."""
    n_chunks, max_len = operands[1][0].shape
    assert n_chunks % row_block == 0 and max_len % col_block == 0
    specs = []
    for x, kind in operands:
        if kind == "tile":
            specs.append(pl.BlockSpec((row_block, col_block), lambda i, j: (i, j)))
        else:
            specs.append(pl.BlockSpec((row_block, x.shape[1]), lambda i, j: (i, _I0)))
    return pl.pallas_call(
        kernel,
        grid=(n_chunks // row_block, max_len // col_block),
        in_specs=specs,
        out_specs=pl.BlockSpec((row_block, col_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, max_len), jnp.int32),
        scratch_shapes=[pltpu.VMEM((row_block, 1), jnp.int32)],
        interpret=interpret,
    )(*(x for x, _ in operands))


_STATIC = ("row_block", "col_block", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def delta_decode_chunked(
    anchors: jax.Array,  # int32 (n_chunks,)
    deltas: jax.Array,  # int8|int16 (n_chunks, chunk_len); col 0 MUST be 0
    ovf_pos: jax.Array,  # int32 (n_chunks, K) escape columns, pad chunk_len
    ovf_add: jax.Array,  # int32 (n_chunks, K) escaped delta values
    row_block: int | None = None,
    col_block: int = DEFAULT_COL_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Decode fixed-width chunks with an escape lane (ChunkedStream rows):

      out[i, j] = anchors[i] + sum(lane deltas[i, :j+1])
                  + sum_k ovf_add[i, k] * 1[j >= ovf_pos[i, k]]

    Shapes must be multiples of the block sizes (kernels/ops.py pads).
    The escape tables ride whole (K columns) in every grid step — K is
    tiny and static, so they live comfortably in VMEM next to the tile.
    """
    if row_block is None:
        row_block = _row_block_for(deltas.dtype)
    return _decode_call(
        _make_decode_kernel(escapes=True, adaptive=False),
        [
            (anchors.reshape(-1, 1).astype(jnp.int32), "row"),
            (deltas, "tile"),
            (ovf_pos.astype(jnp.int32), "row"),
            (ovf_add.astype(jnp.int32), "row"),
        ],
        row_block, col_block, interpret,
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def delta_decode_chunked_adaptive(
    anchors: jax.Array,  # int32 (n_chunks,)
    deltas: jax.Array,  # int8 (n_chunks, chunk_len) lane; col 0 MUST be 0
    hi_g: jax.Array,  # int8 (n_chunks, chunk_len) pre-gathered hi bytes
    wide: jax.Array,  # int32 (n_chunks,) nonzero = wide chunk
    ovf_pos: jax.Array,  # int32 (n_chunks, K)
    ovf_add: jax.Array,  # int32 (n_chunks, K)
    row_block: int | None = None,
    col_block: int = DEFAULT_COL_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Decode adaptive-width chunks (ChunkedStream rows with width tags):
    branch-free per-chunk int8/int16 select inside the grid, then the
    same scan-carry prefix sum + escape corrections as
    ``delta_decode_chunked``.  The hi plane is pre-gathered by ops.py
    (block specs cannot express the compacted plane's data-dependent
    row index).  Shapes must be block multiples (ops.py pads)."""
    if row_block is None:
        row_block = _row_block_for(deltas.dtype)
    return _decode_call(
        _make_decode_kernel(escapes=True, adaptive=True),
        [
            (anchors.reshape(-1, 1).astype(jnp.int32), "row"),
            (deltas, "tile"),
            (hi_g, "tile"),
            (wide.reshape(-1, 1).astype(jnp.int32), "row"),
            (ovf_pos.astype(jnp.int32), "row"),
            (ovf_add.astype(jnp.int32), "row"),
        ],
        row_block, col_block, interpret,
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def delta_decode_padded(
    anchors: jax.Array,  # int32 (n_chunks,)
    deltas: jax.Array,  # int32 (n_chunks, max_len); col 0 MUST be 0
    row_block: int = DEFAULT_ROW_BLOCK,
    col_block: int = DEFAULT_COL_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Decode padded chunks: out[i, j] = anchors[i] + sum(deltas[i, :j+1]).

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    return _decode_call(
        _make_decode_kernel(escapes=False, adaptive=False),
        [(anchors.reshape(-1, 1).astype(jnp.int32), "row"),
         (deltas.astype(jnp.int32), "tile")],
        row_block, col_block, interpret,
    )
