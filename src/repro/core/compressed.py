"""Device-resident chunked delta encoding (the compressed pool lane).

The paper stores massive graphs at a few bytes per edge by chunking each
C-tree and difference-encoding within chunks (§3.2).  ``chunks.py`` holds
the host-side codecs (paper-faithful vbyte, and the fixed-width
``pack_deltas`` reference); this module is the DEVICE layout those
reference: a sorted-ish int32 stream cut into fixed ``CHUNK``-slot rows,
each row stored as

  ``(anchor int32, deltas int8|int16[CHUNK], escape corrections)``

where ``deltas[:, 0] == 0`` (the anchor position) and decode is the
batched row cumsum the seed Pallas kernel (``kernels/delta_decode.py``)
implements — zero serial dependence between chunks.

Fixed chunk geometry (vs. the paper's hash-canonical boundaries) is what
makes the layout *streaming-maintainable* under jit: every shape is
static, so the same compiled encode/decode serves a whole update stream,
and ``CHUNK`` divides the segment-sum kernel's edge block so decode can
fuse into the reduce as an in-kernel prologue (no chunk ever straddles a
kernel tile).

Escape lane
-----------
A delta that overflows the fixed-width lane (|delta| > 127 for int8,
> 32767 for int16) is stored as 0 in the lane and carried in a per-chunk
escape table of ``k`` (static) slots: ``ovf_pos[r, j]`` is the column of
the j-th escaped delta in chunk ``r`` (ascending; ``CHUNK`` marks an
unused slot) and ``ovf_add[r, j]`` the full int32 delta.  Because each
correction applies to every column >= its position, decode stays a pure
cumsum plus ``k`` masked adds — the scan-carry never has to branch.  A
chunk with more than ``k`` escapes sets the ``spill`` flag: the stream no
longer round-trips and callers must fall back to the raw layout (host
builders check the flag once; see ``flat_graph.compress_host``).

Adaptive per-chunk widths (DESIGN.md §12)
-----------------------------------------
A fixed lane width wastes a byte per slot on every chunk whose deltas fit
int8 — ``flat_graph.chunk_stats`` measures exactly that gap
(``bytes_ideal``).  The adaptive layout closes it: the lane stays ONE
int8 plane (field ``deltas``), and each chunk carries a width tag
(``wide`` bool[R]).  A narrow chunk stores its signed delta in the lane
directly; a wide chunk stores the delta's LOW byte (two's-complement bit
pattern) in the lane and its HIGH byte in a *compacted* second plane
``hi`` (int8[H, CHUNK]) holding only the wide chunks' rows, in chunk
order.  The hi-row index is never stored — it is
``cumsum(wide) - 1``, recomputed in-trace — so decode stays branch-free:

  ``delta = wide ? hi * 256 + (lane & 0xFF) : lane``

(``stored >> 8`` / ``stored & 0xFF`` is an exact int16 split: the
arithmetic shift keeps ``hi`` in int8 range for any |delta| <= 32767).
The escape lane is unchanged — int8-range escapes are free in a narrow
chunk (the k slots are statically allocated), so a chunk only goes wide
when it has MORE than ``k`` over-int8 deltas; per-slot escapes then use
the int16 limit.  ``H`` (the hi-plane capacity) is static; more wide
chunks than ``H`` fold into the same ``spill`` flag as escape overflow,
and streaming callers rebuild from the source (``AspenStream`` mirrors
carry headroom so this is rare).  Bytes/chunk: narrow 197 vs wide 325 vs
fixed-int16 324 — adaptive never loses unless every chunk is wide.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 128  # slots per chunk; divides segment_reduce.EDGE_BLOCK (512)
OVF_SLOTS = 8  # default static escape-lane capacity per chunk

_WIDTH_DTYPE = {1: jnp.int8, 2: jnp.int16}
_WIDTH_LIMIT = {1: 127, 2: 32767}


class ChunkedStream(NamedTuple):
    """Delta-encoded int32 stream in fixed ``CHUNK``-slot rows; a pytree.

    anchors : int32[R]        absolute value at each chunk start
    deltas  : int8|int16[R, CHUNK]  col 0 == 0; escaped deltas hold 0
    ovf_pos : int32[R, K]     column of each escaped delta (pad CHUNK)
    ovf_add : int32[R, K]     the escaped delta's full value
    spill   : bool[]          some chunk had > K escapes (decode unsound)
    hi      : int8[H, CHUNK]  adaptive only: compacted high-byte plane
                              (None on fixed-width streams)
    wide    : bool[R]         adaptive only: per-chunk width tag

    The encoded length is ``R * CHUNK``; streams shorter than that are
    tail-padded by repeating the last element (delta 0), so decode of the
    padded region is benign and callers slice to their own length.
    """

    anchors: jax.Array
    deltas: jax.Array
    ovf_pos: jax.Array
    ovf_add: jax.Array
    spill: jax.Array
    hi: Optional[jax.Array] = None
    wide: Optional[jax.Array] = None

    @property
    def length(self) -> int:
        return self.deltas.shape[-2] * self.deltas.shape[-1]

    @property
    def width(self) -> int:
        return jnp.dtype(self.deltas.dtype).itemsize

    @property
    def k(self) -> int:
        return self.ovf_pos.shape[-1]

    @property
    def adaptive(self) -> bool:
        return self.hi is not None

    @property
    def hi_cap(self) -> int:
        """Static hi-plane capacity in chunks (0 on fixed-width streams)."""
        return 0 if self.hi is None else self.hi.shape[-2]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _encode_impl(values: jax.Array, width: int, k: int) -> ChunkedStream:
    if width not in _WIDTH_DTYPE:
        raise ValueError(f"width must be 1 or 2 bytes, got {width}")
    rows, deltas = _chunk_deltas(values)
    lim = _WIDTH_LIMIT[width]
    esc = (deltas < -lim) | (deltas > lim)
    stored = jnp.where(esc, 0, deltas).astype(_WIDTH_DTYPE[width])
    R = rows.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, CHUNK), 1)
    pos_all = jnp.where(esc, cols, jnp.int32(CHUNK))
    order = jnp.argsort(pos_all, axis=1)[:, :k]  # escapes first, ascending
    ovf_pos = jnp.take_along_axis(pos_all, order, axis=1)
    ovf_add = jnp.take_along_axis(jnp.where(esc, deltas, 0), order, axis=1)
    spill = (esc.sum(axis=1) > k).any()
    return ChunkedStream(
        anchors=rows[:, 0].astype(jnp.int32),
        deltas=stored,
        ovf_pos=ovf_pos.astype(jnp.int32),
        ovf_add=ovf_add.astype(jnp.int32),
        spill=spill,
    )


encode_stream = functools.partial(jax.jit, static_argnames=("width", "k"))(
    lambda values, width=2, k=OVF_SLOTS: _encode_impl(values, width, k)
)
encode_stream.__doc__ = (
    "jit encode: int32[L] -> ChunkedStream (static width in bytes, static"
    " escape capacity k).  See the module docstring for the layout."
)


def _chunk_deltas(values: jax.Array):
    """Shared chunking prologue: edge-padded (R, CHUNK) rows + their
    within-chunk deltas (col 0 == 0)."""
    L = values.shape[0]
    if L == 0:
        values = jnp.zeros((1,), jnp.int32)
        L = 1
    Lp = _round_up(L, CHUNK)
    v = jnp.pad(values.astype(jnp.int32), (0, Lp - L), mode="edge")
    rows = v.reshape(-1, CHUNK)
    prev = jnp.concatenate([rows[:, :1], rows[:, :-1]], axis=1)
    return rows, rows - prev


def _encode_adaptive_impl(
    values: jax.Array, hi_cap: int, k: int
) -> ChunkedStream:
    """Adaptive-width encode (module docstring): one int8 lane + a
    compacted hi-byte plane of STATIC capacity ``hi_cap`` chunks.  A
    chunk goes wide iff more than ``k`` of its deltas overflow int8
    (narrow escapes are free — the k slots exist either way); running
    out of hi-plane rows folds into ``spill`` exactly like escape
    overflow."""
    rows, deltas = _chunk_deltas(values)
    R = rows.shape[0]
    abs_d = jnp.abs(deltas)
    wide = (abs_d > _WIDTH_LIMIT[1]).sum(axis=1) > k  # bool[R]
    lim = jnp.where(wide[:, None], _WIDTH_LIMIT[2], _WIDTH_LIMIT[1])
    esc = abs_d > lim
    stored = jnp.where(esc, 0, deltas)  # int32, |.| <= per-chunk limit
    # lane = signed low byte (== the full delta on narrow chunks)
    lane = (((stored & 0xFF) ^ 0x80) - 0x80).astype(jnp.int8)
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, CHUNK), 1)
    pos_all = jnp.where(esc, cols, jnp.int32(CHUNK))
    order = jnp.argsort(pos_all, axis=1)[:, :k]
    ovf_pos = jnp.take_along_axis(pos_all, order, axis=1)
    ovf_add = jnp.take_along_axis(jnp.where(esc, deltas, 0), order, axis=1)
    wide_i = wide.astype(jnp.int32)
    hi_idx = jnp.cumsum(wide_i, dtype=jnp.int32) - 1  # compacted row per wide chunk
    target = jnp.where(wide, hi_idx, hi_cap)
    hi = (
        jnp.zeros((hi_cap, CHUNK), jnp.int8)
        .at[target]
        .set(jnp.where(wide[:, None], stored >> 8, 0).astype(jnp.int8),
             mode="drop")
    )
    spill = (esc.sum(axis=1) > k).any() | (wide_i.sum() > hi_cap)
    return ChunkedStream(
        anchors=rows[:, 0].astype(jnp.int32),
        deltas=lane,
        ovf_pos=ovf_pos.astype(jnp.int32),
        ovf_add=ovf_add.astype(jnp.int32),
        spill=spill,
        hi=hi,
        wide=wide,
    )


encode_stream_adaptive = functools.partial(
    jax.jit, static_argnames=("hi_cap", "k")
)(lambda values, hi_cap, k=OVF_SLOTS: _encode_adaptive_impl(values, hi_cap, k))
encode_stream_adaptive.__doc__ = (
    "jit adaptive encode: int32[L] -> ChunkedStream with per-chunk width"
    " tags (static hi-plane capacity in chunks, static escape capacity k)."
)


def adaptive_deltas(c: ChunkedStream) -> jax.Array:
    """Reconstruct the per-slot int32 deltas of an adaptive stream's lane
    (escapes still 0 — callers add the ovf corrections).  The branch-free
    width select: wide ? hi * 256 + (lane & 0xFF) : lane, with the
    compacted hi row recovered in-trace as ``cumsum(wide) - 1``.
    ndim-aware like ``decode_rows`` (leaves may be (S, ...)-batched)."""
    lane = c.deltas.astype(jnp.int32)
    H = c.hi.shape[-2]
    if H == 0:
        # no wide chunk can exist without spilling; lane is exact
        return lane
    idx = jnp.clip(
        jnp.cumsum(c.wide, axis=-1, dtype=jnp.int32) - 1, 0, H - 1
    )
    hi_g = jnp.take_along_axis(c.hi.astype(jnp.int32), idx[..., None], axis=-2)
    return jnp.where(c.wide[..., None], hi_g * 256 + (lane & 0xFF), lane)


def decode_rows(c: ChunkedStream) -> jax.Array:
    """Pure-jnp decode to (R, CHUNK) int32 rows: anchor + row cumsum plus
    the escape-lane step corrections.  Traced inline by every consumer so
    XLA fuses the decode with whatever reads it — the non-Pallas half of
    the fused-decode contract (the Pallas half lives in
    ``kernels/delta_decode.py`` / ``kernels/segment_reduce.py``)."""
    d = adaptive_deltas(c) if c.hi is not None else c.deltas.astype(jnp.int32)
    base = c.anchors[..., None] + jnp.cumsum(d, axis=-1, dtype=jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, c.deltas.shape, c.deltas.ndim - 1)
    corr = jnp.sum(
        jnp.where(cols[..., None] >= c.ovf_pos[..., None, :], c.ovf_add[..., None, :], 0),
        axis=-1,
    )
    return base + corr


def decode_stream(c: ChunkedStream, length: int | None = None) -> jax.Array:
    """Decode to a flat int32 array (first ``length`` slots; full padded
    stream when None)."""
    flat = decode_rows(c).reshape(*c.deltas.shape[:-2], -1)
    if length is None:
        return flat
    return flat[..., :length]


def stream_nbytes(c: ChunkedStream) -> int:
    """Device-resident bytes of the stream (host accounting helper)."""
    arrays = [c.anchors, c.deltas, c.ovf_pos, c.ovf_add]
    if c.hi is not None:
        arrays += [c.hi, c.wide]
    return sum(
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize for a in arrays
    )


def pytree_nbytes(tree) -> int:
    """Total bytes of every array leaf of a pytree (host accounting for
    the BYTES bench / ``TraversalEngine.resident_nbytes``)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total
