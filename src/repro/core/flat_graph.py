"""TPU-native Aspen graph: CSR over a hash-chunked sorted edge pool.

The faithful level (graph.py) is a tree of C-trees.  Here the whole edge
set is ONE flat C-tree over packed 64-bit keys ``(src << 32) | dst`` —
CSR's edge array *is* the sorted pool, and per-vertex adjacency lists are
contiguous key ranges.  This is exact, not an approximation: a C-tree's
in-order traversal is the sorted pool, and headness is canonical, so the
chunk boundaries (for delta compression) are recomputable by one hash
pass (paper §3.1's key insight, vectorized).

Batch updates are the flat C-tree rank-merge over packed keys (a binary
search per batch row, then shifts of the pool: ``flat_ctree.union_merge``)
and an update of the offsets from the batch rows the merge kept or
dropped (a histogram of their sources and one prefix sum over the n+1
offsets).  On TPU these streaming passes over the pool are
*bandwidth-bound* and beat pointer-chasing by orders of magnitude; the
paper's O(k log n) tree update is the CPU-optimal point of the same
design space (DESIGN.md §2, §8).  The offsets update relies on every
edge's src lying below n (``offsets[n] == m``).

Everything here is fixed-shape jit: graphs carry static (n, edge_capacity)
and a dynamic valid count, so the same compiled update/query step serves a
whole stream.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compressed as cz
from . import flat_ctree as fct
from .hash import is_head_jnp

SENT64 = fct.sentinel_for(jnp.int64)


class FlatGraph(NamedTuple):
    """Immutable graph snapshot; a jax pytree (shardable over edges).

    ``weights`` optionally carries one float32 per pool slot, parallel
    to ``keys`` (the property-graph value array, DESIGN.md §8): every
    rank-merge / compaction permutes it alongside the keys, inserting a
    duplicate key overwrites its weight, deleting a key drops it.
    ``weights is None`` is the unweighted layout — no value array is
    allocated and every kernel traces exactly as before.
    """

    offsets: jax.Array  # int32[n+1] CSR offsets (valid prefix of pool)
    keys: jax.Array  # int64[cap] sorted packed (src<<32|dst); pad SENT64
    m: jax.Array  # int32 scalar: valid edge count
    weights: jax.Array | None = None  # float32[cap] per-edge values (pad 0)

    @property
    def n(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def edge_capacity(self) -> int:
        return self.keys.shape[0]


def pack(src: jax.Array, dst: jax.Array) -> jax.Array:
    return (src.astype(jnp.int64) << 32) | dst.astype(jnp.int64)


def unpack(keys: jax.Array):
    return (keys >> 32).astype(jnp.int32), (keys & 0xFFFFFFFF).astype(jnp.int32)


def _offsets_from_keys(keys: jax.Array, m: jax.Array, n: int) -> jax.Array:
    """offsets[v] = #edges with src < v; one vectorized searchsorted."""
    bounds = (jnp.arange(n + 1, dtype=jnp.int64) << 32)
    offs = jnp.searchsorted(keys, bounds).astype(jnp.int32)
    return jnp.minimum(offs, m.astype(jnp.int32))


def from_edges(
    n: int,
    edges: np.ndarray,
    edge_capacity: int | None = None,
    weights: np.ndarray | None = None,
) -> FlatGraph:
    """Host build from a (k, 2) directed edge array (dedups; a
    duplicated edge keeps the FIRST occurrence's weight)."""
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    packed = (edges[:, 0] << 32) | edges[:, 1]
    if weights is None:
        keys = np.unique(packed)
        w = None
    else:
        keys, first = np.unique(packed, return_index=True)
        w = np.asarray(weights, dtype=np.float32).reshape(-1)[first]
    if edge_capacity is None:
        edge_capacity = fct.grown_capacity(keys.size)
    assert keys.size <= edge_capacity
    pool = np.full(edge_capacity, SENT64, dtype=np.int64)
    pool[: keys.size] = keys
    keys_j = jnp.asarray(pool)
    m = jnp.int32(keys.size)
    wpool = None
    if w is not None:
        wbuf = np.zeros(edge_capacity, dtype=np.float32)
        wbuf[: keys.size] = w
        wpool = jnp.asarray(wbuf)
    return FlatGraph(_offsets_from_keys(keys_j, m, n), keys_j, m, wpool)


def with_unit_weights(g: FlatGraph) -> FlatGraph:
    """Attach a unit value array to an unweighted graph (the upgrade an
    unweighted pool takes when its first weighted batch arrives)."""
    if g.weights is not None:
        return g
    return g._replace(weights=jnp.ones(g.edge_capacity, jnp.float32))


def to_edge_array(g: FlatGraph) -> np.ndarray:
    k = np.asarray(g.keys)[: int(g.m)]
    return np.stack([k >> 32, k & 0xFFFFFFFF], axis=1)


def to_weight_array(g: FlatGraph) -> np.ndarray | None:
    """Per-edge weights aligned with ``to_edge_array`` (None when
    unweighted)."""
    return None if g.weights is None else np.asarray(g.weights)[: int(g.m)]


# ---------------------------------------------------------------------------
# queries (jit, fixed shape)
# ---------------------------------------------------------------------------


@jax.jit
def degrees(g: FlatGraph) -> jax.Array:
    return jnp.diff(g.offsets)


@jax.jit
def edge_endpoints(g: FlatGraph):
    """(src, dst) per pool slot (padding slots give n-off-range ids)."""
    return unpack(g.keys)


@jax.jit
def has_edge(g: FlatGraph, src: jax.Array, dst: jax.Array) -> jax.Array:
    q = pack(src, dst)
    idx = jnp.minimum(jnp.searchsorted(g.keys, q), g.keys.shape[0] - 1)
    return g.keys[idx] == q


@functools.partial(jax.jit, static_argnums=(1, 2))
def chunk_structure(g: FlatGraph, b: int, seed: int):
    """Canonical chunk boundaries over the pool: head iff hash(dst) mod b
    == 0 OR first edge of a vertex (every adjacency list restarts its
    prefix, mirroring the per-vertex C-trees of the faithful level)."""
    src, dst = unpack(g.keys)
    valid = jnp.arange(g.keys.shape[0]) < g.m
    hm = is_head_jnp(dst.astype(jnp.uint32), b, seed) & valid
    first_of_vertex = jnp.zeros_like(hm).at[g.offsets[:-1]].set(True) & valid
    return hm | first_of_vertex


# ---------------------------------------------------------------------------
# batch updates (jit): the streaming hot path
# ---------------------------------------------------------------------------


def _src_counts_below(keys: jax.Array, rows: jax.Array, n: int) -> jax.Array:
    """[n+1] count of the selected ``rows`` of ``keys`` whose src is below
    each vertex id: a histogram over the rows, then one prefix sum."""
    src = jnp.where(rows, keys >> 32, n + 1)
    hist = jnp.zeros((n + 1,), jnp.int32).at[src].add(1, mode="drop")
    return jnp.cumsum(hist, dtype=jnp.int32) - hist


def _resized_offsets(offsets: jax.Array, n: int) -> jax.Array:
    """Offsets over ``n`` vertices from offsets over ``offsets.shape[0]-1``:
    vertices past the old range have no edges yet (every src < n)."""
    n_old = offsets.shape[0] - 1
    if n <= n_old:
        return offsets[: n + 1]
    return jnp.concatenate([offsets, jnp.full((n - n_old,), offsets[-1], offsets.dtype)])


def _insert_edges_impl(
    g: FlatGraph, batch: fct.FlatCTree, out_cap: int, optimized: bool, n_out: int | None
) -> FlatGraph:
    pool = fct.FlatCTree(g.keys, g.m, g.weights)
    n = g.offsets.shape[0] - 1 if n_out is None else n_out
    if not optimized:  # the baseline: sort, then rebuild offsets by search
        merged = fct.union_sort(pool, batch, out_cap)
        with jax.named_scope("merge.offsets"):
            offsets = _offsets_from_keys(merged.data, merged.n, n)
        return FlatGraph(offsets, merged.data, merged.n, merged.vals)
    merged, kept = fct.merge_ranked(pool, batch, out_cap)
    with jax.named_scope("merge.offsets"):
        offsets = _resized_offsets(g.offsets, n) + _src_counts_below(batch.data, kept, n)
    return FlatGraph(offsets, merged.data, merged.n, merged.vals)


def _delete_edges_impl(
    g: FlatGraph, batch: fct.FlatCTree, out_cap: int
) -> FlatGraph:
    pool = fct.FlatCTree(g.keys, g.m, g.weights)
    out, found = fct.difference_ranked(pool, batch, out_cap)
    with jax.named_scope("merge.offsets"):
        offsets = g.offsets - _src_counts_below(batch.data, found, g.offsets.shape[0] - 1)
    return FlatGraph(offsets, out.data, out.n, out.vals)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def insert_edges(
    g: FlatGraph,
    batch: fct.FlatCTree,
    out_cap: int,
    optimized: bool = True,
    n_out: int | None = None,
) -> FlatGraph:
    """InsertEdges: rank-merge batch keys into the pool, rebuild offsets.

    ``batch`` is a FlatCTree of packed keys (sorted, deduped, padded).
    ``n_out`` grows the vertex count (offsets array) when the batch
    introduces vertex ids past the current range.  Device scopes:
    ``merge.rank`` and ``merge.scatter`` (``fct.merge_ranked``), then
    ``merge.offsets`` (the old offsets plus the kept rows' sources below
    each vertex); ``delete_edges`` has the same three
    (``fct.difference_ranked``, the found rows' sources subtracted).
    ``optimized=False`` is the baseline: ``fct.union_sort``, then the
    offsets rebuilt by one search per vertex.
    """
    return _insert_edges_impl(g, batch, out_cap, optimized, n_out)


@functools.partial(jax.jit, static_argnums=(2,))
def delete_edges(g: FlatGraph, batch: fct.FlatCTree, out_cap: int) -> FlatGraph:
    return _delete_edges_impl(g, batch, out_cap)


# donating variants: the old pool buffer is handed back to XLA so the
# merge can reuse it in place (streaming pipelines that own the sole
# reference; versioned mirrors shared with live readers must NOT donate).
_insert_edges_donating = functools.partial(
    jax.jit, static_argnums=(2, 3, 4), donate_argnums=(0,)
)(_insert_edges_impl)
_delete_edges_donating = functools.partial(
    jax.jit, static_argnums=(2,), donate_argnums=(0,)
)(_delete_edges_impl)


def insert_edges_device(
    g: FlatGraph,
    batch: fct.FlatCTree,
    out_cap: int | None = None,
    *,
    optimized: bool = True,
    n_out: int | None = None,
    donate: bool = False,
) -> FlatGraph:
    """Host-free InsertEdges: ``batch`` is already device-resident (see
    ``fct.from_device``), no edge data is copied through numpy, and with
    ``donate=True`` the old pool buffer is donated to the merge.

    NOTE: the ``out_cap=None`` convenience reads two device scalars
    (``g.m``, ``batch.n``) to size the output pool exactly, which blocks
    on the previous merge.  Fully-async pipelines must pass ``out_cap``
    from host-tracked counts, as ``AspenStream`` does.  (Sizing from
    static shapes instead would grow the pool on every call.)

    Donation invalidates ``g``'s buffers — only pass it when the caller
    holds the sole reference (NOT for pools shared across live versions;
    backends without donation support silently copy instead).
    """
    if out_cap is None:
        out_cap = max(g.edge_capacity, fct.grown_capacity(int(g.m) + int(batch.n)))
    fn = _insert_edges_donating if donate else insert_edges
    return fn(g, batch, out_cap, optimized, n_out)


def delete_edges_device(
    g: FlatGraph, batch: fct.FlatCTree, out_cap: int | None = None, *, donate: bool = False
) -> FlatGraph:
    """Host-free DeleteEdges (see ``insert_edges_device`` for donation)."""
    if out_cap is None:
        out_cap = g.edge_capacity
    fn = _delete_edges_donating if donate else delete_edges
    return fn(g, batch, out_cap)


# ---------------------------------------------------------------------------
# compressed pool: the paper's bytes-per-edge layout, device-resident
# ---------------------------------------------------------------------------


class CompressedPool(NamedTuple):
    """FlatGraph with the dst lane chunk-compressed (paper §3.2 on device).

    Same CSR contract as FlatGraph — ``offsets`` indexes the sorted pool,
    ``m`` counts the valid prefix — but the pool itself is factored:

    * src ids are IMPLIED by ``offsets`` (a src-major run never needs its
      src stored per edge; one searchsorted recovers it), and
    * dst ids are delta-chunked (``core/compressed.ChunkedStream``): an
      int32 anchor plus int8/int16 deltas per 128-slot chunk with an
      escape lane for overflow deltas.

    At int16 lane width this is ~2.6 resident bytes/edge against the raw
    pool's 8 (the packed int64 key), before the O(n) offsets both layouts
    share.  ``weights`` stays an uncompressed float32 lane (values are
    not delta-friendly), padded to the chunked capacity.

    Updates decompress -> rank-merge -> recompress inside ONE jit
    (``insert_edges_compressed``): the uncompressed pool exists only as a
    transient inside the update step, the *resident* state is always
    compressed — the CPMA-style contract for batch updates on compressed
    flat layouts.
    """

    offsets: jax.Array  # int32[n+1] CSR offsets (valid prefix of pool)
    dst: cz.ChunkedStream  # chunked dst per pool slot; length = capacity
    m: jax.Array  # int32 scalar: valid edge count
    weights: jax.Array | None = None  # float32[cap] per-edge values (pad 0)

    @property
    def n(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def edge_capacity(self) -> int:
        return self.dst.length


def src_from_offsets(offsets: jax.Array, cap: int) -> jax.Array:
    """Recover per-slot src ids from CSR offsets (slot j belongs to the
    vertex whose offset range contains j); slots past offsets[n] map to n."""
    slots = jnp.arange(cap, dtype=offsets.dtype)
    return (jnp.searchsorted(offsets, slots, side="right") - 1).astype(jnp.int32)


def _compress_impl(
    g: FlatGraph, width: int, k: int, hi_cap: int | None = None
) -> CompressedPool:
    cap = g.edge_capacity
    _, dst = unpack(g.keys)
    # Pad slots hold SENT64 (dst lane decodes to -1); encoding that cliff
    # would waste an escape slot per boundary chunk, so carry the last
    # valid dst forward instead — decompress masks pad slots to SENT64
    # from ``m`` anyway, the encoded pad content is never observed.
    last = dst[jnp.maximum(g.m - 1, 0)]
    dst_enc = jnp.where(jnp.arange(cap) < g.m, dst, last)
    if hi_cap is None:
        stream = cz.encode_stream(dst_enc, width=width, k=k)
    else:  # adaptive per-chunk widths; ``width`` is ignored
        stream = cz.encode_stream_adaptive(dst_enc, hi_cap=hi_cap, k=k)
    w = g.weights
    if w is not None and stream.length > cap:
        w = jnp.pad(w, (0, stream.length - cap))
    return CompressedPool(g.offsets, stream, g.m.astype(jnp.int32), w)


compress = functools.partial(
    jax.jit, static_argnames=("width", "k", "hi_cap")
)(lambda g, width=2, k=cz.OVF_SLOTS, hi_cap=None: _compress_impl(g, width, k, hi_cap))
compress.__doc__ = (
    "jit FlatGraph -> CompressedPool (static lane width/escape capacity;"
    " hi_cap selects the adaptive per-chunk-width layout)."
)


def _decompress_impl(cg: CompressedPool) -> FlatGraph:
    cap = cg.edge_capacity
    dst = cz.decode_stream(cg.dst)
    src = src_from_offsets(cg.offsets, cap)
    packed = (src.astype(jnp.int64) << 32) | (dst.astype(jnp.int64) & 0xFFFFFFFF)
    keys = jnp.where(jnp.arange(cap) < cg.m, packed, SENT64)
    return FlatGraph(cg.offsets, keys, cg.m, cg.weights)


decompress = jax.jit(_decompress_impl)
decompress.__doc__ = (
    "jit CompressedPool -> FlatGraph (exact inverse of ``compress`` for"
    " non-spilled streams; pad slots come back as SENT64)."
)


def compress_host(
    g: FlatGraph,
    width: int | None = None,
    k: int = cz.OVF_SLOTS,
    hi_headroom: float = 0.0,
) -> CompressedPool:
    """Host build: compress with width selection and a one-time spill
    check (the one place a host sync is acceptable — builds and
    rebuilds, not the streaming hot path).

    ``width=None`` (the default) builds the ADAPTIVE per-chunk-width
    layout: encode once with a full-capacity hi plane, then slice the
    plane to exactly the wide-chunk count — resident bytes match
    ``chunk_stats(g)["bytes_ideal"]`` by construction.  ``hi_headroom``
    reserves extra hi rows as a fraction of the chunk count so streaming
    updates can widen chunks in place without spilling (0.0 = exact
    fit).  ``width=1|2`` pins the fixed-width layout.  Raises if the
    stream spills its escape lane either way — the caller keeps the raw
    layout; silent corruption is never an option.
    """
    if width is None:
        R = (max(g.edge_capacity, 1) + cz.CHUNK - 1) // cz.CHUNK
        cg = compress(g, k=k, hi_cap=R)
        if bool(cg.dst.spill):
            raise ValueError(
                f"graph spills the k={k} escape lane even at adaptive "
                "(int16-wide) chunks; keep the raw pool (delta gaps "
                "exceed the chunk escape budget)"
            )
        n_wide = int(np.asarray(cg.dst.wide).sum())
        hi_cap = n_wide
        if hi_headroom > 0.0:
            hi_cap = min(R, n_wide + max(4, int(np.ceil(hi_headroom * R))))
        hi = jnp.asarray(np.asarray(cg.dst.hi)[:hi_cap])
        return cg._replace(dst=cg.dst._replace(hi=hi))
    cg = compress(g, width=width, k=k)
    if bool(cg.dst.spill):
        raise ValueError(
            f"graph spills the k={k} escape lane at width={width} deltas; "
            "keep the raw pool (delta gaps exceed the chunk escape budget)"
        )
    return cg


def with_unit_weights_compressed(cg: CompressedPool) -> CompressedPool:
    """Compressed counterpart of ``with_unit_weights``."""
    if cg.weights is not None:
        return cg
    return cg._replace(weights=jnp.ones(cg.edge_capacity, jnp.float32))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def insert_edges_compressed(
    cg: CompressedPool,
    batch: fct.FlatCTree,
    out_cap: int,
    optimized: bool = True,
    n_out: int | None = None,
) -> CompressedPool:
    """InsertEdges on the compressed pool: decompress -> rank-merge ->
    recompress, one jit.  Lane width (or the adaptive layout's hi-plane
    capacity) and escape capacity are inherited from the input stream
    (static via its dtypes/shapes) — adaptive streams re-select each
    chunk's width on recompress — so a whole update stream reuses one
    compiled step.  The output spill flag ORs in the input's — once a
    stream spills it stays flagged until rebuilt."""
    g = _decompress_impl(cg)
    g2 = _insert_edges_impl(g, batch, out_cap, optimized, n_out)
    hi_cap = cg.dst.hi.shape[-2] if cg.dst.hi is not None else None
    out = _compress_impl(g2, cg.dst.width, cg.dst.k, hi_cap)
    return out._replace(dst=out.dst._replace(spill=out.dst.spill | cg.dst.spill))


@functools.partial(jax.jit, static_argnums=(2,))
def delete_edges_compressed(
    cg: CompressedPool, batch: fct.FlatCTree, out_cap: int
) -> CompressedPool:
    """DeleteEdges on the compressed pool (see ``insert_edges_compressed``)."""
    g = _decompress_impl(cg)
    g2 = _delete_edges_impl(g, batch, out_cap)
    hi_cap = cg.dst.hi.shape[-2] if cg.dst.hi is not None else None
    out = _compress_impl(g2, cg.dst.width, cg.dst.k, hi_cap)
    return out._replace(dst=out.dst._replace(spill=out.dst.spill | cg.dst.spill))


def chunk_stats(
    g: FlatGraph, *, b: int = cz.CHUNK, seed: int = 0, k: int = cz.OVF_SLOTS
) -> dict:
    """Host-side reference statistics for the compressed layout.

    Wires the canonical ``chunk_structure`` boundaries (hash heads — the
    paper's recomputable chunking) alongside the fixed-geometry chunks the
    device layout actually uses, and reports per-chunk delta widths and
    escape counts.  ``bytes_ideal`` is the EXACT resident byte count of
    the adaptive per-chunk-width layout (``compress_host(g)``): the stat
    and the encoder agree by construction — a chunk goes wide iff more
    than ``k`` of its deltas overflow int8, and the layout pays
    anchors(4) + lane(CHUNK) + wide tag(1) + escape slots(8k) per chunk
    plus CHUNK hi-plane bytes per wide chunk.  ``tests/test_compressed.py``
    pins ``bytes_ideal == stream_nbytes`` of the built pool on RMAT
    streams; the BYTES bench reports it next to the fixed-width layouts.
    """
    heads = np.asarray(chunk_structure(g, b, seed))
    m = int(g.m)
    cap = g.edge_capacity
    # low 32 bits viewed as int32 (matching device ``unpack``), widened
    dst = (np.asarray(g.keys) & 0xFFFFFFFF).astype(np.uint32).view(np.int32).astype(np.int64)
    if m > 0:
        dst[m:] = dst[m - 1]  # encoder's carry-forward pad convention
    else:
        dst[:] = 0
    capC = ((max(cap, 1) + cz.CHUNK - 1) // cz.CHUNK) * cz.CHUNK
    dstp = np.concatenate([dst, np.full(capC - cap, dst[-1] if cap else 0, np.int64)])
    rows = dstp.reshape(-1, cz.CHUNK)
    deltas = np.diff(rows, axis=1, prepend=rows[:, :1])
    absd = np.abs(deltas)
    chunk_max = absd.max(axis=1) if rows.size else np.zeros(0, np.int64)
    width_per_chunk = np.where(chunk_max <= 127, 1, np.where(chunk_max <= 32767, 2, 4))
    esc8 = (absd > 127).sum(axis=1)
    esc16 = (absd > 32767).sum(axis=1)
    R = rows.shape[0]
    ovf_bytes = 2 * 4 * k  # pos + add lanes, int32
    bytes_fixed = {
        w: R * (4 + w * cz.CHUNK + ovf_bytes) for w in (1, 2)
    }
    # the adaptive encoder's exact width rule + byte accounting
    wide = esc8 > k
    n_wide = int(wide.sum())
    bytes_ideal = R * (4 + cz.CHUNK + 1 + ovf_bytes) + n_wide * cz.CHUNK
    return {
        "canonical_chunks": int(heads.sum()),
        "fixed_chunks": R,
        "max_abs_delta": int(chunk_max.max()) if R else 0,
        "width_per_chunk": width_per_chunk,
        "escapes_i8": int(esc8.sum()),
        "escapes_i16": int(esc16.sum()),
        "spill_i8": bool((esc8 > k).any()),
        "spill_i16": bool((esc16 > k).any()),
        "bytes_fixed": bytes_fixed,
        "n_wide": n_wide,
        "bytes_ideal": int(bytes_ideal),
    }


def batch_from_edges(
    edges: np.ndarray, cap: int | None = None, weights: np.ndarray | None = None
) -> fct.FlatCTree:
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    keys = (edges[:, 0] << 32) | edges[:, 1]
    return fct.from_array(keys, cap=cap, dtype=jnp.int64, vals=weights)


def insert_edges_host(
    g: FlatGraph,
    edges: np.ndarray,
    optimized: bool = True,
    weights: np.ndarray | None = None,
) -> FlatGraph:
    """Host-driven insert with capacity policy (quantized growth).  A
    weighted batch against an unweighted pool upgrades the pool to unit
    weights first (insert overwrites the weight of an existing edge)."""
    if weights is not None and g.weights is None:
        g = with_unit_weights(g)
    batch = batch_from_edges(edges, weights=weights)
    need = int(g.m) + int(batch.n)
    cap = max(g.edge_capacity, fct.grown_capacity(need))
    return insert_edges(g, batch, cap, optimized)


def delete_edges_host(g: FlatGraph, edges: np.ndarray) -> FlatGraph:
    batch = batch_from_edges(edges)
    return delete_edges(g, batch, g.edge_capacity)
