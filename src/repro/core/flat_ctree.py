"""TPU-native flat C-tree (the hardware adaptation of core/ctree.py).

A pointer treap is hostile to TPUs (no pointers under jit, dynamic shapes,
serial chasing).  The C-tree's *insight* — hash-canonical chunk boundaries
over a sorted pool — survives intact in flat form:

  data[capacity] : sorted element pool (padding = SENTINEL at the top)
  n              : valid-count scalar
  heads          : DERIVED, is_head(data) — never stored, recomputed by one
                   hash pass on the VPU (headness is canonical, paper §3.1)

All operations are fixed-shape jax ops: ``find`` is a searchsorted;
``union`` is either a concat-sort (baseline) or an O(n+k) rank-merge
(optimized; two searchsorteds + scatter — the TPU analogue of the paper's
leaf-level chunk merge); ``difference``/``intersect`` are membership masks
+ compaction.  Chunk compression (fixed-width packed deltas, the vbyte
adaptation) lives in ``chunks.pack_deltas`` for storage accounting and
``kernels/delta_decode`` for the on-device decode.

Capacity is static per jit trace; the host quantizes capacities to powers
of two so recompiles are O(log max_n) over a stream's lifetime.

Equivalence with the faithful C-tree (same elements, same heads, same
chunk boundaries) is property-tested in tests/test_flat_ctree.py.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hash import is_head_jnp

SENTINEL32 = np.int32(np.iinfo(np.int32).max)
SENTINEL64 = np.int64(np.iinfo(np.int64).max)


def sentinel_for(dtype) -> int:
    return int(np.iinfo(np.dtype(dtype)).max)


class FlatCTree(NamedTuple):
    """Flat sorted pool with a valid count; a jax pytree (shardable).

    ``vals`` optionally carries ONE associated value per element (the
    PaC-tree key->value generalization): ``vals[i]`` belongs to
    ``data[i]`` and is permuted by every merge / compaction alongside
    its key.  ``vals is None`` is the plain-set layout — no value array
    is allocated and every operation traces exactly as before (the
    weighted branches below are Python-level, decided at trace time).

    Value semantics across set operations:
      * union (merge or sort): a batch element whose key already exists
        OVERWRITES the pool element's value (last-writer-wins per
        batch); within one batch the FIRST occurrence of a duplicate
        key wins (``from_array`` / ``from_device`` dedup keep-first).
      * difference: dropping a key drops its value.
    """

    data: jax.Array  # [capacity] sorted; data[n:] == SENTINEL
    n: jax.Array  # int32 scalar
    vals: jax.Array | None = None  # [capacity] associated values (pad 0)


def capacity(t: FlatCTree) -> int:
    return t.data.shape[0]


def empty(cap: int, dtype=jnp.int32) -> FlatCTree:
    return FlatCTree(
        jnp.full((cap,), sentinel_for(dtype), dtype=dtype), jnp.int32(0)
    )


def from_array(
    values: np.ndarray,
    cap: int | None = None,
    dtype=jnp.int32,
    vals: np.ndarray | None = None,
    val_dtype=jnp.float32,
) -> FlatCTree:
    """Host-side build: sort+dedup then pad to capacity.  ``vals``
    optionally attaches one value per element (duplicate keys keep the
    FIRST occurrence's value)."""
    raw = np.asarray(values)
    if vals is None:
        v = np.unique(raw)
        w = None
    else:
        v, first = np.unique(raw, return_index=True)
        w = np.asarray(vals, dtype=np.dtype(val_dtype)).reshape(-1)[first]
    if cap is None:
        cap = max(8, int(2 ** np.ceil(np.log2(max(v.size, 1) + 1))))
    assert v.size <= cap
    data = np.full(cap, sentinel_for(dtype), dtype=np.dtype(dtype))
    data[: v.size] = v
    if w is None:
        return FlatCTree(jnp.asarray(data), jnp.int32(v.size))
    wdata = np.zeros(cap, dtype=np.dtype(val_dtype))
    wdata[: v.size] = w
    return FlatCTree(jnp.asarray(data), jnp.int32(v.size), jnp.asarray(wdata))


def to_array(t: FlatCTree) -> np.ndarray:
    d = np.asarray(t.data)
    return d[: int(t.n)]


def to_val_array(t: FlatCTree) -> np.ndarray | None:
    """The valid prefix of the value array (None on plain sets)."""
    return None if t.vals is None else np.asarray(t.vals)[: int(t.n)]


@functools.partial(jax.jit, static_argnums=(1,))
def from_device(values: jax.Array, cap: int, vals: jax.Array | None = None) -> FlatCTree:
    """Device-side build: sort + dedup + compact, all under jit.

    ``values`` is a dense device array of raw (possibly duplicated,
    unsorted) elements; sentinel-valued slots are dropped, so a caller
    may pre-pad to a quantized shape.  The host never touches the data —
    this is the streaming ingest path (batches arrive device-resident
    and stay there).  ``vals`` rides along through a stable argsort, so
    the first occurrence of a duplicate key keeps its value (matching
    ``from_array``).  Its operations carry the device scope
    ``merge.batch`` (the write path's batch sort and dedup)."""
    with jax.named_scope("merge.batch"):
        if vals is None:
            v = jnp.sort(values.ravel())
            keep = _dedup_mask(v, jnp.int32(v.shape[0]))
            return _compact(v, keep, cap)
        order = jnp.argsort(values.ravel(), stable=True)
        v = values.ravel()[order]
        keep = _dedup_mask(v, jnp.int32(v.shape[0]))
        return _compact(v, keep, cap, vals=vals.ravel()[order])


# ---------------------------------------------------------------------------
# membership / find
# ---------------------------------------------------------------------------


@jax.jit
def member(t: FlatCTree, queries: jax.Array) -> jax.Array:
    """Vectorized Find: bool per query (padding-safe)."""
    idx = jnp.searchsorted(t.data, queries)
    idx = jnp.minimum(idx, t.data.shape[0] - 1)
    return (t.data[idx] == queries) & (queries != sentinel_for(t.data.dtype))


def find(t: FlatCTree, e: int) -> bool:
    return bool(member(t, jnp.asarray([e], dtype=t.data.dtype))[0])


# ---------------------------------------------------------------------------
# head / chunk structure (canonical, derived)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2))
def head_mask(t: FlatCTree, b: int, seed: int) -> jax.Array:
    """is_head over valid elements (the one-pass VPU re-chunk)."""
    valid = jnp.arange(t.data.shape[0]) < t.n
    return is_head_jnp(t.data.astype(jnp.uint32), b, seed) & valid


@functools.partial(jax.jit, static_argnums=(1, 2))
def chunk_ids(t: FlatCTree, b: int, seed: int) -> jax.Array:
    """chunk id per slot; prefix = 0, tail of i-th head = i+1."""
    return jnp.cumsum(head_mask(t, b, seed), dtype=jnp.int32)


def num_heads(t: FlatCTree, b: int, seed: int) -> int:
    return int(head_mask(t, b, seed).sum())


# ---------------------------------------------------------------------------
# batch union: baseline (sort) and optimized (rank-merge)
# ---------------------------------------------------------------------------


def _dedup_mask(sorted_data: jax.Array, n_total: jax.Array) -> jax.Array:
    keep = jnp.ones(sorted_data.shape, dtype=bool)
    keep = keep.at[1:].set(sorted_data[1:] != sorted_data[:-1])
    keep &= jnp.arange(sorted_data.shape[0]) < n_total
    keep &= sorted_data != sentinel_for(sorted_data.dtype)
    return keep


def _compact(
    values: jax.Array, keep: jax.Array, out_cap: int, vals: jax.Array | None = None
) -> FlatCTree:
    """Scatter kept values to the front of a fresh pool (associated
    values, when present, ride the same permutation)."""
    sent = sentinel_for(values.dtype)
    pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
    pos = jnp.where(keep, pos, out_cap)  # dropped via OOB
    out = jnp.full((out_cap,), sent, dtype=values.dtype)
    out = out.at[pos].set(values, mode="drop")
    n_out = keep.sum().astype(jnp.int32)
    if vals is None:
        return FlatCTree(out, n_out)
    vout = jnp.zeros((out_cap,), dtype=vals.dtype).at[pos].set(vals, mode="drop")
    return FlatCTree(out, n_out, vout)


def _aligned_vals(t: FlatCTree, batch: FlatCTree):
    """(vals_a, vals_b) for a union, or (None, None) when both inputs
    are plain sets.  A mixed union is upgraded at trace time: the
    value-less side is materialized as unit weights (the streaming
    auto-upgrade — an unweighted pool receiving its first weighted
    batch, or a weighted pool receiving a weight-less batch)."""
    if t.vals is None and batch.vals is None:
        return None, None
    va = t.vals if t.vals is not None else jnp.ones(t.data.shape[0], batch.vals.dtype)
    vb = batch.vals if batch.vals is not None else jnp.ones(
        batch.data.shape[0], t.vals.dtype
    )
    return va, vb


@functools.partial(jax.jit, static_argnums=(2,))
def union_sort(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    """Baseline MultiInsert: concat + sort + dedup + compact.

    O((n+k) log(n+k)) compares; one XLA sort. The paper-faithful analogue
    of rebuilding; kept as the reference and the §Perf 'before'.

    With associated values the sort becomes a stable argsort so values
    ride the permutation; a duplicated key keeps the BATCH value (the
    pool copy sorts first, and each kept slot reads the last value of
    its equal-run — runs are length <= 2 since both inputs are deduped).
    """
    va, vb = _aligned_vals(t, batch)
    if va is None:
        allv = jnp.sort(jnp.concatenate([t.data, batch.data]))
        keep = _dedup_mask(allv, t.n + batch.n)
        return _compact(allv, keep, out_cap)
    allk = jnp.concatenate([t.data, batch.data])
    order = jnp.argsort(allk, stable=True)
    allv = allk[order]
    vals = jnp.concatenate([va, vb])[order]
    keep = _dedup_mask(allv, t.n + batch.n)
    nxt_same = jnp.concatenate(
        [allv[1:] == allv[:-1], jnp.zeros((1,), dtype=bool)]
    )
    vals = jnp.where(nxt_same, jnp.roll(vals, -1), vals)  # batch overwrites
    return _compact(allv, keep, out_cap, vals=vals)


@functools.partial(jax.jit, static_argnums=(2,))
def union_merge(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    """Optimized MultiInsert: O(n+k) rank-merge.

    Output position of a-element = own index + #unique-b-elements below it;
    of a kept b-element = #a-below + #kept-b-below.  Two searchsorteds and
    one scatter — bandwidth-bound, no sort network.  This mirrors the
    paper's Union leaf case (merge two chunks) applied to the whole pool.
    Device scopes: ``merge.rank`` (the searchsorteds and prefix sums
    that place every element) and ``merge.scatter`` (the writes).
    """
    a, b = t.data, batch.data
    sent = sentinel_for(a.dtype)
    ca, cb = a.shape[0], b.shape[0]
    with jax.named_scope("merge.rank"):
        valid_a = jnp.arange(ca) < t.n
        valid_b = jnp.arange(cb) < batch.n

        # which b are duplicates of an a element?
        ia = jnp.minimum(jnp.searchsorted(a, b), ca - 1)
        dup_b = (a[ia] == b) & valid_b
        keep_b = valid_b & ~dup_b
        kb_excl = jnp.cumsum(keep_b, dtype=jnp.int32) - keep_b  # exclusive prefix

        # positions
        ra = jnp.searchsorted(b, a)  # #b-entries < a[i] (valid b only: pad=max)
        kept_below_a = jnp.where(ra > 0, kb_excl[jnp.minimum(ra - 1, cb - 1)] +
                                 keep_b[jnp.minimum(ra - 1, cb - 1)], 0)
        pos_a = jnp.arange(ca, dtype=jnp.int32) + kept_below_a.astype(jnp.int32)
        pos_a = jnp.where(valid_a, pos_a, out_cap)

        rb = jnp.searchsorted(a, b)  # #a < b[j]
        pos_b = rb.astype(jnp.int32) + kb_excl.astype(jnp.int32)
        pos_b = jnp.where(keep_b, pos_b, out_cap)

    with jax.named_scope("merge.scatter"):
        out = jnp.full((out_cap,), sent, dtype=a.dtype)
        out = out.at[pos_a].set(a, mode="drop")
        out = out.at[pos_b].set(b, mode="drop")
        n_out = (t.n + keep_b.sum()).astype(jnp.int32)
        va, vb = _aligned_vals(t, batch)
        if va is None:
            return FlatCTree(out, n_out)
        # values ride the same two scatters; a duplicate b key lands its
        # value on the matched a slot (insert overwrites, PaC-tree style)
        vout = jnp.zeros((out_cap,), dtype=va.dtype)
        vout = vout.at[pos_a].set(va, mode="drop")
        vout = vout.at[pos_b].set(vb, mode="drop")
        pos_dup = jnp.where(dup_b, pos_a[ia], out_cap)
        vout = vout.at[pos_dup].set(vb, mode="drop")
        return FlatCTree(out, n_out, vout)


@functools.partial(jax.jit, static_argnums=(2,))
def difference(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    """MultiDelete: drop elements of t found in batch; compact (a
    dropped key drops its associated value).  Device scopes:
    ``merge.rank`` (the membership search) and ``merge.scatter`` (the
    compaction)."""
    with jax.named_scope("merge.rank"):
        drop = member(batch, t.data)
        valid = jnp.arange(t.data.shape[0]) < t.n
    with jax.named_scope("merge.scatter"):
        return _compact(t.data, valid & ~drop, out_cap, vals=t.vals)


@functools.partial(jax.jit, static_argnums=(2,))
def intersect(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    keep = member(batch, t.data) & (jnp.arange(t.data.shape[0]) < t.n)
    return _compact(t.data, keep, out_cap, vals=t.vals)


# ---------------------------------------------------------------------------
# host-side capacity policy
# ---------------------------------------------------------------------------


def grown_capacity(n_needed: int) -> int:
    """Power-of-two quantization: bounds jit recompiles to O(log max_n)."""
    return max(8, int(2 ** np.ceil(np.log2(n_needed + 1))))


def multi_insert(
    t: FlatCTree,
    values: np.ndarray,
    optimized: bool = True,
    vals: np.ndarray | None = None,
) -> FlatCTree:
    """Host-driven batch insert: build batch, pick capacity, run union."""
    batch = from_array(values, dtype=t.data.dtype, vals=vals)
    need = int(t.n) + int(batch.n)
    cap = max(capacity(t), grown_capacity(need))
    fn = union_merge if optimized else union_sort
    return fn(t, batch, cap)


def multi_delete(t: FlatCTree, values: np.ndarray) -> FlatCTree:
    batch = from_array(values, dtype=t.data.dtype)
    return difference(t, batch, capacity(t))
