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
(optimized — the TPU analogue of the paper's leaf-level chunk merge);
``difference`` is its mirror image, ``intersect`` a membership mask +
compaction.  The rank-merge searches only the k batch rows (one binary
search each), turns their ranks into per-slot shifts of the pool (a
k-row histogram and one prefix sum), moves the pool slots by their
shifts in log2(k)+1 rounds of a static shift and a select, and writes
the batch rows with one k-row scatter: the pool is only ever streamed,
never searched, gathered or scattered at its own length.  Chunk
compression (fixed-width packed deltas, the vbyte adaptation) lives in
``chunks.pack_deltas`` for storage accounting and
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


def _fit(x: jax.Array, length: int, fill) -> jax.Array:
    """``x`` cut or padded with ``fill`` to a static length."""
    if length <= x.shape[0]:
        return x[:length]
    return jnp.concatenate([x, jnp.full((length - x.shape[0],), fill, x.dtype)])


def _shift(x: jax.Array, d: int, fill) -> jax.Array:
    """Static shift by ``d`` slots, right for ``d > 0`` and left for
    ``d < 0``: ``y[j] = x[j - d]``, and the slots shifted in hold
    ``fill``."""
    pad = jnp.full((abs(d),), fill, x.dtype)
    return jnp.concatenate([pad, x[:-d]]) if d > 0 else jnp.concatenate([x[-d:], pad])


def _place(
    s: jax.Array, lanes: Tuple[jax.Array, ...], max_shift: int, right: bool
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Move each slot ``j`` with ``s[j] >= 0`` by ``s[j]`` slots (right or
    left); ``s[j] == -1`` marks an empty slot.  One round per bit of
    ``max_shift``, each a static shift by 2^k and a select over every
    lane.  Right shifts that are non-decreasing over the occupied slots
    take the high bit first, left shifts that are non-decreasing take
    the low bit first: either way the partial positions stay strictly
    increasing, so no two slots ever meet.  A slot whose target lies
    outside the ``L`` slots drops, as in a scatter with ``mode="drop"``.
    Returns the shifts at the targets (-1 where empty) and the moved
    lanes (stale where empty)."""
    L = s.shape[0]
    j = jnp.arange(L, dtype=s.dtype)
    target = j + s if right else j - s
    s = jnp.where((target >= 0) & (target < L), s, -1)
    bits = [1 << k for k in range(max_shift.bit_length()) if (1 << k) < L]
    for d in (reversed(bits) if right else bits):
        mv = (s >= 0) & ((s & d) != 0)
        dd = d if right else -d
        arrive = _shift(mv, dd, False)
        s = jnp.where(arrive, _shift(s, dd, -1), jnp.where(mv, -1, s))
        lanes = tuple(jnp.where(arrive, _shift(x, dd, 0), x) for x in lanes)
    return s, lanes


def merge_ranked(
    t: FlatCTree, batch: FlatCTree, out_cap: int
) -> Tuple[FlatCTree, jax.Array]:
    """``union_merge`` plus the batch rows it kept (valid and not
    already in ``t``), from which ``flat_graph`` updates CSR offsets."""
    a, b = t.data, batch.data
    sent = sentinel_for(a.dtype)
    ca, cb = a.shape[0], b.shape[0]
    va, vb = _aligned_vals(t, batch)
    with jax.named_scope("merge.rank"):
        # one binary search per batch row: #a < b[j], and whether b[j]
        # duplicates the a element it lands on
        rb = jnp.searchsorted(a, b).astype(jnp.int32)
        ia = jnp.minimum(rb, ca - 1)
        valid_b = jnp.arange(cb) < batch.n
        dup_b = (a[ia] == b) & valid_b
        keep_b = valid_b & ~dup_b
        kb_excl = jnp.cumsum(keep_b, dtype=jnp.int32) - keep_b  # exclusive prefix
        pos_b = jnp.where(keep_b, rb + kb_excl, out_cap)
        # a[i] moves right by the number of kept b below it: a histogram
        # of the kept rows' ranks, then one prefix sum over the pool
        hist = jnp.zeros((ca,), jnp.int32).at[jnp.where(keep_b, rb, ca)].add(
            1, mode="drop"
        )
        s = jnp.where(jnp.arange(ca) < t.n, jnp.cumsum(hist, dtype=jnp.int32), -1)

    with jax.named_scope("merge.scatter"):
        lanes = (_fit(a, out_cap, sent),)
        if va is not None:
            # a duplicate b key overwrites its matched a slot's value
            # (insert overwrites, PaC-tree style) before the move
            va = va.at[jnp.where(dup_b, ia, ca)].set(vb, mode="drop")
            lanes += (_fit(va, out_cap, 0),)
        s, lanes = _place(_fit(s, out_cap, -1), lanes, cb, right=True)
        occupied = s >= 0
        out = jnp.where(occupied, lanes[0], sent).at[pos_b].set(b, mode="drop")
        n_out = (t.n + keep_b.sum()).astype(jnp.int32)
        if va is None:
            return FlatCTree(out, n_out), keep_b
        vout = jnp.where(occupied, lanes[1], 0)
        vout = vout.at[pos_b].set(vb, mode="drop")
        return FlatCTree(out, n_out, vout), keep_b


@functools.partial(jax.jit, static_argnums=(2,))
def union_merge(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    """Optimized MultiInsert: O(n+k) rank-merge by shifts.

    Output position of a-element = own index + #kept-b-elements below it;
    of a kept b-element = #a-below + #kept-b-below.  The batch rows are
    ranked by one binary search each (k searches, never one per pool
    slot); the pool's shifts are a k-row histogram of those ranks and
    one prefix sum over the pool.  The pool slots then move by their
    shifts in log2(k)+1 rounds of a static shift and a select, and the
    kept batch rows land in the gaps with one k-row scatter: every pass
    over the pool streams, with no pool-length search, gather or
    scatter.  This mirrors the paper's Union leaf case (merge two
    chunks) applied to the whole pool.  Device scopes: ``merge.rank``
    (the batch search and the shift counts) and ``merge.scatter`` (the
    moves and the batch rows' write).
    """
    return merge_ranked(t, batch, out_cap)[0]


def difference_ranked(
    t: FlatCTree, batch: FlatCTree, out_cap: int
) -> Tuple[FlatCTree, jax.Array]:
    """``difference`` plus the batch rows it found in ``t``, from which
    ``flat_graph`` updates CSR offsets."""
    a, b = t.data, batch.data
    sent = sentinel_for(a.dtype)
    ca, cb = a.shape[0], b.shape[0]
    with jax.named_scope("merge.rank"):
        hit = jnp.searchsorted(a, b).astype(jnp.int32)
        found = (a[jnp.minimum(hit, ca - 1)] == b) & (hit < t.n)
        drop = jnp.zeros((ca,), bool).at[jnp.where(found, hit, ca)].set(
            True, mode="drop"
        )
        # a kept a[i] moves left by the number of dropped slots below it
        keep = (jnp.arange(ca) < t.n) & ~drop
        s = jnp.where(keep, jnp.cumsum(drop, dtype=jnp.int32), -1)

    with jax.named_scope("merge.scatter"):
        lanes = (a,) if t.vals is None else (a, t.vals)
        s, lanes = _place(s, lanes, cb, right=False)
        occupied = _fit(s, out_cap, -1) >= 0
        out = jnp.where(occupied, _fit(lanes[0], out_cap, sent), sent)
        n_out = keep.sum().astype(jnp.int32)
        if t.vals is None:
            return FlatCTree(out, n_out), found
        vout = jnp.where(occupied, _fit(lanes[1], out_cap, 0), 0)
        return FlatCTree(out, n_out, vout), found


@functools.partial(jax.jit, static_argnums=(2,))
def difference(t: FlatCTree, batch: FlatCTree, out_cap: int) -> FlatCTree:
    """MultiDelete: drop elements of t found in batch; compact (a
    dropped key drops its associated value).  One binary search per
    batch row finds the dropped slots; the kept slots move left by the
    dropped count below them (one prefix sum over the pool) in
    log2(k)+1 rounds of a static shift and a select.  Device scopes:
    ``merge.rank`` (the batch search and the shift counts) and
    ``merge.scatter`` (the compaction)."""
    return difference_ranked(t, batch, out_cap)[0]


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
