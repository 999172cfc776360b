"""Flat (TPU-native) C-tree vs numpy oracles and the faithful C-tree."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ctree as ct
from repro.core import flat_ctree as fct
from repro.core import pam
from repro.core.hash import is_head_np

from proptest import given, st


def sets(max_value=1 << 20, max_size=300):
    return st.lists(
        st.integers(min_value=0, max_value=max_value), min_size=0, max_size=max_size
    )


@given(sets())
def test_from_to_array(xs):
    v = np.unique(np.asarray(xs, dtype=np.int64)).astype(np.int32)
    t = fct.from_array(v)
    np.testing.assert_array_equal(fct.to_array(t), v)


@given(sets(max_size=120), sets(max_size=120))
def test_member(a, q):
    va = np.unique(np.asarray(a, dtype=np.int64)).astype(np.int32)
    vq = np.asarray(sorted(set(q)), dtype=np.int32)
    t = fct.from_array(va)
    if vq.size == 0:
        return
    got = np.asarray(fct.member(t, jnp.asarray(vq)))
    np.testing.assert_array_equal(got, np.isin(vq, va))


@given(sets(max_size=200), sets(max_size=200), st.booleans())
def test_union_matches_oracle(a, b, optimized):
    va = np.unique(np.asarray(a, dtype=np.int64)).astype(np.int32)
    vb = np.unique(np.asarray(b, dtype=np.int64)).astype(np.int32)
    ta, tb = fct.from_array(va), fct.from_array(vb)
    cap = fct.grown_capacity(va.size + vb.size)
    fn = fct.union_merge if optimized else fct.union_sort
    out = fn(ta, tb, cap)
    np.testing.assert_array_equal(fct.to_array(out), np.union1d(va, vb))
    # padding intact
    assert (np.asarray(out.data)[int(out.n):] == fct.sentinel_for(out.data.dtype)).all()


@given(sets(max_size=200), sets(max_size=200))
def test_union_merge_equals_union_sort(a, b):
    va = np.unique(np.asarray(a, dtype=np.int64)).astype(np.int32)
    vb = np.unique(np.asarray(b, dtype=np.int64)).astype(np.int32)
    ta, tb = fct.from_array(va), fct.from_array(vb)
    cap = fct.grown_capacity(va.size + vb.size)
    s = fct.union_sort(ta, tb, cap)
    m = fct.union_merge(ta, tb, cap)
    np.testing.assert_array_equal(np.asarray(s.data), np.asarray(m.data))
    assert int(s.n) == int(m.n)


@given(sets(max_size=200), sets(max_size=200))
def test_difference_intersect(a, b):
    va = np.unique(np.asarray(a, dtype=np.int64)).astype(np.int32)
    vb = np.unique(np.asarray(b, dtype=np.int64)).astype(np.int32)
    ta, tb = fct.from_array(va), fct.from_array(vb)
    d = fct.difference(ta, tb, fct.capacity(ta))
    np.testing.assert_array_equal(fct.to_array(d), np.setdiff1d(va, vb))
    i = fct.intersect(ta, tb, fct.capacity(ta))
    np.testing.assert_array_equal(fct.to_array(i), np.intersect1d(va, vb))


def test_multi_insert_delete_host_api():
    rng = np.random.default_rng(0)
    t = fct.from_array(rng.integers(0, 1 << 20, 1000).astype(np.int32))
    base = fct.to_array(t).copy()
    batch = rng.integers(0, 1 << 20, 500).astype(np.int32)
    t2 = fct.multi_insert(t, batch)
    np.testing.assert_array_equal(fct.to_array(t2), np.union1d(base, batch))
    t3 = fct.multi_delete(t2, batch)
    np.testing.assert_array_equal(fct.to_array(t3), np.setdiff1d(np.union1d(base, batch), batch))
    # persistence: t unchanged (immutability of jax arrays)
    np.testing.assert_array_equal(fct.to_array(t), base)


def test_flat_heads_agree_with_faithful_ctree():
    """The two levels chunk identically: same head set, same chunk sizes."""
    rng = np.random.default_rng(1)
    v = np.unique(rng.integers(0, 1 << 20, 5000)).astype(np.int32)
    b, seed = 64, ct.DEFAULT_SEED
    flat = fct.from_array(v)
    hm = np.asarray(fct.head_mask(flat, b, seed))[: v.size]
    np.testing.assert_array_equal(hm, is_head_np(v.astype(np.int64), b, np.uint32(seed)))
    faithful = ct.build(v.astype(np.int64), b=b, seed=seed)
    heads_faithful = [k for k, _ in pam.TreeModule().iter_entries(faithful.tree)] if faithful.tree else []
    np.testing.assert_array_equal(v[hm], np.asarray(heads_faithful, dtype=np.int32))


def test_capacity_growth_policy():
    assert fct.grown_capacity(0) == 8
    assert fct.grown_capacity(8) == 16
    assert fct.grown_capacity(1000) == 1024
    # powers of two quantize recompiles
    caps = {fct.grown_capacity(n) for n in range(1, 10000)}
    assert len(caps) <= 12


# Edge cases of the rank-and-shift merge, each checked against union_sort
# and a numpy reference.  A case: pool keys and capacity, batch keys and
# capacity, the output capacity, and which side carries weights.
_P = (np.arange(1, 21, dtype=np.int64) * 7) << 20  # 20 packed-size keys
_MERGE_CASES = {
    "empty_batch": (_P, 32, _P[:0], 8, 32, "both"),
    "batch_all_duplicates": (_P, 32, _P[::3], 8, 32, "both"),
    "absent_keys": (_P, 32, _P + 1, 32, 64, "pool"),
    "batch_equals_pool": (_P, 32, _P, 32, 32, "both"),
    "full_batch_all_new": (_P, 32, np.arange(8), 8, 32, "both"),
    "full_batch_all_found": (_P, 32, _P[4:12], 8, 32, "pool"),
    "pool_at_capacity": (_P[:16], 16, np.array([0, 5 << 20, 1 << 40]), 8, 32, "both"),
    "batch_cap_above_pool_cap": (_P[:5], 8, _P[3:] + 3, 64, 64, "batch"),
    "out_cap_above_pool_cap": (_P[:10], 16, _P[::2] - 1, 16, 128, "none"),
    "out_cap_below_result": (_P, 32, _P[:12] + 5, 16, 16, "both"),
    "unweighted": (_P, 32, np.arange(0, 300 << 20, 9 << 20), 64, 64, "none"),
    "weighted": (_P, 32, np.arange(0, 300 << 20, 9 << 20), 64, 64, "both"),
    "mixed_unweighted_pool": (_P, 32, _P[5:] - 7, 32, 64, "batch"),
    "mixed_unweighted_batch": (_P, 32, _P[5:] - 7, 32, 64, "pool"),
}


def _tree(keys, cap, weighted, seed):
    w = np.random.default_rng(seed).random(keys.size).astype(np.float32) + 2.0
    return fct.from_array(keys, cap=cap, dtype=jnp.int64, vals=w if weighted else None)


def _assert_tree(t, keys, weights):
    cap = fct.capacity(t)
    assert int(t.n) == keys.size
    n = min(keys.size, cap)  # an overflowing result keeps its first cap keys
    data = np.asarray(t.data)
    np.testing.assert_array_equal(data[:n], keys[:n])
    assert (data[n:] == fct.sentinel_for(data.dtype)).all()
    if weights is None:
        assert t.vals is None
        return
    vals = np.asarray(t.vals)
    assert vals.shape == (cap,)
    np.testing.assert_array_equal(vals[:n], weights[:n])
    assert (vals[n:] == 0).all()


@pytest.mark.parametrize("case", sorted(_MERGE_CASES))
def test_merge_and_difference_edge_cases(case):
    a, cap_a, b, cap_b, out_cap, weights = _MERGE_CASES[case]
    ta = _tree(a, cap_a, weights in ("both", "pool"), 1)
    tb = _tree(b, cap_b, weights in ("both", "batch"), 2)
    wa = np.ones(a.size, np.float32) if ta.vals is None else fct.to_val_array(ta)
    wb = np.ones(b.size, np.float32) if tb.vals is None else fct.to_val_array(tb)

    # union: the batch's weight wins on a shared key; a side without
    # weights counts as unit weights once the other side has them
    keys = np.union1d(a, b)
    by_key = dict(zip(a.tolist(), wa.tolist())) | dict(zip(b.tolist(), wb.tolist()))
    w_union = None if weights == "none" else np.array([by_key[k] for k in keys.tolist()], np.float32)
    merged = fct.union_merge(ta, tb, out_cap)
    _assert_tree(merged, keys, w_union)
    baseline = fct.union_sort(ta, tb, out_cap)
    np.testing.assert_array_equal(np.asarray(merged.data), np.asarray(baseline.data))
    assert int(merged.n) == int(baseline.n)
    if w_union is not None:
        np.testing.assert_array_equal(np.asarray(merged.vals), np.asarray(baseline.vals))

    # difference: a dropped key drops its weight; the batch's weights
    # play no part
    kept = ~np.isin(a, b)
    w_diff = None if ta.vals is None else wa[kept]
    _assert_tree(fct.difference(ta, tb, out_cap), a[kept], w_diff)
