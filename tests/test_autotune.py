"""Kernel block-shape autotuner (kernels/autotune.py, DESIGN.md §12):
winner-cache hit/miss semantics, sweep determinism under a pinned
candidate grid, the consult-once-per-shape-bucket contract dispatch
relies on, and the opt-in on-disk table."""
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import autotune  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_table(monkeypatch):
    """Every test starts from an empty memo, the built-in candidate
    grids, and no disk table / forced sweeping."""
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    autotune.reset()
    autotune.set_candidates(None)
    yield
    autotune.reset()
    autotune.set_candidates(None)


# -- cache key ---------------------------------------------------------------


def test_bucket_rounds_up_to_power_of_two():
    assert [autotune._bucket(x) for x in (1, 2, 3, 1000, 1024, 1025)] == [
        1, 2, 4, 1024, 1024, 2048,
    ]


def test_cache_key_buckets_shapes_together():
    a = autotune.cache_key("segment_sum", "cpu", {"E": 900, "n": 500})
    b = autotune.cache_key("segment_sum", "cpu", {"E": 1024, "n": 512})
    c = autotune.cache_key("segment_sum", "cpu", {"E": 1025, "n": 512})
    assert a == b != c
    assert a[0] == autotune.TABLE_VERSION
    # backend is part of the key: a TPU winner never leaks onto CPU
    assert a != autotune.cache_key("segment_sum", "tpu", {"E": 900, "n": 500})


# -- memo hit/miss -----------------------------------------------------------


def test_winner_cache_miss_then_hit():
    shape = {"E": 4096, "n": 512}
    p1 = autotune.get_params("segment_sum", shape, backend="cpu")
    key = autotune.cache_key("segment_sum", "cpu", shape)
    assert autotune.CONSULTS[key] == 1  # cold consult
    p2 = autotune.get_params("segment_sum", shape, backend="cpu")
    assert p2 == p1
    assert autotune.CONSULTS[key] == 1  # memo hit: no second consult
    # a different bucket is a different entry -> one more cold consult
    autotune.get_params("segment_sum", {"E": 9000, "n": 512}, backend="cpu")
    assert sum(autotune.CONSULTS.values()) == 2


def test_defaults_when_sweeping_disabled():
    # CPU without REPRO_AUTOTUNE=1: sweep_fn must NOT be invoked
    def boom(params):  # pragma: no cover - the point is it never runs
        raise AssertionError("sweep ran with sweeping disabled")

    p = autotune.get_params(
        "segment_sum_chunked", {"R": 64, "n": 256}, sweep_fn=boom, backend="cpu"
    )
    assert p == autotune.DEFAULTS["segment_sum_chunked"]


# -- sweep -------------------------------------------------------------------


def test_sweep_determinism_under_pinned_grid(monkeypatch):
    """With a single-candidate grid the sweep must return that candidate,
    every time, and the veto path must fall through to the survivor."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    pinned = {"edge_block": 256, "dst_block": 128}
    autotune.set_candidates({"segment_sum": [pinned]})
    calls = []

    def make(params):
        calls.append(dict(params))
        return lambda: jnp.zeros(())

    for _ in range(2):
        autotune.reset()
        p = autotune.get_params(
            "segment_sum", {"E": 2048, "n": 256}, sweep_fn=make, backend="cpu"
        )
        assert p == pinned
    assert calls == [pinned, pinned]  # exactly one candidate per sweep


def test_sweep_vetoes_infeasible_candidates(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    good = {"edge_block": 512, "dst_block": 128}
    autotune.set_candidates(
        {"segment_sum": [{"edge_block": 99999, "dst_block": 128}, good]}
    )

    def make(params):
        if params["edge_block"] > 2048:
            raise ValueError("block larger than problem")
        return lambda: jnp.zeros(())

    p = autotune.get_params(
        "segment_sum", {"E": 2048, "n": 256}, sweep_fn=make, backend="cpu"
    )
    assert p == good


def test_sweep_all_vetoed_falls_back_to_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    autotune.set_candidates({"segment_sum": [{"edge_block": 1, "dst_block": 1}]})

    def make(params):
        raise ValueError("nope")

    # a sweep that finds no working candidate is an error, not a silent
    # return of the defaults; the last candidate error is chained
    with pytest.raises(RuntimeError, match="every candidate") as info:
        autotune.get_params(
            "segment_sum", {"E": 128, "n": 64}, sweep_fn=make, backend="cpu"
        )
    assert isinstance(info.value.__cause__, ValueError)
    assert autotune._memo == {}  # nothing recorded


# -- on-disk table -----------------------------------------------------------


def test_disk_table_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    pinned = {"edge_block": 1024, "dst_block": 256}
    autotune.set_candidates({"segment_sum": [pinned]})
    shape = {"E": 4096, "n": 1024}
    p = autotune.get_params(
        "segment_sum", shape, sweep_fn=lambda _: (lambda: jnp.zeros(())),
        backend="cpu",
    )
    assert p == pinned
    table = json.loads(path.read_text())
    key_s = autotune._key_str(autotune.cache_key("segment_sum", "cpu", shape))
    assert table[key_s] == pinned
    # a fresh process (reset memo) reads the winner back WITHOUT sweeping
    autotune.reset()
    autotune.set_candidates({"segment_sum": []})  # sweep would return defaults
    p2 = autotune.get_params("segment_sum", shape, backend="cpu")
    assert p2 == pinned


def test_no_disk_writes_without_env(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    autotune.get_params("segment_sum", {"E": 256, "n": 64}, backend="cpu")
    assert list(tmp_path.iterdir()) == []  # table is process-local only


def test_corrupt_disk_table_is_empty_table(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    path.write_text("{not json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    p = autotune.get_params("segment_sum", {"E": 256, "n": 64}, backend="cpu")
    assert p == autotune.DEFAULTS["segment_sum"]


# -- dispatch integration ----------------------------------------------------


def test_dispatch_consults_once_per_shape_bucket():
    """ops.segment_sum with default blocks consults the table exactly
    once per (kernel, backend, bucket) — repeated dispatches are memo
    hits, a new bucket is one more cold consult."""
    autotune.reset()
    rng = np.random.default_rng(0)

    def run(E, n):
        dst = jnp.asarray(np.sort(rng.integers(0, n, E)), jnp.int32)
        msg = jnp.ones((E, 4), jnp.float32)
        return np.asarray(kops.segment_sum(dst, msg, n))

    run(1000, 256)
    seg_keys = [k for k in autotune.CONSULTS if k[1] == "segment_sum"]
    assert len(seg_keys) == 1 and autotune.CONSULTS[seg_keys[0]] == 1
    run(1000, 256)  # same bucket: still exactly one cold consult
    run(990, 250)   # same bucket after pow2 rounding: still one
    assert sum(v for k, v in autotune.CONSULTS.items() if k[1] == "segment_sum") == 1
    run(5000, 256)  # E buckets to 8192 != 1024: second cold consult
    assert sum(v for k, v in autotune.CONSULTS.items() if k[1] == "segment_sum") == 2


def test_dispatch_result_matches_explicit_blocks():
    rng = np.random.default_rng(1)
    E, n = 2000, 300
    dst = jnp.asarray(np.sort(rng.integers(0, n, E)), jnp.int32)
    msg = jnp.asarray(rng.standard_normal((E, 4)), jnp.float32)
    auto = np.asarray(kops.segment_sum(dst, msg, n))
    manual = np.asarray(kops.segment_sum(dst, msg, n, edge_block=512, dst_block=128))
    np.testing.assert_allclose(auto, manual, rtol=1e-6)
