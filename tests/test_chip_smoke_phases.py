"""chip_smoke.py's phases on the CPU at a tiny scale, and the guards that
keep the device from being hidden: no interpret mode off CPU/TPU, no
autotune sweep under a trace, counted promotion failures, and a compile
cache placed from outside."""
import os
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core.streaming import AspenStream  # noqa: E402
from repro.data.rmat import rmat_edges, symmetrize  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve.graph import GraphQueryService  # noqa: E402
from repro.serve.graph import result_cache  # noqa: E402

SCALE = 10


# -- the smoke phases, end to end on the CPU ---------------------------------


def test_phase_default_small():
    out = chip_smoke.phase_default(SCALE)
    assert out["retraces"] == 0
    assert 1 <= out["max_batch"] <= chip_smoke.MAX_BATCH_CAP


def test_phase_compressed_small():
    out = chip_smoke.phase_compressed(SCALE)
    assert set(out["spans"]) == {"pagerank_0", "insert", "pagerank_1"}


def test_phase_sharded_small():
    out = chip_smoke.phase_sharded(SCALE, n_shards=4)
    assert out["devices"] == min(4, jax.device_count())


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out  # no result line


# -- no interpret mode off CPU/TPU -------------------------------------------


def test_interpret_raises_on_other_backends(monkeypatch):
    assert kops._interpret() is True  # CPU: the interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas kernel path"):
        kops._interpret()


# -- autotune never sweeps under a trace -------------------------------------


def test_autotune_no_sweep_under_jit(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    autotune.reset()
    swept = []
    monkeypatch.setattr(
        autotune, "sweep",
        lambda kernel, *a, **k: swept.append(kernel) or dict(autotune.DEFAULTS[kernel]),
    )
    rng = np.random.default_rng(0)
    dst = jnp.asarray(np.sort(rng.integers(0, 200, 700)), jnp.int32)
    msg = jnp.ones((700, 2), jnp.float32)
    try:
        traced = jax.jit(lambda d, m: kops.segment_sum(d, m, 200))(dst, msg)
        assert swept == []  # under the trace: defaults, no sweep
        want = kops.segment_sum(dst, msg, 200, **autotune.DEFAULTS["segment_sum"])
        np.testing.assert_allclose(np.asarray(traced), np.asarray(want))
        kops.segment_sum(dst, msg, 200)  # eager dispatch may sweep
        assert len(swept) == 1
    finally:
        autotune.reset()


# -- promotion failures are counted, answers stay right ----------------------


def test_promotion_failure_is_counted(monkeypatch):
    n = 256
    stream = AspenStream(G.build_graph(n, symmetrize(rmat_edges(8, 1500, seed=3))))
    svc = GraphQueryService(stream, backend="numpy", max_batch=4)

    def broken(*a, **k):
        raise RuntimeError("forced promotion failure")

    monkeypatch.setattr(result_cache, "_promote_batch", broken)
    with svc:
        for _ in range(2):  # a hit makes the entry hot: promotable
            svc.query("bfs", source=3, timeout=30)
        assert svc.stats()["cache"]["promotion_errors"] == 0
        svc.insert_edges(np.array([[3, 200]]))
        svc.flush_updates()
        svc.flush_promotions()
        cache = svc.stats()["cache"]
        assert cache["promotion_errors"] >= 1
        assert "forced promotion failure" in cache["last_promotion_error"]
        got = svc.query("bfs", source=3, timeout=30)  # a cold miss, still right
        eng = stream.engine("numpy")
        from repro.core.traversal import algorithms as talg

        want = talg.bfs_multi(eng, [3])[1][0]
        assert np.array_equal(talg.bfs_depths(got, 3), want)


# -- the compile cache is placed from outside, else at a fixed path ----------


def test_compile_cache_location(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    assert compile_cache.enable() == str(tmp_path)
    assert updates == []  # the variable is honoured; nothing else is set
    monkeypatch.delenv(compile_cache.ENV)
    first = compile_cache.enable()
    assert first == compile_cache.enable()
    assert Path(first) == ROOT / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    assert str(os.getpid()) not in first
