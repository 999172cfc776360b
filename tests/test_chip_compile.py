"""The main path's Pallas kernels compile for a TPU v5e.

Each case lowers a kernel for one chip of a described ``v5e:2x2``
topology (nothing runs; no chip is needed) with x64 on, as the program
runs, and asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Interpret-mode tests cannot see what only the
TPU compiler refuses: unaligned blocks, i64 index maps, primitives
Mosaic cannot lower.

The topology is described inside a module-scoped fixture, so importing
this file never loads the TPU library; the fixture skips when it cannot
be described.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402,F401  (x64 on)
from repro.kernels import autotune, delta_decode, segment_reduce  # noqa: E402

CHUNK = 128
K = 8  # escape slots per chunk
# (n_out, E): the kernels' defaults at a small problem, and a realistic
# width (2^20 vertices, 2^22 edges) plus the padding dst block
SIZES = {"small": ((1 << 12) + 128, 1 << 14), "real": ((1 << 20) + 128, 1 << 22)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _raw_case(sh, n, E, weighted, D=1, **blocks):
    args = [_sds(sh, (E,), jnp.int32)]
    if weighted:
        args.append(_sds(sh, (E,), jnp.float32))
        fn = lambda d, w, m: segment_reduce.segment_sum_weighted_sorted(  # noqa: E731
            d, w, m, n, **blocks)
    else:
        fn = lambda d, m: segment_reduce.segment_sum_sorted(d, m, n, **blocks)  # noqa: E731
    args.append(_sds(sh, (E, D), jnp.float32))
    return fn, args


def _chunked_case(sh, n, E, weighted, adaptive, lane=jnp.int8, D=1, **blocks):
    R = E // CHUNK
    args = [_sds(sh, (R,), jnp.int32), _sds(sh, (R, CHUNK), lane)]
    if adaptive:
        args += [_sds(sh, (R, CHUNK), jnp.int8), _sds(sh, (R, 1), jnp.int32)]
    args += [_sds(sh, (R, K), jnp.int32), _sds(sh, (R, K), jnp.int32)]
    if weighted:
        args.append(_sds(sh, (E,), jnp.float32))
    args.append(_sds(sh, (E, D), jnp.float32))
    kernel = {
        (False, False): segment_reduce.segment_sum_sorted_chunked,
        (True, False): segment_reduce.segment_sum_weighted_chunked,
        (False, True): segment_reduce.segment_sum_sorted_chunked_adaptive,
        (True, True): segment_reduce.segment_sum_weighted_chunked_adaptive,
    }[(weighted, adaptive)]
    return (lambda *a: kernel(*a, n, **blocks)), args


SEGMENT_CASES = [
    ("raw", dict(weighted=False)),
    ("raw-weighted", dict(weighted=True)),
    ("raw-batched", dict(weighted=True, D=8)),
    ("chunked-int8", dict(chunked=True, weighted=False, adaptive=False)),
    ("chunked-int16", dict(chunked=True, weighted=False, adaptive=False, lane=jnp.int16)),
    ("chunked-weighted", dict(chunked=True, weighted=True, adaptive=False)),
    ("adaptive", dict(chunked=True, weighted=False, adaptive=True)),
    ("adaptive-weighted", dict(chunked=True, weighted=True, adaptive=True)),
]


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("name,case", SEGMENT_CASES, ids=[c[0] for c in SEGMENT_CASES])
def test_segment_sum_compiles_for_v5e(one_chip, size, name, case):
    n, E = SIZES[size]
    case = dict(case)
    if case.pop("chunked", False):
        fn, args = _chunked_case(one_chip, n, E, **case)
    else:
        fn, args = _raw_case(one_chip, n, E, **case)
    assert "tpu_custom_call" in _compile_text(fn, *args)


DECODE_CASES = ["int8", "int16", "adaptive", "padded"]


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("name", DECODE_CASES)
def test_delta_decode_compiles_for_v5e(one_chip, size, name):
    _, E = SIZES[size]
    R = E // CHUNK
    sh = one_chip
    rows = lambda w: _sds(sh, (R, w), jnp.int32)  # noqa: E731
    anchors = _sds(sh, (R,), jnp.int32)
    if name == "padded":
        fn = delta_decode.delta_decode_padded
        args = [anchors, rows(2 * CHUNK)]
    elif name == "adaptive":
        fn = delta_decode.delta_decode_chunked_adaptive
        args = [anchors, _sds(sh, (R, CHUNK), jnp.int8), _sds(sh, (R, CHUNK), jnp.int8),
                anchors, rows(K), rows(K)]
    else:
        lane = jnp.int8 if name == "int8" else jnp.int16
        fn = delta_decode.delta_decode_chunked
        args = [anchors, _sds(sh, (R, CHUNK), lane), rows(K), rows(K)]
    assert "tpu_custom_call" in _compile_text(fn, *args)


TUNED = sorted(
    {(k, tuple(sorted(p.items())))
     for k in autotune.DEFAULTS if k != "spmm"
     for p in autotune.CANDIDATES[k] + [autotune.DEFAULTS[k]]}
)


@pytest.mark.parametrize("kernel,params", TUNED, ids=[f"{k}-{dict(p)}" for k, p in TUNED])
def test_autotune_blocks_compile_for_v5e(one_chip, kernel, params):
    """Every default and sweep candidate of the segment sums is a block
    shape the TPU compiler accepts (a sweep on the chip must never pick,
    or die on, an illegal one)."""
    blocks = dict(params)
    n = (1 << 12) + blocks["dst_block"]
    E = 2 * blocks["edge_block"]
    weighted = "weighted" in kernel
    if "chunked" in kernel:
        fn, args = _chunked_case(one_chip, n, E, weighted=weighted, adaptive=True, **blocks)
    else:
        fn, args = _raw_case(one_chip, n, E, weighted=weighted, **blocks)
    assert "tpu_custom_call" in _compile_text(fn, *args)


def test_band_schedule_bounds_grid_steps():
    """The band schedule lists every intersecting (dst block, edge block)
    pair within its static length, each dst block at least once."""
    rng = np.random.default_rng(0)
    n_blocks, db, eb = 40, 128, 512
    dst = np.sort(rng.integers(0, n_blocks * db - 700, 20 * eb)).astype(np.int32)
    d = dst.reshape(-1, eb)
    bi, bj, fl = (np.asarray(x) for x in segment_reduce.band_schedule(
        jnp.asarray(d[:, 0]), jnp.asarray(d[:, -1]), n_blocks, db))
    assert bi.shape == (n_blocks + d.shape[0],)
    valid = (fl & 2) != 0
    pairs = set(zip(bi[valid].tolist(), bj[valid].tolist()))
    need = {(int(v) // db, j) for j in range(d.shape[0]) for v in d[j]}
    assert need <= pairs
    assert set(bi[valid].tolist()) == set(range(n_blocks))
    assert np.all(np.diff(bi) >= 0)  # dst-block major: tiles written once
