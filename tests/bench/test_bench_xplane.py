"""The reduction from a profiler trace to the benchmark's device numbers,
on a synthesized event list and on a trace recorded on the CPU."""
import glob
import os

import pytest

from _tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench import xplane
from bench.xplane import Op, Span, Summary


def _summary():
    ops = [
        Op("/device:TPU:0", "jit_bfs_batch", "while.1", 100, 50),
        Op("/device:TPU:0", "jit_bfs_batch", "fusion.2", 140, 30),  # overlaps: union
        Op("/device:TPU:0", "jit_segment_sum_sorted", "kernel", 300, 100),
        Op("/device:TPU:0", "jit_insert_edges", "scatter.3", 600, 200),
    ]
    spans = [
        Span("main", "bench.window", 0, 1000),
        Span("q", "bench.submit", 180, 100),
        Span("x", "PjitFunction(bfs_batch)", 170, 120),
        Span("w", "bench.writer.insert", 420, 150),
    ]
    return Summary(ops, spans)


def test_busy_is_the_union_of_device_operations():
    s = _summary()
    assert s.window_ns == (0, 1000)
    # [100, 170) + [300, 400) + [600, 800) = 70 + 100 + 200
    assert s.busy_s == pytest.approx(370e-9)
    assert s.window_s == pytest.approx(1000e-9)


def test_matching_and_device_time_by_module_and_name():
    s = _summary()
    seg = s.matching(modules=[r"segment_sum_sorted"], names=[r"kernel"])
    assert [o.name for o in seg] == ["kernel"]
    assert s.seconds(seg) == pytest.approx(100e-9)
    assert s.matching(modules=[r"nothing"]) == []
    assert len(s.matching()) == 4
    top = s.device_ops()
    # module time from the union of its ops: the bfs ops nest
    assert top[0] == ["jit_insert_edges", pytest.approx(200e-9)]
    assert dict(top)["jit_bfs_batch"] == pytest.approx(70e-9)
    runs = s.module_runs(exclude=[r"insert_edges"])
    assert s.seconds(runs) == pytest.approx(170e-9)


def test_module_events_give_device_time_and_kernel_shapes():
    text = ("%segment_sum_sorted.1 = f32[1152,4]{1,0:T(8,128)} custom-call("
            "s32[25]{0} %a, s32[1,8192]{1,0} %b, f32[8192,4]{1,0:T(8,128)} %c)")
    ops = [Op("/device:TPU:0", "jit_segment_sum_sorted", "%segment_sum_sorted.1", 10, 40, text),
           Op("/device:TPU:0", "jit_segment_sum_sorted", "%copy.5", 55, 5, "%copy.5 = ...")]
    mods = [xplane.Module("/device:TPU:0", "jit_segment_sum_sorted", 0, 80)]
    s = Summary(ops, [], mods)
    assert s.seconds(s.module_runs([r"segment_sum"])) == pytest.approx(80e-9)
    from bench.harness import BENCH, load_module

    reader = load_module(BENCH / "metrics" / "segsum_roofline.py")

    class Rec:
        trace = s
        device = {"kind": "TPU v5 lite"}

        class cell:
            config = {"scale": 10}

    # one call: 8192 edges x 4 lanes onto 1024 rows, in 40 ns
    want = 100 * (8192 * 4 + 8192 * 4 * 4 + 1024 * 4 * 4) / 819e9 / 40e-9
    assert reader.read(Rec) == pytest.approx(want)


def test_idle_gaps_longest_first_with_the_host_span_in_them():
    gaps = _summary().idle_gaps()
    # gaps: [0,100) 100, [170,300) 130, [400,600) 200, [800,1000) 200
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9, 130e-9, 100e-9])
    labels = dict((round(g[1] * 1e9), g[0]) for g in gaps[2:])
    # the bench span is preferred over JAX's own dispatch span
    assert labels[130] == "bench.submit"
    assert labels[100] == "no host span"
    assert gaps[0][0] in ("bench.writer.insert", "no host span")
    assert "bench.writer.insert" in [g[0] for g in gaps]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace recorded on the CPU: host events only, no device plane."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    tmp_path = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    return xplane.find_xplane(str(tmp_path))


def test_a_recorded_cpu_trace_reduces(cpu_trace):
    s = xplane.summarize(cpu_trace, host_ops=True)
    assert any("bench.window" == sp.name for sp in s.spans)
    assert s.ops and all(o.module.startswith("jit_") for o in s.ops)
    assert 0 < s.busy_s <= s.window_s
    assert s.breakdown()["device_ops"]


def test_a_trace_without_device_operations_raises(cpu_trace):
    # on the chip a trace must hold device planes: host events never
    # stand in for them unless the caller asks
    with pytest.raises(ValueError, match="no device operation"):
        xplane.summarize(cpu_trace)


def test_missing_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path))
