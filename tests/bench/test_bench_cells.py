"""Both cells end to end on the CPU at a tiny size: the answers agree
with the reference and the result line has the required shape; a cell,
traffic and metric added as files are picked up without a code edit; and
``run.py`` refuses the CPU and a checkout without the program."""
import json
import shutil
import sys

import numpy as np
import pytest

from _tiny import ROOT, run_tiny, tiny_cell

sys.path.insert(0, str(ROOT / "bench"))
from bench import harness  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


def _shape_ok(out, traced):
    assert RESULT_KEYS <= set(out) and list(out)[-1] == "check"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    for name, c in out["check"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"], name
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    if traced:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
    json.dumps(out)  # one JSON line


@pytest.mark.parametrize("traced", [False, True])
def test_traverse_cell_tiny(traced):
    cell = tiny_cell("g500-s18-traverse-live")
    out = run_tiny(cell, traced=traced)
    _shape_ok(out, traced)
    want = {m["name"] for m in cell.metrics(traced)}
    got = set(out["metrics"])
    if traced:
        # the CPU trace holds no Pallas kernel: that share is left out
        assert got == want - {"segsum_roofline"}
    else:
        assert got == want
        assert got == {"setup_s", "query_p90_ms", "pagerank_p50_ms"}
    assert set(out["check"]) == {"bfs_bad_vertices", "pagerank_max_gap",
                                 "pagerank_rel_gap", "mirror_diff"}


@pytest.mark.parametrize("traced", [False, True])
def test_updates_cell_tiny(traced):
    cell = tiny_cell("rmat16-updates", scale=10, batch_pairs=64)
    out = run_tiny(cell, traced=traced)
    _shape_ok(out, traced)
    want = {m["name"] for m in cell.metrics(traced)}
    assert set(out["metrics"]) == want
    assert set(out["check"]) == {"mirror_diff"}
    if not traced:
        assert out["metrics"]["update_edges_per_s"]["value"] > 0


def test_update_rate_counts_the_write_in_flight_at_the_close_pro_rata():
    from bench.harness import BENCH, Record, Write, load_module

    reader = load_module(BENCH / "metrics" / "update_edges_per_s.py")
    rec = Record(None, 0, 10.0, False)
    rec.t0, rec.t1 = 100.0, 110.0
    pairs = np.zeros((50, 2), np.int64)  # 100 directed updates each

    def writes(*times):
        out = []
        for queued, done in times:
            w = Write("insert", pairs, queued=queued)
            w.done = done
            out.append(w)
        return out

    # two whole publishes, then one queued at 107.0 but started when the
    # second was seen, 108.0, and seen at 112.0: half of it lies inside
    rec.writes = writes((100.0, 104.0), (104.0, 108.0), (107.0, 112.0))
    assert reader.read(rec) == pytest.approx((100 + 100 + 50) / 10.0)
    # one never seen counts nothing; none queued: nothing to read
    rec.writes = writes((100.0, 104.0), (104.0, np.inf))
    assert reader.read(rec) == pytest.approx(100 / 10.0)
    rec.writes = []
    assert reader.read(rec) is None


def test_a_mix_with_a_kind_the_check_cannot_compare_is_refused():
    from bench import gen

    cell = tiny_cell("g500-s18-traverse-live", scale=6)
    graph = gen.make_graph(cell.config, 1)
    for kind in ("sssp", "cc"):
        traffic = dict(cell.traffic, queries=dict(cell.traffic["queries"],
                                                  kinds={"bfs": 0.5, kind: 0.5}))
        with pytest.raises(ValueError, match="no reference"):
            cell.generator.plan(traffic, graph, 1, 5.0)


def test_new_files_are_picked_up_without_an_edit(tmp_path):
    src = harness.BENCH
    b = tmp_path / "bench"
    for sub in ("metrics", "traffic"):
        shutil.copytree(src / sub, b / sub)
    (b / "configs").mkdir()
    (b / "limits").mkdir()
    cfg = harness.load_json(src / "configs" / "graph500-s18.json")
    cfg.update(scale=8, edge_capacity=1 << 13)
    (b / "configs" / "tiny-g500.json").write_text(json.dumps(cfg))
    traffic = harness.load_json(src / "traffic" / "traverse-live.json")
    traffic["queries"].update(rate_per_s=4.0, kinds={"bfs": 1.0}, check_sample=4)
    traffic["writer"].update(batch_pairs=16, period_s=1.0)
    (b / "traffic" / "bfs-only.json").write_text(json.dumps(traffic))
    (b / "limits" / "tiny-cell.json").write_text(json.dumps(
        {"bfs_bad_vertices": 0, "mirror_diff": 0}))
    (b / "metrics" / "answered_count.py").write_text(
        "def read(rec):\n    return sum(1 for q in rec.queries if q.ok)\n")
    bench = {
        "workloads": [{"name": "tiny-cell", "config": "tiny-g500",
                       "traffic": "bfs-only", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"},
            {"name": "answered_count", "unit": "queries", "better": "higher",
             "bound": 0.01, "source": "host_clock", "workloads": ["tiny-cell"]}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell("tiny-cell", bench_json=tmp_path / "BENCHMARK.json", bench_dir=b)
    out = run_tiny(cell, seconds=2.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "answered_count"}
    assert out["metrics"]["answered_count"]["value"] == 8.0
    assert set(out["check"]) == {"bfs_bad_vertices", "mirror_diff"}


def test_run_refuses_the_cpu(capsys, monkeypatch, tmp_path):
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.main(["--workload", "rmat16-updates", "--seed", str(2**31 + 7),
                  "--seconds", "1", "--trace", "0"])
    assert "{" not in capsys.readouterr().out


def test_run_refuses_a_checkout_without_the_program(capsys, monkeypatch, tmp_path):
    import run

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(run, "ROOT", tmp_path)
    assert run.main(["--workload", "rmat16-updates", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert "{" not in capsys.readouterr().out
