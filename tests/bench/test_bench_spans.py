"""The program's profiler spans, and the metrics that read them.

Each tiny cell, traced on the CPU, records every span its traffic
reaches: the writer's ``aspen.publish`` with ``aspen.publish.tree`` and
``aspen.publish.mirror`` inside it on one thread, the executor's
``serve.flush`` with ``serve.flush.fetch`` inside it on another, and
``aspen.engine_build`` and ``serve.promote``.  CPython 3.12 gives its
threads no native name, so the trace names every Python thread's line
after the process; a line is told apart by its place in the host
plane.  The readers of ``idle_in_publish_pct.*`` and ``fetch_wait_ms``
are checked on hand-built summaries."""
import pytest

from _tiny import run_tiny, tiny_cell
from bench import harness, xplane
from bench.harness import BENCH, load_module
from bench.xplane import Op, Span, Summary

WRITER = ("aspen.publish", "aspen.publish.tree", "aspen.publish.mirror")
EXECUTOR = ("serve.flush", "serve.flush.fetch")
ARGS = {"aspen.publish": {"op", "rows"}, "aspen.publish.tree": {"parent"},
        "aspen.publish.mirror": {"parent"}, "aspen.engine_build": {"stamp"},
        "serve.flush": {"kind", "batch", "stamp", "tickets"},
        "serve.flush.fetch": {"kind"}, "serve.promote": {"src", "dst"}}


def _program_events(path):
    """(line, name, start, end, args) of the program's spans, ``line``
    being (plane, index of the line in it)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in ARGS:
                    out.append(((plane.name, i), e.name, e.start_ns,
                                e.start_ns + e.duration_ns, xplane._stats(e)))
    return out


def _traced(monkeypatch, cell):
    """Run ``cell`` traced over most of a 3 s window; returns (the
    run's ``rec.trace``, the raw program spans)."""
    cell.traffic = dict(cell.traffic, trace=[0.1, 0.9])
    seen = {}
    summary = harness.Tracer.summary

    def keep(self, host_ops=False):
        seen["events"] = _program_events(xplane.find_xplane(self.dir))
        seen["trace"] = summary(self, host_ops=host_ops)
        return seen["trace"]

    monkeypatch.setattr(harness.Tracer, "summary", keep)
    out = run_tiny(cell, traced=True)
    assert out["correct"] is True
    return seen["trace"], seen["events"]


def _inside(events, inner, outer):
    """Every ``inner`` span lies in an ``outer`` span of its own line.
    The profiler records a span only if it opens and closes while the
    trace runs, so an inner span of an outer one that was open when the
    trace started or stopped stands alone: those, before the first and
    after the last recorded outer span, are left out."""
    outs = [e for e in events if e[1] == outer]
    assert outs, outer
    first, last = min(o[2] for o in outs), max(o[3] for o in outs)
    for line, _, a, b, _ in (e for e in events if e[1] == inner):
        if first <= a and b <= last:
            assert any(o[0] == line and o[2] <= a and b <= o[3] for o in outs), inner


def _one_line(events, names):
    lines = {e[0] for e in events if e[1] in names}
    assert len(lines) == 1, (names, lines)
    return lines.pop()


def _args_ok(events):
    for _, name, _, _, args in events:
        assert ARGS[name] <= set(args), (name, args)


def test_traverse_cell_records_the_writer_and_executor_spans(monkeypatch):
    cell = tiny_cell("g500-s18-traverse-live", period_s=0.5)
    trace, events = _traced(monkeypatch, cell)
    assert set(ARGS) <= {s.name for s in trace.spans}
    writer, executor = _one_line(events, WRITER), _one_line(events, EXECUTOR)
    assert writer != executor
    _inside(events, "aspen.publish.tree", "aspen.publish")
    _inside(events, "aspen.publish.mirror", "aspen.publish")
    _inside(events, "serve.flush.fetch", "serve.flush")
    _args_ok(events)
    # a flush names the version it served: one the writer published
    published = {a["parent"] for _, n, _, _, a in events if n == "aspen.publish.tree"}
    served = {a["stamp"] for _, n, _, _, a in events if n == "serve.flush"}
    assert served and max(served) <= max(published) + 1


def test_updates_cell_records_the_writer_spans(monkeypatch):
    cell = tiny_cell("rmat16-updates", scale=10, batch_pairs=64)
    trace, events = _traced(monkeypatch, cell)
    names = {s.name for s in trace.spans}
    assert set(WRITER) | {"serve.promote"} <= names
    assert not names & set(EXECUTOR)  # no queries in this cell
    _one_line(events, WRITER)
    _inside(events, "aspen.publish.tree", "aspen.publish")
    _inside(events, "aspen.publish.mirror", "aspen.publish")
    _args_ok(events)
    ops = {a["op"] for _, n, _, _, a in events if n == "aspen.publish"}
    assert ops == {"insert", "delete"}


class _Rec:
    def __init__(self, summary):
        self.trace = summary


def _read(metric, summary):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(_Rec(summary))


DEV = "/device:TPU:0"


def _busy_with_a_gap():
    # the device is busy over [0, 400) and [600, 1000): a 200 ns gap,
    # 20 % of the window
    return [Op(DEV, "jit_bfs_batch", "while.1", 0, 400),
            Op(DEV, "jit_insert_edges", "fusion.2", 600, 400)]


@pytest.mark.parametrize("cell", ["update", "query"])
def test_idle_in_publish_counts_the_gap_under_the_writer(cell):
    metric = f"idle_in_publish_pct.{cell}"
    # a publish over [500, 700) covers half the gap: 10 % of the window
    s = Summary(_busy_with_a_gap(), [Span("w", "aspen.publish", 500, 200),
                                     Span("w", "aspen.publish.tree", 500, 100)])
    assert s.window_ns == (0, 1000)
    assert _read(metric, s) == pytest.approx(10.0)
    # a second, overlapping publish on another thread adds only new idle time
    s = Summary(_busy_with_a_gap(), [Span("w", "aspen.publish", 500, 200),
                                     Span("x", "aspen.publish", 450, 100)])
    assert _read(metric, s) == pytest.approx(15.0)
    # a span that crosses the window's edge is not counted
    s = Summary(_busy_with_a_gap(), [Span("w", "aspen.publish", 500, 200)])
    s.window_ns = (0, 650)
    assert _read(metric, s) == 0.0
    # a program without spans (an older build) reads nothing
    s = Summary(_busy_with_a_gap(), [Span("w", "bench.writer.flush", 500, 200)])
    assert _read(metric, s) is None


def test_fetch_wait_sums_the_fetches_inside_each_flush():
    spans = [
        Span("e", "serve.flush", 100, 300),
        Span("e", "serve.flush.fetch", 150, 100),
        Span("e", "serve.flush.fetch", 260, 40),
        Span("e", "serve.flush", 500, 200),
        Span("e", "serve.flush.fetch", 550, 60),
        Span("p", "serve.flush.fetch", 520, 30),  # another thread's
    ]
    s = Summary(_busy_with_a_gap(), spans)
    # (100 + 40 + 60) ns over two flushes
    assert _read("fetch_wait_ms", s) == pytest.approx(100e-6)
    # a flush crossing the window's edge, and its fetches, are not counted
    s.window_ns = (0, 650)
    assert _read("fetch_wait_ms", s) == pytest.approx(140e-6)
    s = Summary(_busy_with_a_gap(), [Span("e", "bench.submit", 100, 10)])
    assert _read("fetch_wait_ms", s) is None


def test_publish_means_read_the_writer_spans():
    spans = [Span("w", "aspen.publish.tree", 0, 2_000_000),
             Span("w", "aspen.publish.tree", 3_000_000, 4_000_000),
             Span("w", "aspen.publish.mirror", 100, 500_000)]
    s = Summary(_busy_with_a_gap(), spans)
    assert _read("publish_tree_ms", s) == pytest.approx(3.0)
    assert _read("merge_dispatch_ms", s) == pytest.approx(0.5)
    assert _read("publish_tree_ms", Summary(_busy_with_a_gap(), [])) is None
