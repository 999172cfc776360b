"""GraphQueryService: the serving front end (DESIGN.md §13).

Pins the PR's contracts:

  (1) served answers are bit-identical to ``query_batch`` against the
      same version, per kind and backend;
  (2) empty request sets are no-ops: ``query_batch`` returns ``[]``
      (the lane-collapse regression);
  (3) admission is weighted-fair (stride scheduling ~ weight ratio
      under saturation) and respects per-tenant in-flight caps and
      backlog backpressure (``QueueFull``);
  (4) the flush policy: deadline (half-budget) flushes go out before
      the SLO, full lanes flush at ``max_batch``, both visible in
      ``stats()``;
  (5) ``Session`` pinning is strictly serializable: a pinned session
      interleaved with live publishes returns bit-identical answers
      across every read, on numpy / jax (and sharded under an 8-device
      mesh), and sessions never leak version refs (1k publishes);
  (6) steady-state serving never retraces after ``warmup()`` — pinned
      by BOTH the service's trace-key accounting and the jit-body
      ``TRACES`` spy;
  (7) ``drain_updates`` / ``UpdateQueue`` semantics shared with
      ``run_concurrent``: batching, insert-before-delete, the weight
      lane, backpressure counts, and publish listeners.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import graph as G
from repro.core.streaming import AspenStream, UpdateQueue, drain_updates
from repro.core.traversal import TRACES
from repro.data.rmat import rmat_edges, symmetrize
from repro.serve.graph import GraphQueryService, QueueFull

N = 256


@pytest.fixture(scope="module")
def rmat_edge_list():
    return symmetrize(rmat_edges(8, 2000, seed=11))  # 256 vertices


def make_stream(edges, **kw):
    return AspenStream(G.build_graph(N, edges), **kw)


def make_service(edges, **kw):
    kw.setdefault("backend", "jax")
    kw.setdefault("max_batch", 8)
    kw.setdefault("default_deadline_s", 0.25)
    stream = make_stream(edges)
    return stream, GraphQueryService(stream, **kw)


# ---------------------------------------------------------------------------
# (1) served answers == query_batch answers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_served_answers_match_query_batch(rmat_edge_list, backend):
    stream, svc = make_service(rmat_edge_list, backend=backend)
    with svc:
        tickets = {
            "bfs": svc.submit("bfs", source=3),
            "sssp": svc.submit("sssp", source=5),
            "pagerank": svc.submit("pagerank"),
            "cc": svc.submit("cc"),
        }
        got = {k: t.result(timeout=30) for k, t in tickets.items()}
    ref_bfs = stream.query_batch([3], kind="bfs", backend=backend)[0]
    ref_sssp = stream.query_batch([5], kind="sssp", backend=backend)[0]
    assert np.array_equal(got["bfs"], ref_bfs)
    assert np.array_equal(got["sssp"], ref_sssp)
    assert got["pagerank"].shape == (N,)
    assert abs(float(np.asarray(got["pagerank"]).sum()) - 1.0) < 1e-3
    labels = np.asarray(got["cc"])
    assert labels.shape == (N,)
    # cc labels agree with the traversal layer's own answer
    from repro.core.traversal import algorithms as talg

    assert np.array_equal(labels, np.asarray(talg.connected_components(
        stream.engine(backend)), np.int64))


def test_duplicate_sources_one_compute_fan_out(rmat_edge_list):
    stream, svc = make_service(rmat_edge_list, max_batch=8,
                               default_deadline_s=0.5)
    with svc:
        ts = [svc.submit("bfs", source=7) for _ in range(6)]
        rows = [t.result(timeout=30) for t in ts]
    ref = stream.query_batch([7], kind="bfs", backend="jax")[0]
    for r in rows:
        assert np.array_equal(r, ref)


def test_ticket_seq_grows_by_one_per_submit(rmat_edge_list):
    # every submit is numbered, cache hits included: the number a
    # serve.flush profiler span names its tickets by
    stream, svc = make_service(rmat_edge_list, backend="numpy")
    with svc:
        first = svc.submit("bfs", source=3)
        first.result(timeout=30)
        later = [svc.submit(k, source=s) for k, s in
                 [("bfs", 3), ("bfs", 5), ("pagerank", 5), ("sssp", 9)]]
        for t in later:
            t.result(timeout=30)
    assert later[0].cached  # an exact hit, served at submit
    assert [t.seq for t in [first] + later] == list(range(5))


def test_ticket_validation():
    stream, svc = make_service(symmetrize(rmat_edges(8, 2000, seed=11)))
    with svc:
        with pytest.raises(ValueError):
            svc.submit("bfs")  # source required
        with pytest.raises(ValueError):
            svc.submit("nope", source=0)
    with pytest.raises(RuntimeError):
        svc.submit("bfs", source=0)  # stopped service rejects


# ---------------------------------------------------------------------------
# (2) empty request set -> [] (regression: used to raise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_query_batch_empty_returns_empty_list(rmat_edge_list, backend):
    stream = make_stream(rmat_edge_list)
    for kind in ("bfs", "distances", "bc", "sssp"):
        assert stream.query_batch(None, kind=kind, backend=backend) == []
        assert stream.query_batch([], kind=kind, backend=backend) == []
        assert (
            stream.query_batch(np.empty(0, np.int64), kind=kind, backend=backend)
            == []
        )
    assert (
        stream.query_batch(
            kind="pagerank", backend=backend, resets=np.zeros((0, N))
        )
        == []
    )
    # unknown kinds still raise, even on empty request sets
    with pytest.raises(ValueError):
        stream.query_batch(None, kind="nope", backend=backend)


# ---------------------------------------------------------------------------
# (3) weighted fairness, in-flight caps, backpressure
# ---------------------------------------------------------------------------


def test_weighted_fair_admission(rmat_edge_list):
    """Under a saturated backlog, admissions track tenant weights:
    with caps forcing one flush at a time, a 3:1 weight split admits
    ~3x the requests for the heavy tenant over any window."""
    stream, svc = make_service(
        rmat_edge_list,
        tenant_weights={"heavy": 3.0, "light": 1.0},
        max_batch=4,
        max_inflight_total=4,
        default_deadline_s=10.0,  # no deadline flush: admission decides order
    )
    from repro.serve.graph.admission import AdmissionQueue
    from repro.serve.graph.request import QueryTicket

    # unit-test the scheduler itself (deterministic, no threads)
    q = AdmissionQueue(weights={"heavy": 3.0, "light": 1.0},
                       max_inflight_per_tenant=100, max_inflight_total=1000)
    for i in range(40):
        q.submit(QueryTicket("heavy", "bfs", i, {}, deadline=1e18))
        q.submit(QueryTicket("light", "bfs", i, {}, deadline=1e18))
    first = q.admit(max_n=20)
    heavy = sum(1 for t in first if t.tenant == "heavy")
    light = sum(1 for t in first if t.tenant == "light")
    assert heavy == 15 and light == 5  # exact 3:1 stride split

    # and end-to-end: everything completes despite the contention
    with svc:
        ts = [svc.submit("bfs", source=i % N, tenant="heavy") for i in range(12)]
        ts += [svc.submit("bfs", source=i % N, tenant="light") for i in range(12)]
        for t in ts:
            t.result(timeout=60)
        st = svc.stats()
    assert st["tenants"]["heavy"]["completed"] == 12
    assert st["tenants"]["light"]["completed"] == 12


def test_inflight_caps_and_backpressure(rmat_edge_list):
    from repro.serve.graph.admission import AdmissionQueue
    from repro.serve.graph.request import QueryTicket

    q = AdmissionQueue(max_inflight_per_tenant=2, max_inflight_total=3,
                       max_backlog=4)
    for i in range(4):
        q.submit(QueryTicket("a", "bfs", i, {}, deadline=1e18))
    with pytest.raises(QueueFull):
        q.submit(QueryTicket("a", "bfs", 9, {}, deadline=1e18))
    for i in range(2):
        q.submit(QueryTicket("b", "bfs", i, {}, deadline=1e18))
    admitted = q.admit()
    # per-tenant cap (2) binds for a; global cap (3) leaves b one slot
    assert sum(1 for t in admitted if t.tenant == "a") == 2
    assert sum(1 for t in admitted if t.tenant == "b") == 1
    assert q.admit() == []  # everything capped
    q.complete(admitted[0])
    assert len(q.admit()) == 1  # a completion frees exactly one slot


# ---------------------------------------------------------------------------
# (4) flush policy: deadline vs full-lane flushes
# ---------------------------------------------------------------------------


def test_full_lane_flushes_at_max_batch(rmat_edge_list):
    stream, svc = make_service(rmat_edge_list, max_batch=4,
                               default_deadline_s=30.0)
    with svc:
        svc.warmup(kinds=("bfs",))
        ts = [svc.submit("bfs", source=i) for i in range(8)]
        for t in ts:
            t.result(timeout=30)
        st = svc.stats()
    lane = st["lanes"]["bfs"]
    # 30s budgets mean nothing flushed early: both batches went out full
    assert lane["full_flushes"] >= 2
    assert lane["batch_size_hist"].get(4, 0) >= 2
    for t in ts:
        assert t.batch_size == 4
        assert t.deadline_missed is False


def test_work_conserving_flushes_idle_executor(rmat_edge_list):
    """With work_conserving=True a lone request flushes as soon as the
    executor is free — well before the half-budget instant — and the
    flush is accounted as an idle flush."""
    stream, svc = make_service(rmat_edge_list, max_batch=64,
                               default_deadline_s=30.0, work_conserving=True)
    with svc:
        svc.warmup(kinds=("bfs",))
        t = svc.submit("bfs", source=1)
        t.result(timeout=30)
        st = svc.stats()
    assert t.latency_s < 5.0  # nowhere near the 15s half-budget mark
    assert st["lanes"]["bfs"]["idle_flushes"] >= 1


def test_deadline_flush_before_slo(rmat_edge_list):
    stream, svc = make_service(rmat_edge_list, max_batch=64,
                               default_deadline_s=0.3)
    with svc:
        svc.warmup(kinds=("bfs",))
        t = svc.submit("bfs", source=1)  # alone in its lane: never fills
        r = t.result(timeout=30)
        st = svc.stats()
    assert r.shape == (N,)
    assert st["lanes"]["bfs"]["deadline_flushes"] >= 1
    # the half-budget rule waited ~>= 0.15s but answered within the SLO
    assert t.deadline_missed is False


# ---------------------------------------------------------------------------
# (5) session pinning: strict serializability + ref hygiene
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_session_strictly_serializable(rmat_edge_list, backend):
    """A pinned session interleaved with publishes answers every read
    from its open-time version, bit-identical across kinds — while
    unpinned reads see the new edges."""
    stream, svc = make_service(rmat_edge_list, backend=backend)
    with svc:
        with svc.session(tenant="t") as sess:
            stamp0 = sess.stamp
            bfs0 = sess.query("bfs", source=3).result(timeout=30)
            sssp0 = sess.query("sssp", source=3).result(timeout=30)
            pr0 = sess.query("pagerank").result(timeout=30)
            # publish between every pair of session reads
            for i in range(3):
                svc.insert_edges(np.array([[3, 200 + i], [200 + i, 210 + i]]))
                svc.flush_updates()
                assert np.array_equal(
                    sess.query("bfs", source=3).result(timeout=30), bfs0
                )
                assert np.array_equal(
                    sess.query("sssp", source=3).result(timeout=30), sssp0
                )
                assert np.array_equal(
                    sess.query("pagerank").result(timeout=30), pr0
                )
            assert sess.stamp == stamp0
            fresh = svc.submit("bfs", source=3).result(timeout=30)
        assert stream.vg.current_stamp > stamp0
        assert not np.array_equal(fresh, bfs0)  # unpinned reads advanced


@pytest.mark.multidevice
def test_session_strictly_serializable_sharded(rmat_edge_list):
    stream = AspenStream(G.build_graph(N, rmat_edge_list), mirror="sharded",
                         n_shards=8)
    svc = GraphQueryService(stream, backend="sharded", max_batch=4)
    with svc:
        with svc.session(tenant="t") as sess:
            bfs0 = sess.query("bfs", source=3).result(timeout=60)
            svc.insert_edges(np.array([[3, 200], [200, 210]]))
            svc.flush_updates()
            assert np.array_equal(
                sess.query("bfs", source=3).result(timeout=60), bfs0
            )
            fresh = svc.submit("bfs", source=3).result(timeout=60)
        assert not np.array_equal(fresh, bfs0)


def test_sessions_do_not_leak_versions(rmat_edge_list):
    """1k publishes with sessions opened/closed throughout leave no
    extra live versions once closed (GC reclaims everything behind the
    current version)."""
    stream, svc = make_service(rmat_edge_list, backend="numpy")
    with svc:
        for i in range(1000):
            stream.insert_edges(
                np.array([[i % N, (i * 7 + 1) % N]]), symmetric=False
            )
            if i % 100 == 0:
                with svc.session(tenant="t") as s:
                    s.query("bfs", source=0).result(timeout=30)
        assert svc.stats()["sessions_open"] == 0
    assert stream.vg.live_versions() == 1  # only current survives


def test_session_close_is_idempotent_and_blocks_new_queries(rmat_edge_list):
    stream, svc = make_service(rmat_edge_list, backend="numpy")
    with svc:
        sess = svc.session(tenant="t")
        sess.query("bfs", source=0).result(timeout=30)
        sess.close()
        sess.close()  # idempotent
        with pytest.raises(RuntimeError):
            sess.query("bfs", source=0)


# ---------------------------------------------------------------------------
# (6) zero retraces after warmup
# ---------------------------------------------------------------------------


def test_zero_retraces_after_warmup(rmat_edge_list):
    stream, svc = make_service(rmat_edge_list, max_batch=8)
    with svc:
        svc.warmup()
        before = TRACES.count
        rng = np.random.default_rng(0)
        tickets = []
        for _ in range(40):
            tickets.append(svc.submit("bfs", source=int(rng.integers(N))))
            tickets.append(svc.submit("sssp", source=int(rng.integers(N))))
        tickets.append(svc.submit("pagerank"))
        tickets.append(svc.submit("cc"))
        for t in tickets:
            t.result(timeout=60)
        st = svc.stats()
    # both spies agree: nothing compiled in steady state
    assert TRACES.count == before, "jit drivers retraced after warmup"
    for kind, lane in st["lanes"].items():
        assert lane["retraces"] == 0, (kind, lane)


def test_capacity_growth_is_a_legitimate_retrace(rmat_edge_list):
    """A pool-capacity-growing publish changes array shapes, so the
    NEXT flush traces fresh code — the trace-key accounting must call
    that out (retraces > 0) rather than hide it."""
    stream, svc = make_service(rmat_edge_list, max_batch=4)
    cap0 = stream.flat_graph().edge_capacity
    with svc:
        svc.warmup(kinds=("bfs",))
        # bulk insert until the pool capacity actually grows
        rng = np.random.default_rng(1)
        while stream.flat_graph().edge_capacity == cap0:
            stream.insert_edges(rng.integers(0, N, (512, 2)))
        svc.submit("bfs", source=0).result(timeout=60)
        st = svc.stats()
    assert st["lanes"]["bfs"]["retraces"] >= 1


# ---------------------------------------------------------------------------
# (7) drain_updates / UpdateQueue shared writer-loop semantics
# ---------------------------------------------------------------------------


def test_drain_updates_batches_and_orders(rmat_edge_list):
    stream = make_stream(rmat_edge_list)
    v0 = stream.acquire()
    m0 = G.num_edges(v0.graph)
    stream.release(v0)
    q = UpdateQueue()
    # interleaved: insert applies before the delete within one drain,
    # so the pair cancels — edge count is back where it started
    q.put(1, 240)
    q.put(1, 240, delete=True)
    stamp0 = stream.vg.current_stamp
    assert drain_updates(q, stream, max_batch=10) == 2
    v1 = stream.acquire()
    m1 = G.num_edges(v1.graph)
    stream.release(v1)
    assert m1 == m0
    assert stream.vg.current_stamp > stamp0
    assert drain_updates(q, stream, max_batch=10) == 0  # empty: no-op


def test_drain_updates_weight_lane():
    stream = AspenStream(G.build_graph(8, np.array([[0, 1]])))
    q = UpdateQueue()
    q.put(2, 3, weight=2.5)
    q.put(4, 5)  # weight-less row in a mixed batch rides with unit fill
    assert drain_updates(q, stream, max_batch=10) == 2
    eng = stream.engine("numpy")
    assert eng.weighted
    dist = stream.query_batch([2], kind="sssp", backend="numpy")[0]
    assert dist[3] == 2.5
    dist = stream.query_batch([4], kind="sssp", backend="numpy")[0]
    assert dist[5] == 1.0


def test_update_queue_backpressure_and_stats():
    q = UpdateQueue(maxsize=2)
    assert q.put(0, 1, block=False)
    assert q.put(1, 2, block=False)
    assert not q.put(2, 3, block=False)  # full: rejected, counted
    st = q.stats()
    assert st["rejected"] == 1 and st["depth"] == 2 and st["high_water"] == 2
    rows = q.pop_batch(10)
    assert len(rows) == 2 and len(q) == 0


def test_publish_listener_fires_and_unsubscribes():
    stream = AspenStream(G.build_graph(8, np.array([[0, 1]])))
    stamps = []
    unsub = stream.on_publish(lambda v: stamps.append(v.stamp))
    stream.insert_edges(np.array([[1, 2]]))
    assert stamps == [1]
    unsub()
    stream.insert_edges(np.array([[2, 3]]))
    assert stamps == [1]  # unsubscribed: no further calls


def test_service_under_live_writer(rmat_edge_list):
    """The integration shape the smoke script uses: mixed queries from
    two tenants racing a continuous writer, everything completes, clean
    shutdown, coherent stats."""
    stream, svc = make_service(rmat_edge_list, max_batch=8,
                               default_deadline_s=1.0)
    rng = np.random.default_rng(7)
    with svc:
        svc.warmup(kinds=("bfs", "sssp"))
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                svc.enqueue_update(int(rng.integers(N)), int(rng.integers(N)),
                                   delete=(i % 5 == 4), block=False)
                i += 1
                time.sleep(0.001)

        wt = threading.Thread(target=writer)
        wt.start()
        try:
            tickets = []
            for i in range(60):
                kind = "bfs" if i % 2 else "sssp"
                tenant = "a" if i % 3 else "b"
                tickets.append(
                    svc.submit(kind, source=int(rng.integers(N)), tenant=tenant)
                )
            results = [t.result(timeout=60) for t in tickets]
        finally:
            stop.set()
            wt.join()
        svc.flush_updates()
        st = svc.stats()
    assert len(results) == 60 and all(r.shape == (N,) for r in results)
    assert st["publishes"] >= 1
    assert st["admission"]["in_flight"] == 0 and st["admission"]["backlog"] == 0
    done = sum(v["completed"] for v in st["tenants"].values())
    assert done == 60


def test_batch_update_is_one_publish(rmat_edge_list):
    """A batch handed to insert_edges / delete_edges reaches the writer as
    one unit: one publish each (a split batch would publish odd-sized
    pieces, each a new compiled merge shape on the device)."""
    stream, svc = make_service(rmat_edge_list, backend="numpy", update_batch=4096)
    batch = np.stack([np.arange(40, 80), np.arange(140, 180)], axis=1)
    with svc:
        stamp0 = stream.vg.current_stamp
        assert svc.insert_edges(batch) == len(batch)
        svc.flush_updates()
        assert stream.vg.current_stamp == stamp0 + 1
        assert svc.delete_edges(batch[:10]) == 10
        svc.flush_updates()
        assert stream.vg.current_stamp == stamp0 + 2
    snap = stream.flat_snapshot()
    assert all(int(d) in snap.neighbors(int(s)) for s, d in batch[10:])
    assert all(int(d) not in snap.neighbors(int(s)) for s, d in batch[:10])


def test_put_many_rejects_a_piece_that_finds_no_room():
    from repro.core.streaming import UpdateQueue

    q = UpdateQueue(maxsize=4)
    assert q.put_many(np.array([[1, 2], [3, 4], [5, 6]])) == 3
    assert q.put_many(np.array([[7, 8], [9, 10]]), block=False) == 0
    st = q.stats()
    assert (st["depth"], st["rejected"], st["enqueued"]) == (3, 2, 3)
