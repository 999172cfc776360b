"""The general generator of service traffic: open-loop queries beside a
writer, read from a traffic file's parameters.

``queries`` (absent or null: a writer-only mix):

* ``rate_per_s``: offered queries per second.  A run of ``seconds``
  offers exactly ``round(rate * seconds)`` queries at the same instants
  for every seed: the gaps are exponential quantiles (so the arrivals
  look like a Poisson stream) in one fixed shuffled order, scaled to fill
  the window.
* ``kinds``: share of each query kind; the counts and their order are
  fixed with the arrivals.  Only the kinds ``check`` compares with the
  reference (``CHECKED``: ``bfs``, personalized ``pagerank``) are
  accepted: a mix with another kind needs its reference first.
* ``sources``: ``"nonisolated"``: uniform over vertices of degree >= 1
  in the graph of the seed (Graph500's rule for search keys); the seed
  changes the data a query touches, not when it comes or what kind it is.
* ``check_sample``: how many of the window's queries, drawn from the
  seed, are compared with the reference.

``writer``:

* ``batch_pairs``: undirected pairs per batch, fresh from the graph's
  own generator (exactly this many, no self loops).
* ``mode``: ``"periodic"`` offers one operation every ``period_s``
  whether or not the last has landed: an insert at even steps, and at odd
  steps a delete of the oldest live batch beyond ``max_live``.
  ``"closed"`` queues an insert and, beyond ``max_live``, a delete of the
  oldest live batch, waits on ``flush_updates`` and goes on.
* ``max_live``: inserted batches kept live.
* ``hold_versions``, ``hold_within``: closed loop only: how many of the
  first ``hold_within`` steps, drawn from the seed, hold the version they
  published, to compare its mirror with the reference afterwards.

``trace``: the share of the window, ``[start, stop]``, that a traced run
profiles.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import gen
from bench import reference as R
from bench.harness import RESULT_WAIT_S, Query, Write, log


# query kinds whose answers ``check`` compares with the reference
CHECKED = ("bfs", "pagerank")


class Plan:
    def __init__(self, traffic: dict, graph, seed: int, seconds: float):
        self.traffic = traffic
        self.graph = graph
        self.seconds = seconds
        self.writer = traffic["writer"]
        self.queries: list = []
        self.kinds: tuple = ()
        self._batch_rng = gen.rng_for(seed, gen.UPDATES)
        self._sample_rng = gen.rng_for(seed, gen.SAMPLE)
        self.live = collections.deque()
        self.ops: list = []  # window writes, in the order they were queued
        self.held: list = []  # (number of window ops applied, version)
        self.hold_steps: set = set()
        self.backlog_at_close = None
        self.generator_late_s = []
        q = traffic.get("queries")
        if q:
            self._plan_queries(q, seed)
        if self.writer["mode"] == "closed":
            k = int(self.writer.get("hold_versions", 0))
            within = int(self.writer.get("hold_within", 16))
            self.hold_steps = set(self._sample_rng.choice(
                np.arange(1, within + 1), size=min(k, within), replace=False).tolist())

    def _plan_queries(self, q: dict, seed: int) -> None:
        schedule = gen.rng_for(0, gen.QUERIES)  # the same for every seed
        rate = float(q["rate_per_s"])
        n_q = max(1, int(round(rate * self.seconds)))
        gaps = -np.log1p(-(np.arange(n_q) + 0.5) / n_q)
        gaps = schedule.permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        due *= self.seconds / gaps.sum()
        kinds = []
        shares = q["kinds"]
        unchecked = sorted(set(shares) - set(CHECKED))
        if unchecked:
            raise ValueError(f"query kinds {unchecked} have no reference in check()")
        for kind, share in shares.items():
            kinds += [kind] * int(round(share * n_q))
        kinds = (kinds + [next(iter(shares))] * n_q)[:n_q]
        kinds = [kinds[i] for i in schedule.permutation(n_q)]
        if q["sources"] != "nonisolated":
            raise ValueError(f"unknown source rule {q['sources']!r}")
        cand = np.unique(self.graph.edges[:, 0])
        sources = gen.rng_for(seed, gen.QUERIES).choice(cand, size=n_q)
        self.queries = [Query(k, int(s), float(d)) for k, s, d in zip(kinds, sources, due)]
        self.kinds = tuple(sorted(set(kinds)))
        n_s = min(int(q.get("check_sample", 16)), n_q)
        for i in self._sample_rng.choice(n_q, size=n_s, replace=False):
            self.queries[int(i)].sampled = True

    def next_batch(self) -> np.ndarray:
        return self.graph.batch(self._batch_rng, int(self.writer["batch_pairs"]))

    def release_held(self, stream) -> list:
        """Read the held versions' mirrors back and release them."""
        from bench.harness import mirror_arrays
        from repro.core.streaming import MIRROR

        out = []
        for k, v in self.held:
            out.append((k, mirror_arrays(v.aux[MIRROR])))
            stream.release(v)
        self.held = []
        return out


def plan(traffic: dict, graph, seed: int, seconds: float) -> Plan:
    return Plan(traffic, graph, seed, seconds)


def warm(p: Plan, svc, stream, state) -> None:
    """Compile every shape the window uses: the query ladder of the
    mix's kinds, and one insert and one delete of a writer batch (applied
    to the reference too)."""
    if p.kinds:
        svc.warmup(kinds=p.kinds)
    b = p.next_batch()
    svc.insert_edges(b)
    svc.flush_updates(timeout=RESULT_WAIT_S * 10)
    state.insert(b)
    svc.delete_edges(b)
    svc.flush_updates(timeout=RESULT_WAIT_S * 10)
    state.delete(b)
    svc.flush_promotions(timeout=RESULT_WAIT_S)


def _harvest(q: Query) -> None:
    """Copy a finished ticket's stamps (and, if sampled, its answer) and
    drop the ticket, so that answers do not pile up in memory."""
    t = q.ticket
    try:
        ans = t.result(timeout=0)
    except TimeoutError:
        return
    except Exception as e:  # noqa: BLE001 - a failed query is counted, not raised
        q.error = repr(e)
        ans = None
    q.submit, q.flush, q.done_t = t.t_submit, t.t_flush, t.t_done
    q.batch, q.cached = t.batch_size, t.cached
    if q.sampled and ans is not None:
        q.answer = np.array(ans)
    q.ticket = None


def _query_loop(p: Plan, svc, t0: float) -> None:
    pending = collections.deque()
    for q in p.queries:
        due = t0 + q.due
        while pending and pending[0].ticket is not None and pending[0].ticket.done():
            _harvest(pending.popleft())
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        p.generator_late_s.append(time.perf_counter() - due)
        with TraceAnnotation("bench.submit"):
            try:
                q.ticket = svc.submit(q.kind, source=q.source)
            except Exception as e:  # noqa: BLE001 - refused: counted as failed
                q.error = repr(e)
                continue
        pending.append(q)


def _put(p: Plan, svc, op: str, pairs) -> Write:
    w = Write(op, pairs, queued=time.perf_counter())
    with TraceAnnotation(f"bench.writer.{op}"):
        (svc.insert_edges if op == "insert" else svc.delete_edges)(pairs)
    p.ops.append(w)
    return w


def _insert(p: Plan, svc) -> None:
    b = p.next_batch()
    _put(p, svc, "insert", b)
    p.live.append(b)


def _delete_oldest(p: Plan, svc) -> None:
    if len(p.live) > int(p.writer["max_live"]):
        _put(p, svc, "delete", p.live.popleft())


def _periodic_writer(p: Plan, svc, t0: float, t1: float) -> None:
    period = float(p.writer["period_s"])
    step = 0
    while t0 + step * period < t1:
        time.sleep(max(0.0, t0 + step * period - time.perf_counter()))
        if step % 2 == 0:
            _insert(p, svc)
        else:
            _delete_oldest(p, svc)
        step += 1


def _closed_writer(p: Plan, svc, stream, t0: float, t1: float) -> None:
    time.sleep(max(0.0, t0 - time.perf_counter()))
    step = 0
    while time.perf_counter() < t1:
        _insert(p, svc)
        _delete_oldest(p, svc)
        with TraceAnnotation("bench.writer.flush"):
            svc.flush_updates(timeout=RESULT_WAIT_S)
        step += 1
        if step in p.hold_steps:
            p.held.append((len(p.ops), stream.acquire()))


def drive(p: Plan, svc, stream, rec, tracer) -> None:
    """The measured window: queries and writer on threads of their own,
    the profiler started and stopped from this one."""
    listen = stream.on_publish(lambda v: rec.publishes.append((time.perf_counter(), v.stamp)))
    p._unlisten = listen
    t0 = time.perf_counter() + 0.05
    t1 = t0 + p.seconds
    rec.t0, rec.t1 = t0, t1
    threads = []
    if p.queries:
        threads.append(threading.Thread(target=_query_loop, args=(p, svc, t0),
                                        name="bench-queries"))
    if p.writer["mode"] == "periodic":
        threads.append(threading.Thread(target=_periodic_writer, args=(p, svc, t0, t1),
                                        name="bench-writer"))
    else:
        threads.append(threading.Thread(target=_closed_writer, args=(p, svc, stream, t0, t1),
                                        name="bench-writer"))
    with TraceAnnotation("bench.window"):
        for th in threads:
            th.start()
        if tracer.on:
            a, b = p.traffic.get("trace", [0.3, 0.8])
            time.sleep(max(0.0, t0 + a * p.seconds - time.perf_counter()))
            tracer.start()
            time.sleep(max(0.0, t0 + b * p.seconds - time.perf_counter()))
            tracer.stop()
            rec.trace_t0, rec.trace_t1 = tracer.t0, tracer.t1
        time.sleep(max(0.0, t1 - time.perf_counter()))
        # update rows queued but not yet taken by the service's writer
        p.backlog_at_close = len(svc.updates)
        for th in threads:
            th.join()
    rec.queries = p.queries
    rec.writes = p.ops


def settle(p: Plan, svc, rec) -> None:
    """After the window: wait for every query and write due in it (a
    minute past the close at most), then for promotions."""
    end = rec.t1 + RESULT_WAIT_S
    for q in p.queries:
        if q.ticket is None:
            continue
        try:
            q.ticket.result(timeout=max(0.0, end - time.perf_counter()))
        except TimeoutError:
            q.error = "no answer a minute past the window"
            q.ticket = None
            continue
        except Exception:  # noqa: BLE001 - recorded by _harvest
            pass
        _harvest(q)
    try:
        svc.flush_updates(timeout=max(1.0, end - time.perf_counter()))
    except TimeoutError:
        log("settle", writer="still busy a minute past the window")
    p._unlisten()
    # one publish per operation, in the order queued (update_batch is one
    # batch): an operation is done when its publish was seen
    after = sorted(t for t, _ in rec.publishes if t >= rec.t0)
    for i, w in enumerate(p.ops):
        w.done = after[i] if i < len(after) else float("inf")
    svc.flush_promotions(timeout=RESULT_WAIT_S)
    late = p.generator_late_s
    log("window", queries=len(p.queries), writes=len(p.ops),
        generator_late_max_s=max(late) if late else 0.0,
        generator_late_mean_s=float(np.mean(late)) if late else 0.0,
        writer_backlog_at_close=p.backlog_at_close)


# -- the check -----------------------------------------------------------------


def _version_at(pub_times: np.ndarray, t: float) -> int:
    """How many window publishes had been seen by ``t``."""
    return int(np.searchsorted(pub_times, t, side="right"))


def check(p: Plan, rec, state, final: dict, held: list, controls: bool = False) -> dict:
    """Compare the sampled answers, the held mirrors and the final mirror
    with the reference; returns ``{"numbers", "attempted", "failed"}``
    and, with ``controls``, the readings of the lower-precision controls."""
    pub = np.asarray(sorted(t for t, _ in rec.publishes if t >= rec.t0))
    limits = rec.cell.limits
    window_q = [q for q in p.queries]
    failed_q = [q for q in window_q if q.error is not None or q.done_t is None]
    failed_w = [w for w in p.ops if not np.isfinite(w.done)]
    failed = len(failed_q) + len(failed_w)
    if failed:
        log("check", failed_queries=len(failed_q), failed_writes=len(failed_w),
            first_error=next((q.error for q in failed_q if q.error), None),
            publishes_seen=len(pub))
    attempted = len(window_q) + len(p.ops)
    # candidate versions of each sampled query: current at some instant
    # between its submit and its flush (one more for the listener's lag)
    todo = {}
    for q in window_q:
        if not q.sampled or q.answer is None:
            continue
        lo = _version_at(pub, q.submit)
        hi = min(_version_at(pub, q.flush) + 1, len(p.ops))
        for j in range(lo, hi + 1):
            todo.setdefault(j, []).append(q)
    best_bfs, best_pr = {}, {}
    held_at = dict(held)
    mirror_bad = 0
    for j in range(len(p.ops) + 1):
        if j in todo or j in held_at:
            snap = state.snapshot()
            if j in held_at:
                m = held_at[j]
                mirror_bad += R.mirror_diff(snap, m["keys"], m["weights"], m["offsets"])
            qs = todo.get(j, [])
            bfs = [q for q in qs if q.kind == "bfs" and best_bfs.get(id(q), 1) != 0]
            if bfs:
                depths = R.bfs_depths(snap, [q.source for q in bfs])
                for q, d in zip(bfs, depths):
                    bad = R.check_bfs(snap, q.source, q.answer, d)
                    best_bfs[id(q)] = min(best_bfs.get(id(q), bad), bad)
            prs = [q for q in qs if q.kind == "pagerank"]
            if prs:
                srcs = [q.source for q in prs]
                want = R.pagerank(snap, srcs)
                lows = {"high": R.pagerank(snap, srcs, "high")} if controls else {}
                for i, q in enumerate(prs):
                    gaps = R.pagerank_gaps(q.answer, want[i])
                    if gaps["max"] < best_pr.get(id(q), ({"max": np.inf},))[0]["max"]:
                        best_pr[id(q)] = (gaps, {k: R.pagerank_gaps(v[i], want[i])
                                                 for k, v in lows.items()})
        if j < len(p.ops):
            w = p.ops[j]
            (state.insert if w.op == "insert" else state.delete)(w.pairs)
    snap = state.snapshot()
    mirror_bad += R.mirror_diff(snap, final["keys"], final["weights"], final["offsets"])
    readings = {"mirror_diff": mirror_bad}
    ctrl = {}
    if p.queries:
        n_bfs = sum(1 for q in window_q if q.sampled and q.kind == "bfs")
        n_pr = sum(1 for q in window_q if q.sampled and q.kind == "pagerank")
        readings["bfs_bad_vertices"] = sum(best_bfs.values())
        for k in ("max", "l1", "rel"):
            readings[f"pagerank_{k}_gap"] = max(
                [g[k] for g, _ in best_pr.values()], default=0.0)
            vals = [c["high"][k] for _, c in best_pr.values() if c]
            if vals:
                ctrl[f"pagerank_{k}_gap.high"] = max(vals)
        log("check", sampled_bfs=n_bfs, sampled_pagerank=n_pr,
            compared_bfs=len(best_bfs), compared_pagerank=len(best_pr),
            versions_compared=len(todo))
    log("check", readings=readings)
    numbers = {k: {"value": readings[k], "limit": v} for k, v in limits.items()}
    out = {"numbers": numbers, "attempted": attempted, "failed": failed}
    if controls:
        out["controls"] = ctrl
        if p.ops:  # a lost acknowledged write: the last one left out
            last = p.ops[-1]
            (state.delete if last.op == "insert" else state.insert)(last.pairs)
            lost = state.snapshot()
            out["controls"]["mirror_diff.lost_write"] = R.mirror_diff(
                lost, final["keys"], final["weights"], final["offsets"])
    return out
