#!/usr/bin/env python3
"""On-chip smoke run of the graph-streaming service.

    python chip_smoke.py [--scale N] [--four-chips]

Drives the default serving path once at a deployment size, through the
entry points a user calls (``AspenStream`` -> ``GraphQueryService``),
and checks every answer against the numpy reference engine over the
same version's C-tree snapshot.

* Phase A, default path: rMAT (a=0.5, b=c=0.1, seed 1) at ``--scale``
  (default 20: 2^20 vertices, 2^21 generated edges symmetrized to about
  4M directed edges) with integer weights 1..16, the raw device mirror,
  a live writer publishing 5 insert and 2 delete batches of 2^14 edges,
  64 mixed bfs/sssp/pagerank/cc queries from two tenants and a pinned
  session.
* Phase B, compressed path: ``compressed=True`` at scale 14 (the
  largest rMAT the compressed pool holds without spilling); PageRank
  runs through the chunked Pallas kernel.
* ``--four-chips`` runs only the sharded phase: the same graph on
  ``AspenStream(mirror="sharded", n_shards=4)``, publishes that include
  a rebalance, and bfs/sssp/cc/pagerank through ``engine("sharded")``.

The script refuses any platform but TPU and exits non-zero on any
failure.  Sizes, timings, memory and the service's counters go to
earlier lines; the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The phase functions run on any backend, so tests call them on the CPU
at a tiny scale.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

UPDATE_BATCH = 1 << 14
N_INSERT_BATCHES = 5
N_DELETE_BATCHES = 2
N_QUERIES = 64
PARITY_SOURCES = 4
# largest lane batch tried: every power of two up to it is one more cold
# compile of each batched driver (20-35 s each for a v5e at scale 20)
MAX_BATCH_CAP = 2
PR_ATOL = 1e-6  # DESIGN.md §7: f32 Pallas reduce vs f64 numpy
COMPRESSED_SCALE = 14
RESULT_TIMEOUT_S = 900.0


class CheckFailed(Exception):
    """A smoke check that did not hold."""


def check(cond, msg: str) -> None:
    """Raise unless ``cond`` (an ``assert`` would vanish under ``-O``)."""
    if not cond:
        raise CheckFailed(msg)


def log(phase: str, **kv) -> None:
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {body}", flush=True)


@contextlib.contextmanager
def span(spans: dict, name: str):
    """Record the wall-clock seconds of the block as ``spans[name]``."""
    t0 = time.perf_counter()
    yield
    spans[name] = time.perf_counter() - t0


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def make_graph(scale: int, seed: int = 1):
    """Symmetric rMAT edge list (data/rmat.py parameters) and one integer
    weight in 1..16 per undirected pair (both directions agree)."""
    import numpy as np

    from repro.data.rmat import rmat_edges, symmetrize

    edges = symmetrize(rmat_edges(scale, 1 << (scale + 1), seed=seed))
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    weights = (1 + (lo * 2654435761 + hi * 40503) % 16).astype(np.float64)
    return edges, weights


def update_batches(scale: int, edges, seed: int = 7):
    """Insert batches (fresh rMAT pairs) and delete batches (existing
    pairs of ``edges`` that no insert touches), 2^14 undirected pairs
    each, at most; rows are (src, dst) with src < dst."""
    import numpy as np

    from repro.data.rmat import rmat_edges

    rng = np.random.default_rng(seed)
    inserts = []
    for i in range(N_INSERT_BATCHES):
        b = rmat_edges(scale, UPDATE_BATCH, seed=seed + 1 + i)
        b = np.stack([b.min(axis=1), b.max(axis=1)], axis=1)
        inserts.append(np.unique(b[b[:, 0] != b[:, 1]], axis=0))
    ins_keys = np.unique(np.concatenate([_key(b) for b in inserts]))
    und = edges[edges[:, 0] < edges[:, 1]]
    und = und[~np.isin(_key(und), ins_keys)]
    pick = rng.choice(und.shape[0], size=N_DELETE_BATCHES * UPDATE_BATCH,
                      replace=False) if und.shape[0] >= N_DELETE_BATCHES * UPDATE_BATCH \
        else rng.permutation(und.shape[0])
    deletes = np.array_split(und[pick], N_DELETE_BATCHES)
    return inserts, deletes


def _key(pairs):
    return (pairs[:, 0].astype("int64") << 32) | pairs[:, 1].astype("int64")


def build_stream(scale: int, edges, weights, **stream_kw):
    """Host C-tree build, then the stream and its device mirror; returns
    (stream, tree build seconds, mirror build seconds)."""
    from repro.core import graph as G
    from repro.core.streaming import AspenStream

    t0 = time.perf_counter()
    g = G.build_graph(1 << scale, edges, weights=weights)
    t_tree = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream = AspenStream(g, **stream_kw)
    return stream, t_tree, time.perf_counter() - t0


def reference_engine(version):
    """The plain reference: numpy engine over the version's C-tree."""
    from repro.core import graph as G
    from repro.core.traversal import make_engine

    return make_engine(G.flat_snapshot(version.graph), backend="numpy")


def check_parity(tag, answers, ref, sources):
    """``answers`` maps kind -> device answers at ``ref``'s version:
    bfs parent rows and sssp rows per source, the global pagerank row
    and cc labels.  BFS depths, SSSP distances and CC labels must match
    exactly, PageRank within PR_ATOL."""
    import numpy as np

    from repro.core.traversal import algorithms as talg

    _, want_depths = talg.bfs_multi(ref, sources)
    for i, s in enumerate(sources):
        got = talg.bfs_depths(answers["bfs"][i], int(s))
        check(np.array_equal(got, want_depths[i]), f"{tag}: bfs depths differ (source {s})")
    want_sssp = talg.sssp_multi(ref, sources)
    for i, s in enumerate(sources):
        check(np.array_equal(np.asarray(answers["sssp"][i], np.float64),
                             np.asarray(want_sssp[i], np.float64)),
              f"{tag}: sssp distances differ (source {s})")
    want_cc = np.asarray(talg.connected_components(ref), np.int64)
    check(np.array_equal(np.asarray(answers["cc"], np.int64), want_cc),
          f"{tag}: cc labels differ")
    want_pr = np.asarray(talg.pagerank_multi(ref)[0], np.float64)
    got_pr = np.asarray(answers["pagerank"], np.float64).reshape(-1)
    err = float(np.abs(got_pr - want_pr).max())
    check(np.isfinite(got_pr).all() and err <= PR_ATOL, f"{tag}: pagerank max err {err}")
    log(tag, parity="ok", bfs_sources=len(sources), sssp_sources=len(sources),
        cc_components=int(np.unique(want_cc).size), pagerank_max_abs_err=err)


def kernel_in_program(fn, *args) -> bool:
    """True if the compiled program of ``fn(*args)`` holds a Pallas TPU
    kernel; on other backends Pallas runs in interpret mode and the
    question does not apply (returns None)."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def device_memory(devices=None):
    import jax

    out = []
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats()
        out.append(None if st is None else dict(st))
    return out


# ---------------------------------------------------------------------------
# Phase A: the default serving path
# ---------------------------------------------------------------------------


def pick_max_batch(eng, cap: int = MAX_BATCH_CAP):
    """Largest power-of-two lane batch (<= cap) whose ``bfs_batch``
    program fits in half the device memory left free, read from the
    compiled program's ``memory_analysis()``.  Returns (B, report)."""
    import jax
    import jax.numpy as jnp

    from repro.core.traversal import jax_backend as jb

    stats = device_memory()[0]
    free = None
    if stats and "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    report = {}
    B = cap
    while True:
        t0 = time.perf_counter()
        compiled = jb.bfs_batch.lower(
            eng.g, eng.aux, jnp.zeros((B,), jnp.int32),
            ids_budget=eng._auto_ids_budget, edge_budget=eng._auto_edge_budget,
        ).compile()
        ma = compiled.memory_analysis()
        need = None
        if ma is not None:
            need = ma.temp_size_in_bytes + ma.output_size_in_bytes
        report[B] = {"compile_s": time.perf_counter() - t0, "temp_plus_out_bytes": need}
        if free is None or need is None or need <= free // 2 or B == 1:
            return B, {"free_bytes": free, "bfs_batch": report}
        B //= 2


def phase_default(scale: int, seed: int = 1) -> dict:
    import numpy as np

    from repro.core import flat_graph as fg
    from repro.core.flat_ctree import grown_capacity
    from repro.core.streaming import MIRROR
    from repro.core.traversal import TRACES
    from repro.kernels import ops as kops
    from repro.serve.graph import GraphQueryService

    tag = "A"
    spans = {}
    edges, weights = make_graph(scale, seed)
    inserts, deletes = update_batches(scale, edges)
    # the pool's capacity is a static shape of every compiled query:
    # reserve room for the writer's inserts so serving never recompiles
    cap = grown_capacity(edges.shape[0] + 2 * sum(b.shape[0] for b in inserts))
    stream, t_tree, t_mirror = build_stream(scale, edges, weights, edge_capacity=cap)
    n = 1 << scale
    log(tag, vertices=n, directed_edges=int(edges.shape[0]), edge_capacity=cap,
        host_tree_build_s=t_tree, mirror_build_s=t_mirror)

    with span(spans, "pick_max_batch"):
        eng0 = stream.engine("jax")
        max_batch, mb_report = pick_max_batch(eng0)
    log(tag, max_batch=max_batch, memory_analysis=json.dumps(mb_report))
    pallas = kernel_in_program(
        lambda d, w, m: kops.segment_sum_weighted(d, w, m, n),
        eng0.aux.dst_sorted, eng0.aux.w_by_dst,
        np.zeros((eng0.aux.dst_sorted.shape[0], 1), np.float32),
    )
    log(tag, pagerank_reduce_is_pallas_kernel=pallas)
    check(pallas in (None, True), "raw PageRank reduce holds no Pallas kernel")
    del eng0

    svc = GraphQueryService(
        stream, backend="jax", max_batch=max_batch,
        update_batch=2 * UPDATE_BATCH, update_queue_size=4 * UPDATE_BATCH,
        tenant_weights={"alpha": 3.0, "beta": 1.0},
    )
    kinds = ("bfs", "sssp", "pagerank", "cc")
    rng = np.random.default_rng(seed)
    with svc:
        with span(spans, "warmup"):
            svc.warmup(kinds=kinds)
        traces_after_warmup = TRACES.count
        log(tag, warmup_s=spans["warmup"], traces_in_warmup=traces_after_warmup)

        pinned = svc.session(tenant="alpha")
        s0 = int(rng.integers(n))
        pinned_before = pinned.query("bfs", source=s0).result(timeout=RESULT_TIMEOUT_S)
        # hot entries (hit at least once) are what carry-forward promotes
        # across each publish: make one per kind that promotes
        for kind in kinds:
            for _ in range(2):
                svc.query(kind, source=None if kind == "cc" else s0,
                          timeout=RESULT_TIMEOUT_S)

        def writer():
            for b in inserts:
                svc.insert_edges(b)
                svc.flush_updates(timeout=RESULT_TIMEOUT_S)
            for b in deletes:
                svc.delete_edges(b)
                svc.flush_updates(timeout=RESULT_TIMEOUT_S)

        werr = []

        def run_writer():
            try:
                writer()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                werr.append(e)

        with span(spans, "live"):
            wt = threading.Thread(target=run_writer, name="smoke-writer")
            wt.start()
            tickets = []
            for i in range(N_QUERIES):
                kind = kinds[i % len(kinds)]
                src = None if kind == "cc" else int(rng.integers(n))
                tickets.append(svc.submit(kind, source=src,
                                          tenant="alpha" if i % 2 else "beta"))
                time.sleep(0.01)
            for t in tickets:
                a = np.asarray(t.result(timeout=RESULT_TIMEOUT_S))
                check(a.shape == (n,), f"{t.kind} answer shape {a.shape}")
                if t.kind in ("pagerank", "sssp"):
                    check(not np.isnan(a).any(), f"{t.kind} answer has NaN")
            wt.join()
        if werr:
            raise werr[0]
        svc.flush_updates(timeout=RESULT_TIMEOUT_S)
        svc.flush_promotions(timeout=RESULT_TIMEOUT_S)
        lat = sorted(t.latency_s for t in tickets)
        log(tag, live_s=spans["live"], queries=len(tickets),
            publishes=svc.stats()["publishes"],
            latency_p50_s=lat[len(lat) // 2], latency_max_s=lat[-1])

        # the pinned session's answer does not move across publishes
        pinned_after = pinned.query("bfs", source=s0).result(timeout=RESULT_TIMEOUT_S)
        check(pinned.stamp < stream.vg.current_stamp, "no publish landed")
        check(np.array_equal(pinned_before, pinned_after), "pinned session answer changed")
        pinned.close()

        # parity at one version, every kind, against the numpy reference
        with span(spans, "parity"):
            with svc.session(tenant="alpha") as sess:
                srcs = [int(s) for s in rng.choice(n, PARITY_SOURCES, replace=False)]
                answers = {
                    "bfs": [sess.query("bfs", source=s) for s in srcs],
                    "sssp": [sess.query("sssp", source=s) for s in srcs],
                    "pagerank": sess.query("pagerank"),
                    "cc": sess.query("cc"),
                }
                answers = {
                    k: ([t.result(timeout=RESULT_TIMEOUT_S) for t in v]
                        if isinstance(v, list) else v.result(timeout=RESULT_TIMEOUT_S))
                    for k, v in answers.items()
                }
                ref = reference_engine(sess.version)
                check_parity(tag, answers, ref, srcs)
                mirror = sess.version.aux[MIRROR]
                check(int(np.asarray(mirror.m)) == ref.m, "device edge count differs")

        # every acknowledged write reads back on the device
        ins = np.concatenate(inserts)
        dels = np.concatenate(deletes)
        ins = ins[~np.isin(_key(ins), _key(dels))]
        cur = stream.flat_graph()
        for pairs, want in ((ins, True), (dels, False)):
            both = np.concatenate([pairs, pairs[:, ::-1]])
            got = np.asarray(fg.has_edge(cur, both[:, 0], both[:, 1]))
            check((got == want).all(),
                  f"{int((got != want).sum())} acknowledged {'inserts' if want else 'deletes'} not on the device")
        log(tag, acked_inserts_on_device=int(ins.shape[0]) * 2,
            acked_deletes_gone=int(dels.shape[0]) * 2)

        st = svc.stats()
        retraces = sum(l["retraces"] for l in st["lanes"].values())
        cache = st["cache"]
        log(tag, retraces_after_warmup=retraces,
            promotion_errors=cache["promotion_errors"],
            last_promotion_error=cache["last_promotion_error"],
            cache_hits=cache["hits"], cache_misses=cache["misses"],
            promoted_incremental=cache["promoted_incremental"],
            promoted_full=cache["promoted_full"],
            lanes=json.dumps({k: {"flushed_batches": v["flushed_batches"],
                                  "errors": v["errors"], "retraces": v["retraces"],
                                  "deadline_misses": v["deadline_misses"]}
                              for k, v in st["lanes"].items()}))
        check(retraces == 0, f"{retraces} retraces after warmup")
        lane_errors = sum(l["errors"] for l in st["lanes"].values())
        check(lane_errors == 0, f"{lane_errors} lane dispatch errors")
        check(cache["promotion_errors"] == 0,
              f"promotion errors: {cache['promotion_errors']} ({cache['last_promotion_error']})")
        check(cache["promoted_incremental"] + cache["promoted_full"] > 0,
              "carry-forward promoted nothing")
    mem = device_memory()[0]
    log(tag, spans=json.dumps(spans),
        peak_bytes_in_use=None if mem is None else mem.get("peak_bytes_in_use"))
    return {"spans": spans, "max_batch": max_batch, "retraces": retraces}


# ---------------------------------------------------------------------------
# Phase B: the compressed pool
# ---------------------------------------------------------------------------


def phase_compressed(scale: int = COMPRESSED_SCALE, seed: int = 2) -> dict:
    import numpy as np

    from repro.core.traversal import algorithms as talg
    from repro.core.traversal import jax_backend as jb

    tag = "B"
    spans = {}
    edges, weights = make_graph(scale, seed)
    stream, t_tree, t_mirror = build_stream(scale, edges, weights, compressed=True)
    n = 1 << scale
    log(tag, vertices=n, directed_edges=int(edges.shape[0]),
        host_tree_build_s=t_tree, mirror_build_s=t_mirror)
    inserts, _ = update_batches(scale, edges, seed=seed + 10)
    for step in range(2):
        v = stream.acquire()  # no writer runs here: the engine is v's
        try:
            eng = stream.engine("jax")
            check(isinstance(eng, jb.CompressedEngine), type(eng).__name__)
            if step == 0:
                pallas = kernel_in_program(
                    lambda caux, x: jb._edge_map_reduce_compressed(
                        caux, x, n=eng.n, dtype=eng.ops.float_dtype),
                    eng.caux, np.zeros((1, eng.n), np.float32),
                )
                log(tag, pagerank_reduce_is_pallas_kernel=pallas,
                    resident_bytes=eng.resident_nbytes)
                check(pallas in (None, True), "compressed reduce holds no Pallas kernel")
            with span(spans, f"pagerank_{step}"):
                got = np.asarray(talg.pagerank_multi(eng)[0], np.float64)
            want = np.asarray(talg.pagerank_multi(reference_engine(v))[0], np.float64)
            err = float(np.abs(got - want).max())
            check(np.isfinite(got).all() and err <= PR_ATOL, f"B: pagerank max err {err}")
            log(tag, version=v.stamp, pagerank_parity="ok", pagerank_max_abs_err=err)
        finally:
            stream.release(v)
        if step == 0:
            with span(spans, "insert"):
                stream.insert_edges(inserts[0])
    log(tag, spans=json.dumps(spans))
    return {"spans": spans}


# ---------------------------------------------------------------------------
# four chips: the sharded mirror
# ---------------------------------------------------------------------------


def phase_sharded(scale: int, n_shards: int = 4, seed: int = 1) -> dict:
    import jax
    import numpy as np

    from repro.core.streaming import SHARDED_MIRROR
    from repro.core.traversal import algorithms as talg

    tag = "S"
    spans = {}
    devices = jax.devices()[: min(n_shards, jax.device_count())]
    before = device_memory(devices)
    edges, weights = make_graph(scale, seed)
    stream, t_tree, t_mirror = build_stream(
        scale, edges, weights, mirror="sharded", n_shards=n_shards)
    n = 1 << scale
    log(tag, vertices=n, directed_edges=int(edges.shape[0]), n_shards=n_shards,
        host_tree_build_s=t_tree, mirror_build_s=t_mirror)

    v = stream.acquire()
    pool = v.aux[SHARDED_MIRROR].pool
    stream.release(v)
    held = {d.id for d in pool.data.sharding.device_set}
    after = device_memory(devices)
    grown = [None if b is None else a["bytes_in_use"] - b["bytes_in_use"]
             for b, a in zip(before, after)]
    log(tag, pool_devices=sorted(held), bytes_in_use_growth=grown)
    check(len(held) == len(devices), f"pool spans {len(held)} devices, not {len(devices)}")
    check(all(g is None or g > 0 for g in grown), f"a device holds no pool bytes: {grown}")

    inserts, deletes = update_batches(scale, edges)
    with span(spans, "publishes"):
        stream.insert_edges(inserts[0])
        stream.delete_edges(deletes[0])
        lo_before = np.asarray(pool.lo)
        stream.rebalance()
        stream.insert_edges(inserts[1])
    v = stream.acquire()
    try:
        pool = v.aux[SHARDED_MIRROR].pool
        counts = np.asarray(pool.n)
        log(tag, publishes=v.stamp, shard_counts=counts.tolist(),
            shard_lo_moved=bool((np.asarray(pool.lo) != lo_before).any()))
        eng = stream.engine("sharded")  # no writer runs here: v's engine
        rng = np.random.default_rng(seed)
        srcs = [int(s) for s in rng.choice(n, PARITY_SOURCES, replace=False)]
        with span(spans, "queries"):
            answers = {
                "bfs": talg.bfs_multi(eng, srcs)[0],
                "sssp": talg.sssp_multi(eng, srcs),
                "cc": talg.connected_components(eng),
                "pagerank": talg.pagerank_multi(eng)[0],
            }
        with span(spans, "parity"):
            check_parity(tag, answers, reference_engine(v), srcs)
    finally:
        stream.release(v)
    log(tag, spans=json.dumps(spans))
    return {"spans": spans, "devices": len(held)}


# ---------------------------------------------------------------------------


def device_check(count: int) -> dict:
    """Refuse anything but a TPU with ``count`` chips; compiled kernels."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    from repro.kernels import ops as kops

    check(kops._interpret() is False, "Pallas would run in interpret mode")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=20,
                    help="log2 of the vertex count of the main graph (default 20)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase on four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro" / "__init__.py").is_file():
        print(f"chip_smoke: the repro package is missing under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import repro  # noqa: F401  (x64 on, as the program runs)

    device = device_check(4 if args.four_chips else 1)
    from repro import compile_cache

    log("setup", device=json.dumps(device), scale=args.scale,
        compile_cache=compile_cache.enable())
    t0 = time.perf_counter()
    if args.four_chips:
        phase_sharded(args.scale, n_shards=4)
    else:
        phase_default(args.scale)
        phase_compressed()
    log("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
