"""Streaming analytics: the paper's §7.3 experiment as an application.

Concurrent writer (edge stream) + reader (BFS queries) on one
AspenStream, then the SAME analytics (BFS / PageRank / CC) through the
backend-unified traversal engine on both substrates — the numpy
FlatSnapshot engine and the jit-compiled FlatGraph engine — with a
parity + speed report.

    PYTHONPATH=src python examples/streaming_analytics.py
"""
import time

import numpy as np

from repro import compile_cache
from repro.core import flat_graph as fg
from repro.core import graph as G
from repro.core.streaming import AspenStream, make_update_stream, run_concurrent
from repro.core.traversal import make_engine
from repro.core.traversal import algorithms as talg
from repro.data.rmat import rmat_edges, symmetrize

compile_cache.enable()  # persistent XLA cache, before the first compile

n = 4096
edges = symmetrize(rmat_edges(12, 80_000, seed=0))
keep, stream_updates = make_update_stream(edges, 5_000, seed=1)

# --- faithful level: concurrent updates + global queries -------------------
# mirror=False isolates the paper's tree-level experiment; the resident
# FlatGraph mirror is demonstrated below.
g0 = G.build_graph(n, keep)
s = AspenStream(g0, mirror=False)
src = int(edges[0, 0])
stats = run_concurrent(
    s, stream_updates,
    query_fn=lambda snap: talg.bfs(make_engine(snap), src),
    duration_s=3.0, batch_size=10,
)
print("== faithful (tree-of-C-trees) level ==")
print(f"update throughput : {stats.updates_per_sec:,.0f} directed edges/s")
print(f"update latency    : {stats.mean_update_latency_s * 1e6:.1f} us/batch")
print(f"query latency     : {stats.query_latency_concurrent_s * 1e3:.2f} ms concurrent "
      f"vs {stats.query_latency_isolated_s * 1e3:.2f} ms isolated "
      f"({100 * (stats.query_latency_concurrent_s / stats.query_latency_isolated_s - 1):+.1f}%)")

# --- TPU-native level: jit streaming step --------------------------------
import jax

gf = fg.from_edges(n, keep)
ins_np = stream_updates[stream_updates[:, 2] == 0][:1024, :2]
# both directions, matching AspenStream.insert_edges(symmetric=True)
batch_np = np.concatenate([ins_np, ins_np[:, ::-1]])
batch = fg.batch_from_edges(batch_np)
cap = gf.edge_capacity * 2
ins = jax.jit(lambda g, b: fg.insert_edges(g, b, cap))
gf2 = jax.block_until_ready(ins(gf, batch))  # compile
t0 = time.perf_counter()
for _ in range(20):
    gf2 = ins(gf, batch)
jax.block_until_ready(gf2)
dt = (time.perf_counter() - t0) / 20
print("\n== TPU-native (flat pool) level ==")
print(f"batch insert      : {batch_np.shape[0] / dt:,.0f} edges/s (jit rank-merge)")

# --- dual representation: resident mirror, version-pinned engines ---------
# Every version the stream publishes pairs the tree with a FlatGraph
# mirror kept current by the same jit rank-merge — so the time-to-first-
# query after a batch is the merge + one jit engine refresh, not an O(m)
# host rebuild (DESIGN.md §6).
sd = AspenStream(g0)  # mirror=True: every version carries the flat pool
ins_all = stream_updates[stream_updates[:, 2] == 0]
warm, batch2 = ins_all[1024:1124, :2], ins_all[1124:1224, :2]
sd.insert_edges(warm)  # warm: compile merge + engine refresh at this shape
talg.bfs(sd.engine("jax"), src)
t0 = time.perf_counter()
sd.insert_edges(batch2)
talg.bfs(sd.engine("jax"), src)
ttfq = time.perf_counter() - t0
e_cached = sd.engine("jax")
print(f"time-to-first-query after a {batch2.shape[0]}-edge batch: {ttfq * 1e3:.1f} ms "
      f"(engine cached per version: {e_cached is sd.engine('jax')})")

# --- unified traversal engine: same algorithms, both backends -------------
# Callers pick the backend at snapshot time: ``AspenStream.engine("numpy")``
# (or "jax") on the stream, or ``make_engine(FlatGraph)`` on the flat
# pool.  Parity is checked on one shared snapshot (the post-insert pool).
eng_jx = make_engine(gf2)
eng_np = make_engine(G.flat_snapshot(G.build_graph(n, fg.to_edge_array(gf2))))

print("\n== unified edgeMap engine: numpy vs jax parity + speed ==")
print(f"{'algorithm':<12}{'numpy ms':>10}{'jax ms':>10}  parity")
for name, run, check in [
    ("bfs", lambda e: talg.bfs(e, src),
     lambda a, b: np.array_equal(talg.bfs_depths(a, src), talg.bfs_depths(b, src))),
    ("pagerank", lambda e: talg.pagerank(e, iters=5),
     lambda a, b: np.allclose(a, b, atol=1e-5)),
    ("cc", lambda e: talg.connected_components(e), np.array_equal),
]:
    run(eng_jx)  # warm the jit cache
    run(eng_np)  # warm the CSR caches (symmetric warm-up for fair timing)
    t0 = time.perf_counter(); out_j = run(eng_jx); t_j = time.perf_counter() - t0
    t0 = time.perf_counter(); out_n = run(eng_np); t_n = time.perf_counter() - t0
    print(f"{name:<12}{t_n * 1e3:>10.1f}{t_j * 1e3:>10.1f}  {bool(check(out_n, out_j))}")

# --- property graph: weighted streaming + weighted analytics --------------
# Per-edge values are first-class (DESIGN.md §8): the stream carries a
# weight per inserted edge through BOTH substrates (tree weight-map +
# mirror value array, published atomically), and the same algorithm
# texts run weighted — SSSP over the (min, +) semiring, PageRank over
# the weighted (+, x) semiring — on either backend.
lo, hi = np.minimum(keep[:, 0], keep[:, 1]), np.maximum(keep[:, 0], keep[:, 1])
wk = ((lo * 1000003 + hi) % 7 + 1).astype(np.float64)  # symmetric, integer
sw = AspenStream(G.build_graph(n, keep, weights=wk))
ins_w = stream_updates[stream_updates[:, 2] == 0][:200, :2]
sw.insert_edges(ins_w, weights=np.ones(ins_w.shape[0]))  # unit-weight batch
print("\n== weighted serve path (SSSP / weighted PageRank) ==")
d_batch = sw.query_batch(np.array([src, int(keep[1, 0])]), kind="sssp")
d_np = talg.sssp(sw.engine("numpy"), src)
print(f"sssp: batched-jax == serial-numpy: {np.array_equal(d_batch[0], d_np)} "
      f"(reached {np.isfinite(d_np).sum()} vertices, "
      f"max dist {d_np[np.isfinite(d_np)].max():g})")
wpr_j = talg.weighted_pagerank(sw.engine("jax"), iters=5)
wpr_n = talg.weighted_pagerank(sw.engine("numpy"), iters=5)
print(f"weighted pagerank: parity {np.allclose(wpr_j, wpr_n, atol=1e-5)}, "
      f"mass {wpr_n.sum():.6f}")
