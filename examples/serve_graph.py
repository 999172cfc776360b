"""Serving walkthrough: GraphQueryService with two tenants and a
pinned session (DESIGN.md §13).

    PYTHONPATH=src python examples/serve_graph.py

The service turns one live AspenStream into a multi-tenant query
server: a writer thread publishes batched edge updates while client
queries coalesce into per-kind lanes, flush as power-of-two batches
against the freshest version, and tenants share throughput by weight.
A Session pins the version current at open time, so a sequence of
reads is strictly serializable — the paper's snapshot guarantee,
stretched across multiple queries.
"""
import threading
import time

import numpy as np

from repro import compile_cache
from repro.core import graph as G
from repro.core.streaming import AspenStream
from repro.data.rmat import rmat_edges, symmetrize
from repro.serve.graph import GraphQueryService

compile_cache.enable()  # persistent XLA cache, before the first compile

# --- 1. A graph, a stream, a service ---------------------------------------
n = 1 << 10
edges = symmetrize(rmat_edges(10, 15_000, seed=7))
stream = AspenStream(G.build_graph(n, edges))

# alice pays for 3x bob's share; lanes coalesce up to 16 queries;
# work_conserving flushes whatever is pending whenever the executor
# frees up (continuous batching), with the 250ms SLO as the backstop
service = GraphQueryService(
    stream,
    backend="jax",
    max_batch=16,
    default_deadline_s=0.25,
    tenant_weights={"alice": 3.0, "bob": 1.0},
    work_conserving=True,
)
service.start()
service.warmup(kinds=("bfs", "sssp"))  # pre-compile the pow2 trace ladder
print(f"service up: backend={service.backend}, version {stream.vg.current_stamp}")

# --- 2. A continuous update stream on the writer thread --------------------
stop = threading.Event()


def update_feed():
    rng = np.random.default_rng(1)
    while not stop.is_set():
        for _ in range(20):  # bursts amortize into one publish each
            service.enqueue_update(int(rng.integers(n)), int(rng.integers(n)))
        time.sleep(0.05)


feeder = threading.Thread(target=update_feed)
feeder.start()

# --- 3. Two tenants querying concurrently ----------------------------------
rng = np.random.default_rng(2)
tickets = []
for i in range(60):
    tenant = "alice" if i % 4 else "bob"
    kind = "bfs" if i % 2 else "sssp"
    tickets.append(service.submit(kind, source=int(rng.integers(n)), tenant=tenant))
answers = [t.result(timeout=30) for t in tickets]
lat = sorted(t.latency_s for t in tickets)
print(f"60 mixed queries served: p50 {lat[30] * 1e3:.1f} ms, "
      f"p99 {lat[-1] * 1e3:.1f} ms, "
      f"largest flush {max(t.batch_size for t in tickets)} requests")

# --- 4. A pinned session: strictly-serializable multi-query reads ----------
with service.session(tenant="alice") as sess:
    print(f"session pinned at version {sess.stamp}")
    bfs_before = sess.query("bfs", source=5).result(timeout=30)
    # the writer keeps publishing underneath...
    time.sleep(0.3)
    service.flush_updates()
    bfs_after = sess.query("bfs", source=5).result(timeout=30)
    fresh = service.submit("bfs", source=5, tenant="alice").result(timeout=30)
    print(f"  session reads identical across publishes: "
          f"{np.array_equal(bfs_before, bfs_after)}")
    print(f"  freshest read sees {stream.vg.current_stamp - sess.stamp} "
          f"newer versions (answers differ: {not np.array_equal(bfs_after, fresh)})")

# --- 5. The result cache: hot repeats are free, publishes warm-start -------
# (DESIGN.md §14) Queries on one version are pure functions of
# (kind, params, source), so exact repeats answer from memory without
# touching admission, and on each publish a promotion thread carries
# the hot entries to the new version through the incremental paths.
zrng = np.random.default_rng(3)
t0 = time.perf_counter()
replay = []
for i in range(400):  # Zipf-skewed two-tenant replay: mostly repeats
    src = int(min(zrng.zipf(2.0) - 1, n - 1))
    kind = "bfs" if zrng.random() < 0.8 else "sssp"
    t = service.submit(kind, source=src, tenant=f"t{i % 2}")
    t.result(timeout=30)  # closed loop: each repeat sees the last fill
    replay.append(t)
service.flush_updates()      # the live writer kept publishing...
service.flush_promotions()   # ...and carry-forward kept up
cst = service.stats()["cache"]
warm = [t.latency_s for t in replay if t.cached]
print(f"replay: {len(warm)}/400 served from cache in "
      f"{time.perf_counter() - t0:.2f}s "
      f"(hit rate {100 * len(warm) / 400:.0f}%, "
      f"promoted {cst['promoted_incremental']} incremental / "
      f"{cst['promoted_full']} full)")

# the cache NEVER leaks a newer version's answer into a pinned session:
# entries live on the version itself, so a session lookup can only see
# results computed against its exact snapshot
with service.session(tenant="alice") as sess:
    pinned = sess.query("bfs", source=0).result(timeout=30)
    # publish under the session's feet, promotion and all
    service.enqueue_update(0, int(rng.integers(1, n)))
    service.flush_updates()
    service.flush_promotions()
    again = sess.query("bfs", source=0).result(timeout=30)  # cached, pinned
    print(f"  pinned session repeat is cached AND identical across a "
          f"publish: {np.array_equal(pinned, again)}")

# --- 6. Observability + clean shutdown -------------------------------------
stop.set()
feeder.join()
st = service.stats()
print(f"stats: {st['publishes']} publishes, "
      f"tenants alice/bob completed "
      f"{st['tenants']['alice']['completed']}/{st['tenants']['bob']['completed']}, "
      f"bfs lane hist {st['lanes']['bfs']['batch_size_hist']}, "
      f"retraces after warmup {sum(l['retraces'] for l in st['lanes'].values())}")
service.stop()
print(f"shut down cleanly; live versions: {stream.vg.live_versions()}")
