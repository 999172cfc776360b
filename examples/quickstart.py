"""Quickstart: C-trees and Aspen in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import algorithms as alg
from repro.core import ctree as ct
from repro.core import graph as G
from repro.core.streaming import AspenStream
from repro.data.rmat import rmat_edges, symmetrize

compile_cache.enable()  # persistent XLA cache, before the first compile

# --- 1. A C-tree is a compressed purely-functional ordered set ------------
rng = np.random.default_rng(0)
values = np.unique(rng.integers(0, 1 << 20, 50_000))
c = ct.build(values, b=256)
print(f"C-tree: {ct.ctree_size(c)} elements, "
      f"{ct.nbytes(c) / ct.ctree_size(c):.2f} B/elem compressed "
      f"(vs {ct.UNCOMPRESSED_NODE_BYTES} B/elem as a plain functional tree)")

# updates are functional: the old version is untouched
c2 = ct.multi_insert(c, rng.integers(0, 1 << 20, 1000))
print(f"after insert: new={ct.ctree_size(c2)}, old still={ct.ctree_size(c)}")

# --- 2. A graph is a tree of C-trees --------------------------------------
n = 4096
edges = symmetrize(rmat_edges(12, 60_000, seed=1))
g = G.build_graph(n, edges)
print(f"graph: {G.num_vertices(g)} vertices, {G.num_edges(g)} edges "
      f"({G.graph_nbytes(g) / G.num_edges(g):.2f} B/edge)")

# --- 3. Snapshots + queries ------------------------------------------------
snap = G.flat_snapshot(g)  # O(n): array of edge-tree pointers (paper §5.1)
src = int(edges[0, 0])
parents = alg.bfs(snap, src)
print(f"BFS from {src}: reached {(parents >= 0).sum()} vertices")

# --- 4. Streaming: concurrent-safe updates via versioning ------------------
stream = AspenStream(g)
v0 = stream.acquire()  # a reader pins version 0
stream.insert_edges(rmat_edges(12, 500, seed=2))  # writer publishes v1
v1 = stream.acquire()
print(f"reader v0 sees {G.num_edges(v0.graph)} edges; "
      f"v1 sees {G.num_edges(v1.graph)} (serializable snapshots)")
stream.release(v0), stream.release(v1)

# --- 5. Property graphs: per-edge values, weighted traversal ---------------
# insert_edges(weights=...) attaches one value per edge (both directions
# of a symmetric insert); re-inserting an edge overwrites its weight.
from repro.core.traversal import algorithms as talg

wedges = np.array([[0, 1], [1, 2], [0, 2]])
wstream = AspenStream(G.build_graph(3, np.empty((0, 2), np.int64)))
wstream.insert_edges(wedges, weights=np.array([1.0, 1.0, 10.0]))
dist = talg.sssp(wstream.engine("numpy"), 0)  # Bellman-Ford (min, +)
print(f"SSSP 0->2: {dist[2]:g} (2-hop cheap path beats the 10.0 edge)")
wstream.insert_edges(wedges[2:], weights=np.array([0.5]))  # overwrite
print(f"after overwrite: {talg.sssp(wstream.engine('numpy'), 0)[2]:g} "
      f"(direct edge now wins)")
