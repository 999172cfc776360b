"""End-to-end driver: train GraphSAGE on a streaming graph.

Demonstrates the full stack working together:
  * Aspen flat graph as the storage layer (streaming inserts mid-training)
  * the REAL neighbor sampler reading the live CSR pool
  * train loop with AdamW + WSD schedule + checkpoint/restore
  * deterministic restart (kill it mid-run and re-run: it resumes)

    PYTHONPATH=src python examples/train_gnn.py [--steps 300]
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import flat_graph as fg
from repro.data.pipeline import NeighborSampler, power_law_graph
from repro.dist.fault_tolerance import ResumableRun
from repro.models.gnn import graphsage
from repro.optim import adamw
from repro.train import train_step as TS

compile_cache.enable()  # persistent XLA cache, before the first compile


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--m", type=int, default=120_000)
    ap.add_argument("--d-feat", type=int, default=64)
    ap.add_argument("--d-hidden", type=int, default=128)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--fanout", type=int, nargs=2, default=(15, 10))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_gnn")
    ap.add_argument("--stream-every", type=int, default=50,
                    help="insert a batch of new edges every K steps")
    args = ap.parse_args()

    # --- storage layer: an Aspen flat graph we keep streaming into ---------
    offsets, nbrs = power_law_graph(args.n, args.m, seed=0)
    edges = np.stack([np.repeat(np.arange(args.n), np.diff(offsets)), nbrs], 1)
    graph = fg.from_edges(args.n, edges)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((args.n, args.d_feat)).astype(np.float32)
    # labels correlated with features so training learns something real
    w_true = rng.standard_normal((args.d_feat, args.classes))
    labels = (feats @ w_true).argmax(1)

    params = graphsage.init(jax.random.PRNGKey(0), args.d_feat, args.d_hidden, args.classes)
    step_fn = jax.jit(TS.make_train_step(
        TS.sage_sampled_loss(), adamw.wsd_schedule(20, args.steps, 50, 1e-2)
    ))

    run = ResumableRun(args.ckpt_dir, make_state=lambda: TS.init_state(params),
                       save_every=100)
    start, state = run.restore_or_init()
    if start:
        print(f"[restore] resuming from step {start}")

    t0 = time.time()
    for step in range(start, args.steps):
        if step % args.stream_every == 0 and step > 0:
            # live streaming insert: the sampler sees the new edges because
            # it reads the (immutable) new snapshot's CSR arrays
            new = np.stack([rng.integers(0, args.n, 512), rng.integers(0, args.n, 512)], 1)
            graph = fg.insert_edges_host(graph, new)
        csr_off = np.asarray(graph.offsets)
        csr_nbr = (np.asarray(graph.keys)[: int(graph.m)] & 0xFFFFFFFF)
        sampler = NeighborSampler(csr_off, csr_nbr, feats)
        sb = sampler.sample_batch(0, step, args.batch, tuple(args.fanout))
        batch = {
            "x_self": jnp.asarray(sb["x_self"]),
            "neigh_feats": [jnp.asarray(f) for f in sb["neigh_feats"]],
            "neigh_masks": [jnp.asarray(m) for m in sb["neigh_masks"]],
            "labels": jnp.asarray(labels[sb["seeds"]]),
        }
        state, metrics = step_fn(state, batch)
        run.maybe_save(step, state)
        if step % 25 == 0:
            acc = _eval_acc(state.params, sampler, labels, args)
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"acc {acc:.3f}  edges {int(graph.m)}  "
                  f"({(time.time() - t0) / max(step - start + 1, 1):.3f} s/step)")
    run.finish()
    acc = _eval_acc(state.params, sampler, labels, args)
    print(f"done. final accuracy {acc:.3f} (chance {1 / args.classes:.3f})")


def _eval_acc(params, sampler, labels, args) -> float:
    sb = sampler.sample_batch(1, 999, 512, tuple(args.fanout))
    logits = graphsage.forward_sampled(
        params, jnp.asarray(sb["x_self"]),
        [jnp.asarray(f) for f in sb["neigh_feats"]],
        [jnp.asarray(m) for m in sb["neigh_masks"]],
    )
    return float((np.asarray(logits).argmax(1) == labels[sb["seeds"]]).mean())


if __name__ == "__main__":
    main()
