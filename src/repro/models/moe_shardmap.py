"""shard_map MoE: shard-local routing + explicit collective schedule.

Why: the einsum/scatter MoE (moe.py) routes with a GLOBAL argsort over
batch-sharded tokens; GSPMD lowers the resulting data-dependent
gathers/scatters as masked-select + full-buffer all-reduces — measured
346 GB/layer/device on qwen3-moe prefill (EXPERIMENTS.md §Perf B0-B2).

Here every (data, model) device runs a LOCAL program:

  1. route + sort + capacity-assign ONLY its own T/nd tokens
     (C_local = C/nd slots per expert per data shard);
  2. build the local dispatch buffer (E, C_local, D), slice out the
     E/nm experts this model-column owns;
  3. all_gather over "data": (nd, E/nm, C_local, D) == the full capacity
     for my experts — 2 orders of magnitude less traffic than the
     GSPMD-inferred all-reduces;
  4. local grouped GEMMs with my expert weights (E/nm, D, F);
  5. all_gather over "model": every data shard gets all experts' outputs
     for ITS C_local slots; local combine-gather back to (T/nd, D).

Token order, capacity-drop policy, and numerics match moe.py exactly
when capacities don't overflow (property-tested in tests/test_moe.py).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.sharded_pool import shard_map


# set by launch/cells.py before tracing (mesh objects cannot live in a
# hashable LMConfig)
ACTIVE_MESH: Mesh | None = None


def _local_dispatch(xt, router, m, C_local):
    """Everything token-local: returns (buf (E, C_local, D), combine info)."""
    T, D = xt.shape
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), m.top_k)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    first_of_e = jnp.searchsorted(e_sorted, jnp.arange(m.n_experts))
    rank = jnp.arange(T * m.top_k) - first_of_e[e_sorted]
    keep = rank < C_local
    slot = e_sorted * C_local + rank
    src_tok = flat_t[order]
    buf = jnp.zeros((m.n_experts * C_local, D), xt.dtype)
    buf = buf.at[jnp.where(keep, slot, m.n_experts * C_local)].set(
        xt[src_tok], mode="drop"
    )
    return buf.reshape(m.n_experts, C_local, D), (slot, keep, src_tok, flat_p, order)


def moe_apply_shardmap(params: Dict[str, Any], cfg, x: jax.Array, mesh: Mesh) -> jax.Array:
    """x: (B, S, D) sharded P(('pod','data'), None, None)."""
    m = cfg.moe
    B, S, D = x.shape
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    nd = 1
    for a in data_axes:
        nd *= mesh.shape[a]
    nm = mesh.shape["model"]
    assert m.n_experts % nm == 0
    T_local = (B * S) // nd
    C_local = max(8, -(-int(m.capacity_factor * T_local * m.top_k / m.n_experts) // 8) * 8)
    e_per = m.n_experts // nm

    def local(x_loc, router, w_gate, w_up, w_down, shared):
        # x_loc: (B/nd, S, D); weights already model-sharded: (E/nm, D, F)
        xt = x_loc.reshape(-1, D)
        buf, (slot, keep, src_tok, flat_p, order) = _local_dispatch(
            xt, router, m, C_local
        )
        # my model-column's experts
        mi = jax.lax.axis_index("model")
        mine = jax.lax.dynamic_slice_in_dim(buf, mi * e_per, e_per, axis=0)
        # (nd, E/nm, C_local, D): full capacity for my experts
        full = jax.lax.all_gather(mine, data_axes, axis=0, tiled=False)
        full = full.reshape(nd * 1 if full.ndim == 4 else -1, e_per, C_local, D) \
            if full.ndim == 4 else full
        full = full.reshape(-1, e_per, C_local, D)  # (nd, E/nm, C_local, D)
        h = full.transpose(1, 0, 2, 3).reshape(e_per, nd * C_local, D)
        g = jnp.einsum("ecd,edf->ecf", h, w_gate)
        u = jnp.einsum("ecd,edf->ecf", h, w_up)
        o = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)
        # back to (nd, E/nm, C_local, D), pick my data shard's slots
        o = o.reshape(e_per, nd, C_local, D).transpose(1, 0, 2, 3)
        di = jax.lax.axis_index(data_axes)
        o_mine = jax.lax.dynamic_index_in_dim(o, di, axis=0, keepdims=False)
        # gather all experts' outputs for MY slots: (E, C_local, D)
        o_all = jax.lax.all_gather(o_mine, "model", axis=0, tiled=True)
        o_flat = o_all.reshape(m.n_experts * C_local, D)
        gathered = o_flat[jnp.where(keep, slot, 0)] * jnp.where(
            keep, flat_p[order], 0.0
        )[:, None].astype(x.dtype)
        out = jnp.zeros((xt.shape[0], D), x.dtype).at[src_tok].add(gathered)
        if shared is not None:
            from . import layers as L

            out = out + L.swiglu(shared, xt)
        return out.reshape(x_loc.shape)

    shared = params.get("shared")
    in_specs = (
        P(data_axes, None, None),  # x
        P(None, None),  # router (replicated)
        P("model", None, None),  # w_gate
        P("model", None, None),  # w_up
        P("model", None, None),  # w_down
        (jax.tree.map(lambda _: P(None, None), shared) if shared is not None else None),
    )
    fn = shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(data_axes, None, None),
    )
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"], shared)
