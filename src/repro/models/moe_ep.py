"""Explicit expert-parallel MoE: shard_map local dispatch + all_to_all.

The pjit capacity-scatter implementation (models/moe.py) is partitioned by
XLA SPMD with "involuntary full rematerialization" (its own warning),
inflating collectives ~250x over the ideal token exchange
(EXPERIMENTS.md §Roofline).  This module is the engineered fix, the
MaxText/Megatron formulation:

  1. inside shard_map, each (data-row, model-col) device routes its LOCAL
     tokens and scatters them into a local [E, C_loc, d] buffer — no
     cross-device indexing;
  2. one all_to_all over the model axis regroups by expert:
     [E, C_loc, d] -> [E/ep, ep*C_loc, d], aligning tokens with the
     expert weight shard resident on the device;
  3. local expert FFNs (dense MXU matmuls);
  4. the reverse all_to_all returns expert outputs to the owning shard,
     which combines them with the gate weights.

Wire cost per device per step = 2 x (top_k-expanded activations), the
information-theoretic minimum for capacity-based EP.

Requires n_experts % model_axis_size == 0 (DeepSeek 64e on a 16-way axis;
Mixtral's 8e keeps the pjit path)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense
from repro.parallel.sharding import current_policy


def _shared_mlp(p, x):
    # plain gated MLP: no logical() constraints (illegal inside shard_map,
    # where the mesh axes are manual)
    h = jax.nn.silu(dense(x, p["w_gate"])) * dense(x, p["w_up"])
    return dense(h, p["w_down"])


def _local_moe(p, xt, cfg, ep: int, model_axis: str):
    """Per-device body (inside shard_map).  xt: [T_loc, d] local tokens;
    expert weights already sharded: p['w_*'] leading dim E/ep."""
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.top_k
    e_loc = E // ep

    logits = dense(xt.astype(jnp.float32), p["w_router"])       # [T, E]
    gate_w, gate_ids = jax.lax.top_k(logits, K)
    gate_w = jax.nn.softmax(gate_w, axis=-1).astype(xt.dtype)

    C = max(8, int(cfg.capacity_factor * T * K / E))
    flat_ids = gate_ids.reshape(-1)                             # [T*K]
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    keep = pos < C
    slot = jnp.where(keep, pos, C)

    # 1. local dispatch buffer [E, C+1, d]
    buf = jnp.zeros((E, C + 1, d), xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_ids, slot].add(xt[tok_idx])[:, :C]        # [E, C, d]

    # 2. all_to_all (tiled): split experts across the axis, concatenate the
    #    received capacity blocks — [E, C, d] -> [E/ep, ep*C, d]
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)

    # 3. local expert FFNs
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # [E/ep, ep*C, d]

    # 4. reverse all_to_all: back to [E, C, d] on the owning shard with the
    #    original slot layout (device order round-trips)
    out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                             tiled=True)

    # combine locally
    gathered = out[flat_ids, jnp.minimum(slot, C - 1)]
    gathered = gathered * keep[:, None].astype(xt.dtype)
    combined = (gathered.reshape(T, K, d) * gate_w[..., None]).sum(axis=1)

    if cfg.n_shared_experts:
        combined = combined + _shared_mlp(p["shared"], xt)
    return combined


def moe_block_ep(p, x, cfg):
    """x: [B, S, d] -> [B, S, d] via explicit EP.  Falls back to the pjit
    path when no mesh is active or experts don't divide the model axis."""
    pol = current_policy()
    mesh = pol.mesh if pol is not None else None
    if mesh is None or "model" not in mesh.axis_names \
            or cfg.n_experts % mesh.shape["model"] != 0:
        from repro.models.moe import moe_block
        return moe_block(p, x, cfg)
    ep = mesh.shape["model"]
    B, S, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                       and B % mesh.shape[a] == 0)

    def body(p_loc, x_loc):
        Bl, Sl, _ = x_loc.shape
        y = _local_moe(p_loc, x_loc.reshape(Bl * Sl, d), cfg, ep, "model")
        return y.reshape(Bl, Sl, d)

    pspec = {
        "w_router": P(),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if cfg.n_shared_experts:
        pspec["shared"] = {k: P() for k in p["shared"]}
    xspec = P(batch_axes if batch_axes else None, None, None)

    return shard_map(body, mesh=mesh, in_specs=(pspec, xspec),
                     out_specs=xspec, check_vma=False)(p, x)
