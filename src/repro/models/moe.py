"""Mixture-of-Experts: top-k router + capacity-bounded sort-free dispatch.

Dispatch uses the Switch-Transformer position-in-expert construction
(cumsum over one-hot assignments) followed by scatter into per-expert
buffers [E, C, d].  With experts sharded over the ``model`` mesh axis the
scatter/gather lowers to the expected all-to-all exchange under SPMD
(expert parallelism); with few experts (Mixtral's 8 on a 16-way axis) the
expert dim stays replicated and the per-expert FFN weights shard over
``d_ff`` instead (TP inside experts) — both fall out of the divisibility
rules in parallel/sharding.py.

DeepSeek-MoE fine-grained routing (64 routed + 2 shared experts, top-6) is
the same code path with ``n_shared_experts`` > 0.

``moe_dropless`` is the serving layer of the ``mla_moe`` blocks
(DeepSeek-V2): no capacity, so no token is dropped, and told which
experts this chip holds (``cfg.held_experts``).  It routes over all
``n_experts``, computes only the (token, held expert) pairs and the
shared experts, and counts the pairs per held expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense, mlp_swiglu
from repro.parallel.sharding import current_policy, logical


def _moe_axes(E: int):
    """Pick buffer sharding: when the expert dim divides the model axis
    (fine-grained MoE, DeepSeek 64e) shard experts only — adding a capacity
    axis makes XLA's scatter repartitioning pathological (8.3s -> 77s of
    collectives on a 4k-token deepseek training step).  When experts
    cannot shard (Mixtral 8e on a 16-way axis) shard capacity over data
    instead, which keeps the expert FFN compute distributed (44s -> 8.7s
    compute)."""
    pol = current_policy()
    if pol is None or pol.mesh is None:
        return "expert", None
    axes = tuple(a for a in pol.rules.get("expert", ())
                 if a in pol.mesh.axis_names)
    size = 1
    for a in axes:
        size *= pol.mesh.shape[a]
    if axes and E % size == 0:
        return "expert", None
    return None, "capacity"


def route(p, xt, cfg):
    """Router over all ``n_experts`` (float32): (weights [T, K], expert
    ids [T, K]).  ``topk_softmax`` renormalises over the top k (Mixtral);
    ``softmax_topk`` keeps the top k of the softmax over every expert, as
    they are, times ``routed_scaling`` (DeepSeek-V2)."""
    logits = dense(xt.astype(jnp.float32), p["w_router"])       # [T, E]
    if cfg.router == "softmax_topk":
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
        return w * cfg.routed_scaling, ids
    w, ids = jax.lax.top_k(logits, cfg.top_k)
    return jax.nn.softmax(w, axis=-1), ids


def moe_block(p, x, cfg):
    """x: [B, S, d] -> [B, S, d]."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    # ---- router ----------------------------------------------------------
    gate_w, gate_ids = route(p, xt, cfg)                        # [T, K]
    gate_w = gate_w.astype(x.dtype)

    # ---- capacity + position-in-expert ------------------------------------
    C = int(cfg.capacity_factor * T * K / E)
    C = max(8, min(C, T))
    flat_ids = gate_ids.reshape(-1)                             # [T*K]
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)       # [T*K, E]
    pos_in_exp = (jnp.cumsum(onehot, axis=0) - onehot)          # exclusive
    pos = jnp.sum(pos_in_exp * onehot, axis=1)                  # [T*K]
    keep = pos < C
    slot = jnp.where(keep, pos, C)                              # C = drop row

    # ---- dispatch: scatter tokens into [E, C+1, d] -------------------------
    # expert dim shards over `model` (EP) when divisible, capacity over
    # `data` — the scatter from token-sharded to expert-sharded layout is
    # the all-to-all exchange of expert parallelism
    buf = jnp.zeros((E, C + 1, d), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_ids, slot].add(xt[tok_idx])
    e_ax, c_ax = _moe_axes(E)
    buf = logical(buf[:, :C], e_ax, c_ax, None)                 # [E, C, d]

    # ---- expert FFNs -------------------------------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = logical(h, e_ax, c_ax, "d_ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])        # [E, C, d]
    out_buf = logical(out_buf, e_ax, c_ax, None)

    # ---- combine: gather back and weight ------------------------------------
    gathered = out_buf[flat_ids, jnp.minimum(slot, C - 1)]      # [T*K, d]
    gathered = gathered * keep[:, None].astype(x.dtype)
    combined = (gathered.reshape(T, K, d)
                * gate_w[..., None]).sum(axis=1)                # [T, d]

    # ---- shared experts (DeepSeek-MoE) ---------------------------------------
    if cfg.n_shared_experts:
        combined = combined + mlp_swiglu(p["shared"], xt)

    return combined.reshape(B, S, d)


DENSE_TOKENS = 64   # at or below: every held expert runs over every token


def moe_dropless(p, x, cfg, valid=None):
    """x [B, S, d] -> (y [B, S, d], routed pairs per row and held expert
    [B, n] int32).

    ``p`` holds the held experts' weights ([n, ...], expert
    ``cfg.experts_first`` first) and the full-width router.  ``valid``
    [B, S] bool marks real tokens: the others (bucket padding, idle pool
    rows) are neither counted nor, on the grouped path, computed.

    Up to ``DENSE_TOKENS`` tokens (a decode step) every held expert runs
    over every token, weighted by zero where not routed: each expert's
    weights are read once either way.  Above, the held pairs are sorted by
    expert and run as ``ragged_dot`` groups, so only routed pairs are
    computed.  Either way nothing is dropped.
    """
    B, S, d = x.shape
    T, K = B * S, cfg.top_k
    first, n = cfg.held_experts
    f32 = jnp.float32
    xt = x.reshape(T, d)
    w, ids = route(p, xt, cfg)
    local = ids - first
    here = (local >= 0) & (local < n)
    if valid is not None:
        here &= jnp.reshape(valid, (T, 1))
    slot = jnp.where(here, local, n)                            # n: elsewhere
    hot = jax.nn.one_hot(slot, n, dtype=jnp.int32)              # [T, K, n]
    counts = hot.reshape(B, S * K, n).sum(axis=1)
    if T <= DENSE_TOKENS:
        gate = jnp.einsum("tke,tk->te", hot.astype(f32), w)     # [T, n]
        h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, p["w_gate"])) \
            * jnp.einsum("td,edf->etf", xt, p["w_up"])
        ye = jnp.einsum("etf,efd->etd", h, p["w_down"])
        routed = jnp.einsum("te,etd->td", gate, ye.astype(f32))
    else:
        flat = slot.reshape(-1)                                 # [T*K]
        order = jnp.argsort(flat, stable=True)  # held pairs first, by expert
        pair_here = flat[order] < n
        tok = order // K
        sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
        xs = xt[tok]
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) \
            * jax.lax.ragged_dot(xs, p["w_up"], sizes)
        ys = jax.lax.ragged_dot(h, p["w_down"], sizes)          # [T*K, d]
        ys = jnp.where(pair_here[:, None], ys.astype(f32)
                       * w.reshape(-1)[order][:, None], 0.0)
        routed = jnp.zeros((T, d), f32).at[tok].add(ys)
    if cfg.n_shared_experts:
        routed = routed + mlp_swiglu(p["shared"], xt).astype(f32)
    return routed.astype(x.dtype).reshape(B, S, d), counts
