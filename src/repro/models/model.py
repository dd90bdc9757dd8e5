"""Model entry points: init, cache management, and the serve-path
wrappers used by the serving engine and the scheduler."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.sharding import logical


def init_params(cfg: ModelConfig, key):
    return T.init_params(cfg, key)


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of the parameters — used by the trainer's
    sharding specs and the parameter count, so no memory is ever allocated
    for full-size configs."""
    return jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def param_count(cfg: ModelConfig) -> int:
    import math
    return sum(math.prod(x.shape)                # python ints: no overflow
               for x in jax.tree.leaves(abstract_params(cfg)))


def active_param_count(cfg: ModelConfig) -> int:
    """Active parameters per token (MoE: top_k + shared experts only)."""
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    f = cfg.moe_d_ff or cfg.d_ff
    per_expert = 3 * cfg.d_model * f
    kinds = cfg.lead_blocks + cfg.block_pattern * cfg.n_pattern_blocks \
        + cfg.extra_blocks
    n_blocks = sum(1 for k in kinds if k in ("moe", "mla_moe"))
    held = cfg.held_experts[1]      # of which top_k / n_experts are active
    inactive = (n_blocks * held * (cfg.n_experts - cfg.top_k) * per_expert
                // cfg.n_experts)
    return total - inactive


# ==========================================================================
# KV / recurrent cache
# ==========================================================================

def _slot_cache(cfg, kind: str, nb: Optional[int], batch: int, max_len: int):
    """Cache pytree for one pattern slot; leading nb axis when scanned."""
    dt = jnp.dtype(cfg.dtype)

    def shp(*s):
        return (nb,) + tuple(s) if nb is not None else tuple(s)

    if kind in ("attn", "attn_swa", "attn_local", "moe", "dec_attn_cross"):
        Hkv, D = cfg.n_kv_heads, cfg.head_dim
        return {"k": jnp.zeros(shp(batch, max_len, Hkv, D), dt),
                "v": jnp.zeros(shp(batch, max_len, Hkv, D), dt)}
    if kind in T.MLA_KINDS:
        # one latent row [c ; k_pe] per token, no head axis (DESIGN.md §11)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return {"ckv": jnp.zeros(shp(batch, max_len, width), dt)}
    if kind == "ssd":
        H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        dc = H * P + 2 * N                      # conv runs over (x, B, C)
        # recurrent state kept in f32 for numerical stability
        return {"conv": jnp.zeros(shp(batch, cfg.conv_kernel - 1, dc), dt),
                "ssm": jnp.zeros(shp(batch, H, P, N), jnp.float32)}
    if kind == "rglru":
        dr = cfg.rglru_width
        return {"conv": jnp.zeros(shp(batch, cfg.conv_kernel - 1, dr), dt),
                "h": jnp.zeros(shp(batch, dr), jnp.float32)}
    if kind == "cross":
        return None
    raise ValueError(kind)


def counts_experts(cfg: ModelConfig) -> bool:
    """True when the stack has dropless expert blocks, whose routed pairs
    per row and held expert a cache accumulates (``expert_tokens``)."""
    kinds = cfg.lead_blocks + cfg.block_pattern + cfg.extra_blocks
    return "mla_moe" in kinds


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    nb = cfg.n_pattern_blocks
    cache = {
        "layers": [_slot_cache(cfg, kind, nb, batch, max_len)
                   for kind in cfg.block_pattern],
        "extra": [_slot_cache(cfg, kind, None, batch, max_len)
                  for kind in cfg.extra_blocks],
        "len": jnp.zeros((), jnp.int32),
    }
    if cfg.lead_blocks:
        cache["lead"] = [_slot_cache(cfg, kind, None, batch, max_len)
                         for kind in cfg.lead_blocks]
    if counts_experts(cfg):
        cache["expert_tokens"] = jnp.zeros((batch, cfg.held_experts[1]),
                                           jnp.int32)
    return cache


# ==========================================================================
# Serve-path entry points
# ==========================================================================

def prefill(cfg: ModelConfig, params, tokens, max_len: int, *,
            cross_states=None, frontend_embeds=None):
    """tokens [B, S] -> (last-position logits [B, vocab], cache)."""
    if cfg.enc_layers and frontend_embeds is not None:
        cross_states = T.encode(cfg, params, frontend_embeds)
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = jnp.arange(S)[None]
    x, cache = T.run_stack(cfg, params, x, positions=positions,
                           caches=cache, cross_states=cross_states)
    x = T._norm(cfg, params["final_norm"], x[:, -1:])
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(x[:, 0], head)
    return logical(logits, "batch", "vocab"), cache


def decode_step(cfg: ModelConfig, params, cache, tokens, *,
                cross_states=None):
    """One decode step: tokens [B, 1] -> (logits [B, vocab], new cache).

    The KV cache is donated by the serving engine (buffer reuse)."""
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = cache["len"] + jnp.arange(1)[None]
    x, cache = T.run_stack(cfg, params, x, positions=positions,
                           caches=cache, cross_states=cross_states)
    x = T._norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(x[:, 0], head)
    return logical(logits, "batch", "vocab"), cache


forward = T.forward
