"""Unified transformer assembly for all assigned architectures.

The model is a stack of *super-blocks*: each super-block applies the
config's ``block_pattern`` once (e.g. ("rglru","rglru","attn_local") for
RecurrentGemma).  Super-blocks are scanned with ``jax.lax.scan`` over
stacked parameters so the HLO contains one super-block body + a loop —
essential to keep 100-layer configs compilable — and the scan body is
rematerialized (``jax.checkpoint``) for training memory.

All functions are pure; parameters are nested dicts with a leading
``n_pattern_blocks`` axis per pattern slot.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.attention import attention_block
from repro.models.mla import mla_attention
from repro.models.moe import moe_block, moe_dropless
from repro.models.rglru import rglru_block
from repro.models.ssm import mamba2_block
from repro.parallel.sharding import logical

ATTN_KINDS = ("attn", "attn_swa", "attn_local", "moe", "enc_attn")
MLA_KINDS = ("mla", "mla_moe")


# ==========================================================================
# Parameter initialization (per block kind)
# ==========================================================================

def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _attn_params(cfg, key, cross: bool = False):
    d, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.he_init(ks[0], (d, H * D), _dt(cfg)),
        "wk": L.he_init(ks[1], (d, Hkv * D), _dt(cfg)),
        "wv": L.he_init(ks[2], (d, Hkv * D), _dt(cfg)),
        "wo": L.he_init(ks[3], (H * D, d), _dt(cfg)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * D,), _dt(cfg))
        p["bk"] = jnp.zeros((Hkv * D,), _dt(cfg))
        p["bv"] = jnp.zeros((Hkv * D,), _dt(cfg))
    return p


def _mla_params(cfg, key):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": L.he_init(ks[0], (d, H * (dn + dr)), _dt(cfg)),
        "wkv_a": L.he_init(ks[1], (d, r + dr), _dt(cfg)),
        "kv_norm": jnp.zeros((r,), _dt(cfg)),
        "w_uk": L.he_init(ks[2], (r, H * dn), _dt(cfg)),
        "w_uv": L.he_init(ks[3], (r, H * dv), _dt(cfg)),
        "wo": L.he_init(ks[4], (H * dv, d), _dt(cfg)),
    }


def _mlp_params(cfg, key, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": L.he_init(ks[0], (d, f), _dt(cfg)),
        "w_up": L.he_init(ks[1], (d, f), _dt(cfg)),
        "w_down": L.he_init(ks[2], (f, d), _dt(cfg)),
    }


def _moe_params(cfg, key):
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    E = cfg.held_experts[1]          # the router spans all n_experts
    ks = jax.random.split(key, 5)
    p = {
        "w_router": L.he_init(ks[0], (d, cfg.n_experts), jnp.float32),
        "w_gate": L.he_init(ks[1], (E, d, f), _dt(cfg)),
        "w_up": L.he_init(ks[2], (E, d, f), _dt(cfg)),
        "w_down": L.he_init(ks[3], (E, f, d), _dt(cfg)),
    }
    if cfg.n_shared_experts:
        p["shared"] = _mlp_params(cfg, ks[4],
                                  d_ff=f * cfg.n_shared_experts)
    return p


def _ssd_params(cfg, key):
    d = cfg.d_model
    H, P, N, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.conv_kernel
    d_inner = H * P
    dc = d_inner + 2 * N
    ks = jax.random.split(key, 4)
    return {
        "w_in": L.he_init(ks[0], (d, 2 * d_inner + 2 * N + H), _dt(cfg)),
        "w_conv": L.trunc_normal(ks[1], (dc, K), _dt(cfg), 0.1),
        "dt_bias": jnp.zeros((H,), _dt(cfg)),
        "a_log": jnp.zeros((H,), jnp.float32),
        "w_out": L.he_init(ks[3], (d_inner, d), _dt(cfg)),
    }


def _rglru_params(cfg, key):
    d, dr, K = cfg.d_model, cfg.rglru_width, cfg.conv_kernel
    ks = jax.random.split(key, 6)
    return {
        "w_in_x": L.he_init(ks[0], (d, dr), _dt(cfg)),
        "w_in_y": L.he_init(ks[1], (d, dr), _dt(cfg)),
        "w_conv": L.trunc_normal(ks[2], (dr, K), _dt(cfg), 0.1),
        "w_a": L.he_init(ks[3], (dr, dr), _dt(cfg)),
        "b_a": jnp.zeros((dr,), _dt(cfg)),
        "w_x": L.he_init(ks[4], (dr, dr), _dt(cfg)),
        "b_x": jnp.zeros((dr,), _dt(cfg)),
        "lam": jnp.full((dr,), 0.7, jnp.float32),
        "w_out": L.he_init(ks[5], (dr, d), _dt(cfg)),
    }


def _norm_params(cfg):
    if cfg.norm == "ln":
        return {"scale": jnp.ones((cfg.d_model,), _dt(cfg)),
                "bias": jnp.zeros((cfg.d_model,), _dt(cfg))}
    return {"scale": jnp.zeros((cfg.d_model,), _dt(cfg))}


def _block_params(cfg, key, kind: str):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": _norm_params(cfg)}
    if kind in ("attn", "attn_swa", "attn_local", "enc_attn"):
        p["attn"] = _attn_params(cfg, ks[0])
        p["norm2"] = _norm_params(cfg)
        p["mlp"] = _mlp_params(cfg, ks[1])
    elif kind == "moe":
        p["attn"] = _attn_params(cfg, ks[0])
        p["norm2"] = _norm_params(cfg)
        p["moe"] = _moe_params(cfg, ks[1])
    elif kind in MLA_KINDS:
        p["attn"] = _mla_params(cfg, ks[0])
        p["norm2"] = _norm_params(cfg)
        if kind == "mla_moe":
            p["moe"] = _moe_params(cfg, ks[1])
        else:
            p["mlp"] = _mlp_params(cfg, ks[1])
    elif kind == "ssd":
        p["ssd"] = _ssd_params(cfg, ks[0])
    elif kind == "rglru":
        p["rglru"] = _rglru_params(cfg, ks[0])
        p["norm2"] = _norm_params(cfg)
        p["mlp"] = _mlp_params(cfg, ks[1])
    elif kind == "cross":
        p["cross"] = _attn_params(cfg, ks[0], cross=True)
        p["norm2"] = _norm_params(cfg)
        p["mlp"] = _mlp_params(cfg, ks[1])
        p["gate"] = jnp.zeros((1,), _dt(cfg))     # gated cross-attn (llama3.2)
    elif kind == "dec_attn_cross":
        p["attn"] = _attn_params(cfg, ks[0])
        p["norm2"] = _norm_params(cfg)
        p["cross"] = _attn_params(cfg, ks[1], cross=True)
        p["norm3"] = _norm_params(cfg)
        p["mlp"] = _mlp_params(cfg, ks[2])
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    cfg.validate()
    nb = cfg.n_pattern_blocks
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.trunc_normal(keys[0], (cfg.vocab, cfg.d_model),
                                _dt(cfg), cfg.d_model ** -0.5),
        "final_norm": _norm_params(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.trunc_normal(
            keys[1], (cfg.vocab, cfg.d_model), _dt(cfg),
            cfg.d_model ** -0.5)

    def stack_slot(slot_idx, kind):
        ks = jax.random.split(jax.random.fold_in(keys[2], slot_idx), nb)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[_block_params(cfg, k, kind) for k in ks])

    params["blocks"] = [stack_slot(i, kind)
                        for i, kind in enumerate(cfg.block_pattern)]
    params["extra"] = [_block_params(cfg, jax.random.fold_in(keys[3], i), k)
                       for i, k in enumerate(cfg.extra_blocks)]
    if cfg.lead_blocks:
        params["lead"] = [_block_params(cfg, jax.random.fold_in(keys[6], i),
                                        k)
                          for i, k in enumerate(cfg.lead_blocks)]
    if cfg.enc_layers:
        kse = jax.random.split(keys[4], cfg.enc_layers)
        params["encoder"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_block_params(cfg, k, "enc_attn") for k in kse])
        params["enc_final_norm"] = _norm_params(cfg)
        params["enc_pos"] = L.trunc_normal(
            keys[5], (cfg.frontend_tokens or 1500, cfg.d_model),
            _dt(cfg), 0.02)
    return params


# ==========================================================================
# Forward
# ==========================================================================

def _norm(cfg, p, x):
    if cfg.norm == "ln":
        return L.layer_norm(x, p["scale"], p["bias"])
    return L.rms_norm(x, p["scale"])


def block_forward(cfg, kind: str, p, x, *, positions, cache=None,
                  cache_len=None, cache_bt=None, cross_states=None,
                  causal=True, valid=None, counts=None):
    """One block of kind ``kind``.  Returns (x, new_cache).

    Attention caches are stored per layer as {"k","v"} (dense rows),
    {"kp","vp"} (paged block arenas) or {"ckv"} (latent rows, MLA); the
    shared fill length — and, for paged caches, the shared block table
    ``cache_bt`` — is threaded separately so layer caches can be stacked
    and scanned.  A dropless expert block appends its routed pairs per
    row and held expert [B, n] to ``counts`` (a list), counting the
    tokens ``valid`` [B, S] marks (all when None).
    """
    def _with_len(c):
        if c is None:
            return None
        c = {**c, "len": cache_len}
        if cache_bt is not None and "kp" in c:
            c["bt"] = cache_bt
        return c

    def _strip_len(c):
        return None if c is None else {k: v for k, v in c.items()
                                       if k not in ("len", "bt")}

    new_cache = None
    if kind in ("attn", "attn_swa", "attn_local", "enc_attn", "moe"):
        window = {"attn_swa": cfg.window,
                  "attn_local": cfg.local_window}.get(kind, 0)
        h, new_cache = attention_block(
            p["attn"], _norm(cfg, p["norm1"], x), cfg, positions=positions,
            cache=_with_len(cache),
            causal=causal and kind != "enc_attn", window=window)
        new_cache = _strip_len(new_cache)
        # named checkpoint: the "attn_out" remat policy saves exactly these
        # (cheap to store, expensive to recompute) and remats the FFN
        h = jax.ad_checkpoint.checkpoint_name(h, "attn_out")
        x = x + h
        ff_in = _norm(cfg, p["norm2"], x)
        if kind == "moe":
            if cfg.moe_impl == "shard_map":
                from repro.models.moe_ep import moe_block_ep
                x = x + moe_block_ep(p["moe"], ff_in, cfg)
            else:
                x = x + moe_block(p["moe"], ff_in, cfg)
        else:
            x = x + L.mlp_swiglu(p["mlp"], ff_in)
    elif kind in MLA_KINDS:
        with jax.named_scope("terra.mla"):
            h, new_cache = mla_attention(
                p["attn"], _norm(cfg, p["norm1"], x), cfg,
                positions=positions, cache=_with_len(cache), causal=causal)
        new_cache = _strip_len(new_cache)
        x = x + jax.ad_checkpoint.checkpoint_name(h, "attn_out")
        ff_in = _norm(cfg, p["norm2"], x)
        if kind == "mla_moe":
            with jax.named_scope("terra.moe"):
                y, n_pairs = moe_dropless(p["moe"], ff_in, cfg, valid=valid)
            if counts is not None:
                counts.append(n_pairs)
            x = x + y
        else:
            x = x + L.mlp_swiglu(p["mlp"], ff_in)
    elif kind == "ssd":
        h, new_cache = mamba2_block(p["ssd"], _norm(cfg, p["norm1"], x),
                                    cfg, cache=cache)
        x = x + h
    elif kind == "rglru":
        h, new_cache = rglru_block(p["rglru"], _norm(cfg, p["norm1"], x),
                                   cfg, cache=cache)
        x = x + h
        x = x + L.mlp_swiglu(p["mlp"], _norm(cfg, p["norm2"], x))
    elif kind == "cross":
        h, _ = attention_block(p["cross"], _norm(cfg, p["norm1"], x), cfg,
                               positions=positions,
                               cross_states=cross_states)
        x = x + jnp.tanh(p["gate"]) * h
        x = x + L.mlp_swiglu(p["mlp"], _norm(cfg, p["norm2"], x))
        new_cache = cache    # cross caches are static
    elif kind == "dec_attn_cross":
        h, new_cache = attention_block(
            p["attn"], _norm(cfg, p["norm1"], x), cfg,
            positions=positions, cache=_with_len(cache), causal=True)
        new_cache = _strip_len(new_cache)
        x = x + h
        h, _ = attention_block(p["cross"], _norm(cfg, p["norm2"], x), cfg,
                               positions=positions,
                               cross_states=cross_states)
        x = x + h
        x = x + L.mlp_swiglu(p["mlp"], _norm(cfg, p["norm3"], x))
    else:
        raise ValueError(kind)
    return x, new_cache


def _superblock(cfg, slot_params, x, *, positions, caches=None,
                cache_len=None, cache_bt=None, cross_states=None,
                valid=None, counts=None):
    """Apply one instance of the block pattern.  slot_params/caches are
    per-slot lists (already sliced to this super-block)."""
    new_caches = []
    for slot, kind in enumerate(cfg.block_pattern):
        c = caches[slot] if caches is not None else None
        x, nc = block_forward(cfg, kind, slot_params[slot], x,
                              positions=positions, cache=c,
                              cache_len=cache_len, cache_bt=cache_bt,
                              cross_states=cross_states, valid=valid,
                              counts=counts)
        new_caches.append(nc)
    return x, new_caches


APPEND_TILE = 128     # positions per read-modify-write of an appended row


def append_rows(cache, rows, pos, lead=None):
    """Write each row's new K or V into a dense cache leaf at its position.

    cache [..., B, Smax, *F] (``lead`` leading layer axes when stacked;
    by default F is [Hkv, D]), rows [..., B, *F], pos [B] or scalar.  An
    MLA latent leaf has F = [r + dr].  Each row is a read-modify-write
    of the aligned tile of ``min(128, Smax)`` positions that holds it, in a
    loop over the rows: on a TPU that keeps the leaf in its stored,
    position-minor layout, where a scatter or a one-position update makes
    XLA re-lay out the whole leaf.  A position at or beyond ``Smax`` lands
    at ``Smax - 1``, as ``dynamic_update_slice`` clamps.
    """
    lead = cache.ndim - 4 if lead is None else lead
    B, Smax = cache.shape[lead], cache.shape[lead + 1]
    feat = cache.ndim - lead - 2
    blk = min(APPEND_TILE, Smax)
    p = jnp.minimum(jnp.broadcast_to(pos, (B,)), Smax - 1).astype(jnp.int32)
    s0 = jnp.minimum(p // blk * blk, Smax - blk)
    tile_shape = cache.shape[:lead] + (1, blk) + cache.shape[lead + 2:]
    lane = jnp.arange(blk).reshape((blk,) + (1,) * feat)

    def write(b, c):
        start = (0,) * lead + (b, s0[b]) + (0,) * feat
        tile = jax.lax.dynamic_slice(c, start, tile_shape)
        row = jax.lax.dynamic_slice_in_dim(rows, b, 1, axis=lead)
        row = jnp.expand_dims(row, lead + 1).astype(c.dtype)
        tile = jnp.where(lane == p[b] - s0[b], row, tile)
        return jax.lax.dynamic_update_slice(c, tile, start)

    return jax.lax.fori_loop(0, B, write, cache)


def _appended(out) -> int:
    """Cache leaves a block's output appends to (its ``*_row`` entries)."""
    return 0 if out is None else sum(1 for k in out if k.endswith("_row"))


def _settle(cache, out, pos, lead: int):
    """A block's cache after the step: the rows a single-token decode
    appended (``<leaf>_row``), written into ``cache``; else the cache the
    block returned.  ``lead`` is 1 for a scanned (stacked) cache."""
    if not _appended(out):
        return out
    return {name: append_rows(leaf, out[name + "_row"], pos, lead=lead)
            for name, leaf in cache.items()}


def _unscanned(cfg, kinds, params, caches, x, counts, **kw):
    """Apply blocks one by one (leading or extra blocks)."""
    new = []
    for i, kind in enumerate(kinds):
        c = caches[i] if caches is not None else None
        x, nc = block_forward(cfg, kind, params[i], x, cache=c,
                              counts=counts, **kw)
        new.append(nc)
    return x, new


def run_stack(cfg, params, x, *, positions, caches=None, cross_states=None,
              record=None, valid=None):
    """Leading blocks, a scan over super-blocks, then the extra blocks.

    Single-token decode against dense attention caches reads each layer's
    cache as scan input only; the scan emits the new K/V (or latent) rows,
    written after the layer loop (``append_rows``, DESIGN.md §11).
    ``record``, if given, receives at trace time ``kv_append_leaves``: the
    number of cache leaves this step writes by appending (0 when the path
    did not engage), and ``experts_held`` where dropless expert blocks
    ran.  With ``caches``, those blocks' routed pairs per row and held
    expert [B, n], over the tokens ``valid`` [B, S] marks, are added to
    ``caches["expert_tokens"]`` (zero where absent) in the returned
    caches.
    """
    x = logical(x, "batch", None, None)
    cache_len = caches["len"] if caches is not None else None
    cache_bt = caches.get("bt") if caches is not None else None
    kw = dict(positions=positions, cache_len=cache_len, cache_bt=cache_bt,
              cross_states=cross_states, valid=valid)
    counts = []
    x, new_lead = _unscanned(cfg, cfg.lead_blocks, params.get("lead", []),
                             caches.get("lead") if caches is not None
                             else None, x, counts, **kw)

    def body(h, xs):
        slot_params, slot_caches = xs
        cnt = []
        h, new_caches = _superblock(cfg, slot_params, h,
                                    caches=slot_caches, counts=cnt, **kw)
        return h, (new_caches, sum(cnt) if cnt else None)

    if cfg.remat:
        policy = {
            "dots": jax.checkpoint_policies.dots_saveable,
            "attn_out": jax.checkpoint_policies.save_only_these_names(
                "attn_out"),
            "full": None,
        }[cfg.remat_policy]
        body_fn = jax.checkpoint(body, policy=policy)
    else:
        body_fn = body
    scanned_caches = (caches["layers"] if caches is not None
                      else [None] * len(cfg.block_pattern))
    x, (new_layer_caches, layer_counts) = jax.lax.scan(
        body_fn, x, (params["blocks"], scanned_caches))
    if layer_counts is not None:
        counts.append(layer_counts.sum(axis=0))

    x, new_extra = _unscanned(cfg, cfg.extra_blocks, params["extra"],
                              caches["extra"] if caches is not None
                              else None, x, counts, **kw)

    if caches is None:
        return x, None
    outs = new_lead + list(new_layer_caches) + new_extra
    if record is not None:
        record["kv_append_leaves"] = sum(_appended(o) for o in outs)
        if counts:
            record["experts_held"] = cfg.held_experts[1]
    new_caches = {
        "layers": [_settle(c, o, cache_len, 1)
                   for c, o in zip(scanned_caches, new_layer_caches)],
        "extra": [_settle(c, o, cache_len, 0)
                  for c, o in zip(caches["extra"], new_extra)],
        "len": cache_len + x.shape[1]}
    if cfg.lead_blocks:
        new_caches["lead"] = [_settle(c, o, cache_len, 0)
                              for c, o in zip(caches["lead"], new_lead)]
    if counts:
        new_caches["expert_tokens"] = (caches.get("expert_tokens", 0)
                                       + sum(counts))
    return x, new_caches


_run_stack = run_stack   # back-compat alias


def encode(cfg, params, frontend_embeds):
    """Encoder stack (Whisper): frontend embeddings [B, T, d] -> states."""
    x = frontend_embeds.astype(jnp.dtype(cfg.dtype))
    x = x + params["enc_pos"][:x.shape[1]][None]
    positions = jnp.arange(x.shape[1])[None]

    def body(h, p):
        h, _ = block_forward(cfg, "enc_attn", p, h, positions=positions,
                             causal=False)
        return h, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["encoder"])
    return _norm(cfg, params["enc_final_norm"], x)


def forward(cfg: ModelConfig, params, tokens, *, cross_states=None,
            frontend_embeds=None):
    """Training/eval forward: tokens [B, S] -> logits [B, S, vocab].

    ``frontend_embeds``: [B, S, d] continuous inputs replacing the token
    embedding (Mamba/audio stubs use tokens; VLM passes vision states via
    ``cross_states``; Whisper encodes ``frontend_embeds`` first).
    """
    if cfg.enc_layers and frontend_embeds is not None:
        cross_states = encode(cfg, params, frontend_embeds)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = jnp.arange(tokens.shape[1])[None]
    x, _ = _run_stack(cfg, params, x, positions=positions,
                      cross_states=cross_states)
    x = _norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = L.unembed(x, head)
    return logical(logits, "batch", None, "vocab")
