"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Each token's keys and values come from one ``kv_lora_rank`` latent ``c``
(RMS-normed) and one rope key ``k_pe`` shared by every head.  A cache
holds the row ``[c ; k_pe]`` per token and layer (512 + 64 values at
DeepSeek-V2's widths), with no head axis: leaf ``ckv`` [B, Smax, r + dr].

Two paths:

* multi-token (training forward, prefill into a cache): the latent is
  expanded into per-head keys ``[c W_UK ; k_pe]`` (nope + rope wide) and
  values ``c W_UV`` (v wide), and ``chunked_attention`` attends with a
  value width of its own;
* single-token decode against a dense cache, absorbed: W_UK folds into
  the query (``q_lat = q_nope W_UK^T``, r wide per head), a score is
  ``q_lat . c + q_pe . k_pe`` over the stored rows, and the weighted
  latent leaves through W_UV.  Like ``attention_block``'s decode it
  attends the cache as it was plus the new row, and returns only the new
  row ``ckv_row`` for ``transformer.append_rows`` to write after the
  layer loop (DESIGN.md §11).

Rope is rotate-half on the stored order of the rope dims, with YaRN
frequencies where ``cfg.yarn_factor`` is set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import NEG_INF, chunked_attention


def softmax_scale(cfg) -> float:
    """(nope + rope)**-0.5, times YaRN's mscale(mscale_all_dim) squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def _rope(cfg, x, positions):
    if not cfg.yarn_factor:
        return L.rope(x, positions, cfg.rope_theta)
    inv = L.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                          cfg.yarn_factor, cfg.yarn_original_max_len,
                          cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    out = L.rope(x, positions, inv_freq=inv)
    m = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return out if m == 1.0 else (out * m).astype(out.dtype)


def _absorbed_decode(cfg, p, q_nope, q_pe, cache, new, pos):
    """One query per row against the cache as it was plus its own new row.

    q_nope [B,H,dn], q_pe [B,H,dr]; cache [B,Smax,r+dr]; new [B,r+dr];
    pos [B] or scalar, the new row's position (one at or beyond ``Smax``
    stands for the clamped write at ``Smax - 1``, whose stored row is then
    left out).  Returns [B,H,dv]."""
    f32 = jnp.float32
    H, dn, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    Smax = cache.shape[1]
    w_uk = p["w_uk"].reshape(r, H, dn)
    w_uv = p["w_uv"].reshape(r, H, dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk,
                       preferred_element_type=f32)
    qc = jnp.concatenate([q_lat, q_pe.astype(f32)], -1).astype(cache.dtype)
    scale = softmax_scale(cfg)
    s = jnp.einsum("bhc,bsc->bhs", qc, cache,
                   preferred_element_type=f32) * scale
    s_new = jnp.einsum("bhc,bc->bh", qc, new,
                       preferred_element_type=f32) * scale
    idx = jnp.reshape(pos, (-1, 1))
    valid = jnp.arange(Smax)[None, :] < jnp.minimum(idx, Smax - 1)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.maximum(s.max(-1), s_new)
    pr = jnp.exp(s - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = pr.sum(-1) + p_new
    # the whole stored row is contracted (its rope tail dropped after), so
    # no slice of the layer's cache is taken
    o = jnp.einsum("bhs,bsc->bhc", (pr / denom[..., None]).astype(cache.dtype),
                   cache, preferred_element_type=f32)[..., :r]
    w_new = (p_new / denom).astype(cache.dtype).astype(f32)
    o = o + w_new[..., None] * new[:, None, :r].astype(f32)
    return jnp.einsum("bhr,rhv->bhv", o.astype(cache.dtype), w_uv,
                      preferred_element_type=f32)


def mla_attention(p, x, cfg, *, positions, cache=None, causal=True):
    """Returns (out [B,S,d], new_cache).

    cache: None, or {"ckv": [B,Smax,r+dr], "len"}.  A single-token step
    with a cache decodes absorbed and returns ``{"ckv_row", "len"}``; a
    multi-token step (scalar ``len``) writes its rows and attends the
    expanded cache, returning ``{"ckv", "len"}``."""
    B, S, _ = x.shape
    H, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim,
                        cfg.kv_lora_rank)
    q = L.dense(x, p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(cfg, q[..., dn:], positions)
    ckv = L.dense(x, p["wkv_a"])
    c = L.rms_norm(ckv[..., :r], p["kv_norm"])
    k_pe = _rope(cfg, ckv[..., None, r:], positions)[:, :, 0]
    row = jnp.concatenate([c, k_pe], -1)                     # [B,S,r+dr]

    if cache is not None and S == 1:
        idx = cache["len"]
        new = row[:, 0].astype(cache["ckv"].dtype)
        out = _absorbed_decode(cfg, p, q_nope[:, 0], q_pe[:, 0],
                               cache["ckv"], new, idx)
        out = out[:, None].astype(x.dtype)
        new_cache = {"ckv_row": new, "len": idx + 1}
    else:
        if cache is not None:
            idx = cache["len"]
            if jnp.ndim(idx):
                raise NotImplementedError(
                    "per-row cache positions support single-token decode "
                    "only (got S=%d)" % S)
            src = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], row.astype(cache["ckv"].dtype), idx, axis=1)
            new_cache = {"ckv": src, "len": idx + S}
            q_offset, kv_valid = idx, idx + S
        else:
            src, new_cache, q_offset, kv_valid = row, None, 0, None
        Skv = src.shape[1]
        k_nope = L.dense(src[..., :r], p["w_uk"]).reshape(B, Skv, H, dn)
        v = L.dense(src[..., :r], p["w_uv"]).reshape(B, Skv, H, dv)
        k_rope = jnp.broadcast_to(src[:, :, None, r:], (B, Skv, H, dr))
        k = jnp.concatenate([k_nope, k_rope.astype(k_nope.dtype)], -1)
        out = chunked_attention(jnp.concatenate([q_nope, q_pe], -1), k, v,
                                causal=causal, q_offset=q_offset,
                                kv_valid_len=kv_valid, q_block=cfg.q_block,
                                kv_block=cfg.kv_block, unroll=cfg.unroll,
                                scale=softmax_scale(cfg))
    return L.dense(out.reshape(B, S, H * dv), p["wo"]), new_cache
