"""Attention: GQA / MQA, causal, sliding-window, local, cross; chunked
memory-efficient XLA implementation (the Pallas flash kernel in
repro.kernels is the TPU-optimized path; this module is the portable
XLA path every serving and training step runs).

The chunked implementation scans over query blocks and, within each, over
key/value blocks with an online-softmax accumulator, so peak memory is
O(Bq*Bk) instead of O(S^2) — required for the 32k-prefill and 4k-train
shapes at production batch sizes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense, rope
from repro.parallel.sharding import logical

NEG_INF = -1e30

# Trace-time switch: when True, paged-cache decode attends through the
# Pallas paged-attention kernel instead of the gather + dense reference
# path.  Flipped by the kernel-substituted ``kernel.slot_decode_paged``
# op around its trace (pass pipeline ``kernels``, DESIGN.md §12).
PAGED_KERNEL = False


def _pick_block(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (block sizes must tile s)."""
    b = min(target, s)
    while s % b:
        b -= 1
    return max(b, 1)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_block: int = 512, kv_block: int = 1024,
                      q_offset=0, kv_valid_len=None, scale=None):
    """q: [B,Sq,Hq,D], k: [B,Skv,Hkv,D], v: [B,Skv,Hkv,Dv] with
    Hq % Hkv == 0; returns [B,Sq,Hq,Dv].

    ``window`` > 0 restricts attention to the last ``window`` keys (SWA /
    local attention).  ``q_offset`` is the absolute position of q[0]
    (used at decode time and for local attention in cache mode).
    ``scale`` defaults to D**-0.5.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Skv, kv_block)
    nq, nk = Sq // qb, Skv // kb

    # [B, Hkv, G, nq, qb, D]
    qr = q.reshape(B, nq, qb, Hkv, G, D).transpose(0, 3, 4, 1, 2, 5) * scale
    kr = k.reshape(B, nk, kb, Hkv, D).transpose(0, 3, 1, 2, 4)
    vr = v.reshape(B, nk, kb, Hkv, Dv).transpose(0, 3, 1, 2, 4)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, qb)
    k_pos = jnp.arange(Skv).reshape(nk, kb)

    def q_step(_, qi):
        qblk = qr[:, :, :, qi]                     # [B,Hkv,G,qb,D]
        qp = q_pos[qi]                             # [qb]

        def kv_step(carry, ki):
            acc, m, l = carry
            kblk = kr[:, :, ki]                    # [B,Hkv,kb,D]
            vblk = vr[:, :, ki]
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32)
            kp = k_pos[ki]
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= kp[None, :] > qp[:, None] - window
            if kv_valid_len is not None:
                mask &= kp[None, :] < kv_valid_len
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, Hkv, G, qb, Dv), jnp.float32),
                jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, qb), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: [nq, B, Hkv, G, qb, Dv] -> [B, Sq, Hq, Dv]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, Hq, Dv)
    return out


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0):
    """Single-token decode over an already written cache: q [B,1,Hq,D];
    caches [B,Smax,Hkv,D]; cache_len: [B] or scalar valid length.  The
    serving path appends instead (:func:`decode_attention_append`); this
    write-then-attend form is its reference in the tests."""
    B, _, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, D) * (D ** -0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(Smax)
    valid = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    if window:
        valid &= pos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def decode_attention_append(q, k_cache, v_cache, k_new, v_new, cache_len, *,
                            window: int = 0):
    """Single-token decode against the cache as it was *before* the step,
    plus the new token's own K/V as a separate term — the same function as
    writing the row at ``cache_len`` and calling :func:`decode_attention`.

    q [B,1,Hq,D]; caches [B,Smax,Hkv,D]; k_new/v_new [B,Hkv,D]; cache_len:
    [B] or scalar, the new token's position.  A position at or beyond
    ``Smax`` stands for the clamped write at ``Smax - 1``: that stored
    position is left out, as the write would have replaced it.
    """
    B, _, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, D) * (D ** -0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache,
                   preferred_element_type=jnp.float32)
    s_new = jnp.einsum("bhgd,bhd->bhg", qr, k_new,
                       preferred_element_type=jnp.float32)
    pos = jnp.arange(Smax)
    idx = jnp.reshape(cache_len, (-1, 1))
    valid = pos[None, :] < jnp.minimum(idx, Smax - 1)
    if window:
        valid &= pos[None, :] >= idx + 1 - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.maximum(s.max(-1), s_new)
    p = jnp.exp(s - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = p.sum(-1) + p_new
    out = jnp.einsum("bhgs,bshd->bhgd",
                     (p / denom[..., None]).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    w_new = (p_new / denom).astype(v_new.dtype).astype(jnp.float32)
    out = out + w_new[..., None] * v_new.astype(jnp.float32)[:, :, None]
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# Full attention block (projections + rope + cache handling)
# --------------------------------------------------------------------------

def attention_block(p, x, cfg, *, positions=None, cache=None,
                    cross_states=None, causal=True, window=0,
                    use_rope=True):
    """Returns (out, new_cache).

    cache: None (training/prefill-no-cache) or dict with k/v [B,Smax,Hkv,D]
    and ``len`` (filled length).  Single-token decode against a dense
    cache *appends*: it reads the cache as it was, attends over it plus
    the new token's own K/V, and returns only the new rows
    ``{"k_row", "v_row"}`` [B,Hkv,D] — the caller writes them
    (``transformer.append_rows``, DESIGN.md §11).  When ``cross_states``
    is given, k/v come from the encoder/vision states and no cache/causal
    masking applies (cross-attention caches are precomputed at prefill in
    serve mode).
    """
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = dense(x, p["wq"], p.get("bq")).reshape(B, S, H, D)
    kv_src = cross_states if cross_states is not None else x
    Skv = kv_src.shape[1]
    k = dense(kv_src, p["wk"], p.get("bk")).reshape(B, Skv, Hkv, D)
    v = dense(kv_src, p["wv"], p.get("bv")).reshape(B, Skv, Hkv, D)

    if positions is None:
        positions = jnp.arange(S)[None, :]
    if use_rope and cross_states is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(Skv)[None, :] if cache is None else positions,
                 cfg.rope_theta)

    q = logical(q, "batch", None, "heads", None)
    k = logical(k, "batch", None, "kv_heads", None)
    v = logical(v, "batch", None, "kv_heads", None)

    new_cache = None
    if cache is not None and cross_states is None and "kp" in cache:
        # paged decode: K/V live in a flat block arena addressed through
        # the per-slot block table ``bt`` [B, nbps].  The new K/V lands at
        # the row's current position (block-table indirection); attention
        # gathers the row's blocks back into logical order, which is
        # bit-identical to the dense row, so paged == dense greedy tokens.
        idx = cache["len"]
        if S != 1 or not jnp.ndim(idx):
            raise NotImplementedError(
                "paged cache supports vector-position single-token decode")
        kp, vp, bt = cache["kp"], cache["vp"], cache["bt"]
        kv = k.astype(kp.dtype)[:, 0]              # [B, Hkv, D]
        vv = v.astype(vp.dtype)[:, 0]
        nblk, bs = kp.shape[0], kp.shape[1]
        blk = jnp.take_along_axis(bt, (idx // bs)[:, None], axis=1)[:, 0]
        dest = blk * bs + idx % bs                 # flat arena position
        kp = kp.reshape(nblk * bs, Hkv, D).at[dest].set(kv).reshape(kp.shape)
        vp = vp.reshape(nblk * bs, Hkv, D).at[dest].set(vv).reshape(vp.shape)
        new_cache = {"kp": kp, "len": idx + 1, "vp": vp}
        if PAGED_KERNEL:
            from repro.kernels import ops as kops
            out = kops.paged_attention(q, kp, vp, bt, idx + 1, window=window)
        else:
            # the same formula as dense decode: the arena's row is masked
            # out at the new position and the new K/V enters as its own term
            Bq, nbps = bt.shape
            kg = kp[bt].reshape(Bq, nbps * bs, Hkv, D)
            vg = vp[bt].reshape(Bq, nbps * bs, Hkv, D)
            out = decode_attention_append(q, kg, vg, kv, vv, idx,
                                          window=window)
    elif cache is not None and cross_states is None and S == 1:
        # single-token decode appends: attend over the cache as it was plus
        # the new K/V, and hand the new rows back for the caller to write
        # after the layer loop.  ``len`` is a scalar (lock-step serving:
        # every row at the same fill) or a [B] vector (slot-pooled serving:
        # per-slot positions).
        idx = cache["len"]
        kv = k.astype(cache["k"].dtype)[:, 0]       # [B, Hkv, D]
        vv = v.astype(cache["v"].dtype)[:, 0]
        out = decode_attention_append(q, cache["k"], cache["v"], kv, vv, idx,
                                      window=window)
        new_cache = {"k_row": kv, "v_row": vv, "len": idx + 1}
    elif cache is not None and cross_states is None:
        # multi-token step (prefill into a cache): write, then attend
        idx = cache["len"]
        if jnp.ndim(idx):
            raise NotImplementedError(
                "per-row cache positions support single-token decode "
                "only (got S=%d)" % S)
        kv, vv = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], kv,
                                                      idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], vv,
                                                      idx, axis=1)
        new_cache = {"k": k_cache, "v": v_cache, "len": idx + S}
        out = chunked_attention(q, k_cache, v_cache, causal=causal,
                                window=window, q_offset=idx,
                                kv_valid_len=idx + S,
                                q_block=cfg.q_block,
                                kv_block=cfg.kv_block)
    else:
        out = chunked_attention(q, k, v,
                                causal=causal and cross_states is None,
                                window=window,
                                q_block=cfg.q_block, kv_block=cfg.kv_block)

    out = logical(out, "batch", None, "heads", None)
    out = dense(out.reshape(B, S, H * D), p["wo"])
    return out, new_cache
