"""Pallas TPU flash attention (causal / sliding-window / GQA).

TPU-native tiling: the kernel streams KV blocks through VMEM against a
resident Q block, maintaining the online-softmax running max / denominator
in f32 VMEM scratch.  Grid = (B, H, nq, nk) with the KV axis innermost —
Pallas TPU grids execute sequentially, so the scratch accumulator carries
across the nk steps and is finalized on the last one.  Block shapes are
MXU-aligned (multiples of 128 on the contracting/lane dims).

This is the substrate's compute hot-spot (long prefill and training);
the paper itself has no kernel-level contribution (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 qb: int, kb: int, nk: int, causal: bool, window: int,
                 scale: float):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * qb
    k_start = ki * kb
    # skip fully-masked blocks (causal: kv block entirely after q block)
    if causal:
        run = k_start <= q_start + qb - 1
    else:
        run = jnp.asarray(True)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [qb, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [kb, D]
        v = v_ref[0, 0].astype(jnp.float32)                # [kb, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        mask = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512,
                    interpret: bool = False):
    """q: [B,H,Sq,D]; k/v: [B,Hkv,Skv,D] with H % Hkv == 0.
    Returns [B,H,Sq,D]."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, "GQA requires H % Hkv == 0"
    qb = min(q_block, Sq)
    while Sq % qb:
        qb //= 2
    kb = min(kv_block, Skv)
    while Skv % kb:
        kb //= 2
    nq, nk = Sq // qb, Skv // kb
    g = H // Hkv

    kernel = functools.partial(
        _attn_kernel, qb=qb, kb=kb, nk=nk, causal=causal, window=window,
        scale=D ** -0.5)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kb, D),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, kb, D),
                         lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, D), jnp.float32),      # output accumulator
            pltpu.VMEM((qb,), jnp.float32),        # running max
            pltpu.VMEM((qb,), jnp.float32),        # running denominator
        ],
        interpret=interpret,
    )(q, k, v)
