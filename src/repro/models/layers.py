"""Shared neural-net building blocks (pure JAX, param pytrees)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dt)


def dense(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope(x, positions, theta: float = 500000.0, inv_freq=None):
    """x: [..., S, H, D]; positions: [..., S] (broadcastable).
    ``inv_freq`` [D/2] replaces the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    half = d // 2
    freqs = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention scale for a context stretched ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float):
    """DeepSeek-V2's YaRN frequencies [dim/2]: the plain frequencies where
    a dimension turns more than ``beta_fast`` times over the original
    context, those divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between."""
    def corr(rot):
        return (dim * math.log(original_len / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (2 * i / dim)
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * (1.0 - keep) + extra * keep


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def _hidden_names(ndim):
    return ("batch",) + (None,) * (ndim - 2) + ("d_ff",)


def mlp_swiglu(p, x):
    """Llama-family gated MLP: down(silu(gate(x)) * up(x))."""
    h = jax.nn.silu(dense(x, p["w_gate"])) * dense(x, p["w_up"])
    h = logical(h, *_hidden_names(h.ndim))
    return dense(h, p["w_down"])


def mlp_gelu(p, x):
    h = jax.nn.gelu(dense(x, p["w_up"], p.get("b_up")))
    h = logical(h, *_hidden_names(h.ndim))
    return dense(h, p["w_down"], p.get("b_down"))


def embed(table, ids):
    return jnp.take(table, ids, axis=0)


def unembed(x, table):
    """Logits projection; table [vocab, d] (tied) -> [..., vocab]."""
    return jnp.einsum("...d,vd->...v", x, table)


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------

def trunc_normal(key, shape, dtype, scale: float):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def he_init(key, shape, dtype):
    fan_in = shape[0] if len(shape) >= 1 else 1
    return trunc_normal(key, shape, dtype, (2.0 / max(fan_in, 1)) ** 0.5)
