"""RecurrentGemma (arXiv:2402.19427) recurrent block: temporal conv + RG-LRU.

RG-LRU recurrence (per channel):
    r_t = sigmoid(W_a x_t + b_a)              (recurrence gate)
    i_t = sigmoid(W_x x_t + b_x)              (input gate)
    a_t = exp(-c * softplus(Lambda) * r_t)    (decay, c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training/prefill uses an associative scan (log-depth on the sequence);
decode is a single recurrent update, whatever the context length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense

_C = 8.0


def _rg_lru_scan(x_gated, a, h0=None):
    """h_t = a_t * h_{t-1} + x_gated_t via associative scan.
    x_gated/a: [B, S, D]."""
    if h0 is not None:
        # fold the initial state into the first element
        x_gated = x_gated.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, a2 * x1 + x2

    _, h = jax.lax.associative_scan(combine, (a, x_gated), axis=1)
    return h


def rglru_block(p, x, cfg, *, cache=None):
    """x: [B, S, d].  cache: None or dict(conv [B,K-1,dr], h [B,dr])."""
    B, S, d = x.shape
    dr = cfg.rglru_width                       # recurrent width
    K = cfg.conv_kernel

    xb = dense(x, p["w_in_x"])                 # [B,S,dr] linear branch
    yb = jax.nn.gelu(dense(x, p["w_in_y"]))    # gated branch

    # temporal conv (depthwise, causal)
    new_conv = None
    if cache is None:
        pad = jnp.zeros((B, K - 1, dr), xb.dtype)
        ci = jnp.concatenate([pad, xb], axis=1)
    else:
        ci = jnp.concatenate([cache["conv"], xb], axis=1)
        new_conv = ci[:, -(K - 1):]
    win = jnp.stack([ci[:, i:i + S] for i in range(K)], axis=-1)
    xc = jnp.einsum("bsdk,dk->bsd", win, p["w_conv"])

    # RG-LRU
    r = jax.nn.sigmoid(dense(xc, p["w_a"]) + p["b_a"])
    i = jax.nn.sigmoid(dense(xc, p["w_x"]) + p["b_x"])
    log_a = -_C * jax.nn.softplus(p["lam"]) * r          # [B,S,dr]
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-6)) * (i * xc)

    a = a.astype(jnp.float32)
    gated = gated.astype(jnp.float32)
    new_h = None
    if cache is None:
        h = _rg_lru_scan(gated, a)
    elif S == 1:
        h1 = a[:, 0] * cache["h"] + gated[:, 0]
        h = h1[:, None]
        new_h = h1
    else:
        h = _rg_lru_scan(gated, a, h0=cache["h"].astype(jnp.float32))
        new_h = h[:, -1]

    out = dense(h.astype(x.dtype) * yb, p["w_out"])
    if cache is not None:
        return out, {"conv": new_conv, "h": new_h}
    return out, None
