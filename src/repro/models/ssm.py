"""Mamba-2 (SSD: state-space duality, arXiv:2405.21060) block.

Chunked SSD algorithm: the sequence is split into chunks of length Q;
within a chunk the output is the quadratic (attention-like) form masked by
the cumulative decay; across chunks a recurrence carries the state
[H, P, N].  This is the TPU-friendly formulation (dense matmuls for the
MXU); the Pallas kernel in repro.kernels/ssd_scan.py implements the same
contraction with explicit VMEM tiling, and this module doubles as its
reference.

Decode: a single recurrent state update per token — O(H*P*N) per step,
which is why the 500k-token decode cell runs for this family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense
from repro.parallel.sharding import logical


def _segsum(a_chunk):
    """log-space cumulative decay matrix L[i, j] = sum_{k=j+1..i} a_k for
    i >= j else -inf.  a_chunk: [..., Q]."""
    Q = a_chunk.shape[-1]
    cs = jnp.cumsum(a_chunk, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]       # [.., i, j] = cs_i - cs_j
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, return_final: bool = False):
    """SSD forward.

    x:  [B, S, H, P]   (inputs per head)
    dt: [B, S, H]      (positive step sizes, post-softplus)
    A:  [H]            (negative decay rates)
    Bm: [B, S, N]      (input projection, shared across heads — Mamba-2)
    Cm: [B, S, N]      (output projection)
    returns y: [B, S, H, P]
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q

    a = (dt * A[None, None, :])                      # [B,S,H] log-decay (<0)
    xr = x.reshape(B, nc, Q, H, P)
    ar = a.reshape(B, nc, Q, H)
    dtr = dt.reshape(B, nc, Q, H)
    Br = Bm.reshape(B, nc, Q, N)
    Cr = Cm.reshape(B, nc, Q, N)

    # ---- intra-chunk (quadratic, attention-like) --------------------------
    L = jnp.exp(_segsum(ar.transpose(0, 1, 3, 2)))   # [B,nc,H,Q,Q]
    scores = jnp.einsum("bcqn,bckn->bcqk", Cr, Br)   # [B,nc,Q,Q]
    M = scores[:, :, None] * L                       # [B,nc,H,Q,Q]
    y_diag = jnp.einsum("bchqk,bckh,bckhp->bcqhp", M, dtr, xr)

    # ---- chunk states ------------------------------------------------------
    a_cum = jnp.cumsum(ar, axis=2)                   # [B,nc,Q,H]
    a_tot = a_cum[:, :, -1]                          # [B,nc,H]
    decay_states = jnp.exp(a_tot[:, :, None] - a_cum)          # [B,nc,Q,H]
    states = jnp.einsum("bcqn,bcqh,bcqh,bcqhp->bchpn",
                        Br, decay_states, dtr, xr)   # [B,nc,H,P,N]

    # ---- inter-chunk recurrence -------------------------------------------
    def step(h, inp):
        st, atot = inp                               # [B,H,P,N], [B,H]
        h_new = h * jnp.exp(atot)[:, :, None, None] + st
        return h_new, h                              # emit state BEFORE chunk

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (states.astype(jnp.float32).transpose(1, 0, 2, 3, 4),
          a_tot.astype(jnp.float32).transpose(1, 0, 2))
    h_final, prev_states = jax.lax.scan(step, h0, xs)
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]

    # ---- contribution of carried state to each position --------------------
    state_decay = jnp.exp(a_cum)                     # [B,nc,Q,H]
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp",
                       Cr.astype(jnp.float32), prev_states,
                       state_decay.astype(jnp.float32))

    y = (y_diag.astype(jnp.float32) + y_off).reshape(B, S, H, P)
    y = y.astype(x.dtype)
    if return_final:
        return y, h_final
    return y


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """One-token recurrent update.

    state: [B, H, P, N]; x: [B, H, P]; dt: [B, H]; Bm/Cm: [B, N]
    returns (y [B,H,P], new_state)
    """
    da = jnp.exp(dt * A[None, :]).astype(jnp.float32)    # [B,H]
    upd = jnp.einsum("bn,bh,bhp->bhpn", Bm, dt, x).astype(jnp.float32)
    new_state = state.astype(jnp.float32) * da[:, :, None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), new_state)
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------
# Full Mamba-2 block (projections + conv + SSD + gate)
# --------------------------------------------------------------------------

def mamba2_block(p, x, cfg, *, cache=None):
    """x: [B, S, d].  cache: None or dict(conv [B,K-1,dc], ssm [B,H,P,N]).

    Projections follow Mamba-2: in_proj -> (z gate, x, B, C, dt heads).
    """
    B, S, d = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner = H * P
    K = cfg.conv_kernel

    zxbcdt = dense(x, p["w_in"])            # [B,S, 2*d_inner + 2*N + H]
    z, xin, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + N,
                 2 * d_inner + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])          # [B,S,H]

    # depthwise causal conv over (x, B, C) as in Mamba-2
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)            # [B,S,dc]
    dc = conv_in.shape[-1]
    new_conv_state = None
    if cache is None:
        pad = jnp.zeros((B, K - 1, dc), conv_in.dtype)
        ci = jnp.concatenate([pad, conv_in], axis=1)
    else:
        ci = jnp.concatenate([cache["conv"], conv_in], axis=1)
        new_conv_state = ci[:, -(K - 1):]
    win = jnp.stack([ci[:, i:i + S] for i in range(K)], axis=-1)  # [B,S,dc,K]
    conv_out = jax.nn.silu(jnp.einsum("bsdk,dk->bsd", win, p["w_conv"]))
    xc, Bc, Cc = jnp.split(conv_out, [d_inner, d_inner + N], axis=-1)
    xc = xc.reshape(B, S, H, P)

    A = -jnp.exp(p["a_log"])                         # [H], negative
    new_ssm_state = None
    if cache is None:
        y = ssd_chunked(xc, dt, A, Bc, Cc, cfg.ssd_chunk)
    elif S > 1:
        # prefill-with-cache: also return the final recurrent state
        y, new_ssm_state = ssd_chunked(xc, dt, A, Bc, Cc, cfg.ssd_chunk,
                                       return_final=True)
    else:
        y1, new_ssm_state = ssd_decode_step(
            cache["ssm"], xc[:, 0], dt[:, 0], A, Bc[:, 0], Cc[:, 0])
        y = y1[:, None]

    y = y.reshape(B, S, d_inner)
    y = y * jax.nn.silu(z)
    out = dense(y, p["w_out"])
    if cache is not None:
        return out, {"conv": new_conv_state, "ssm": new_ssm_state}
    return out, None
