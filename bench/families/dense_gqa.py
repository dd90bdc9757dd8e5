"""Dense GQA decoder (Llama-style blocks): weights, plain reference, counts.

Everything here belongs to the benchmark, not to the program under test:

* ``init_weights`` makes the weights from the seed on the device, in one
  jitted call, in the layout the program takes (a params pytree with the
  layers stacked on a leading axis) and in the type they are served in.
* ``reference`` is the plain forward pass: float32, ``HIGHEST`` matmul
  precision, no kernels, no cache, no batching tricks.  It regenerates
  each layer's weights from the seed itself, one layer at a time, so it
  takes nothing the program made and needs little device memory.  It
  imports nothing of the program.
* ``control`` is the same reference computed in float8 (e4m3, scaled per
  output channel and per token), the step below the bfloat16 the
  configuration states.  It is what a comparison has to fail.
* the count functions give the FLOPs and bytes the algorithm needs,
  from shapes alone.

A configuration file (``bench/configs/*.json``) gives the sizes under the
Hugging Face ``config.json`` key names.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


class Sizes(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float


def sizes(cfg: Dict) -> Sizes:
    return Sizes(int(cfg["num_hidden_layers"]), int(cfg["hidden_size"]),
                 int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
                 int(cfg["intermediate_size"]), int(cfg["vocab_size"]),
                 float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))


def program_config(cfg: Dict):
    """The program's ModelConfig for this file: its named architecture
    with every size taken from the file."""
    import dataclasses
    from repro.configs import get_config
    s = sizes(cfg)
    return dataclasses.replace(
        get_config(cfg["program_arch"]), n_layers=s.layers, d_model=s.d,
        n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.ffn, vocab=s.vocab, rope_theta=s.rope_theta,
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def _layer_shapes(s: Sizes):
    """(leaf name, shape, init std) of one layer; norms store weight - 1."""
    d, hq, hkv = s.d, s.heads * s.head_dim, s.kv_heads * s.head_dim
    return (("norm1", (d,), 0.1),
            ("wq", (d, hq), (2 / d) ** 0.5),
            ("wk", (d, hkv), (2 / d) ** 0.5),
            ("wv", (d, hkv), (2 / d) ** 0.5),
            ("wo", (hq, d), (2 / hq) ** 0.5),
            ("norm2", (d,), 0.1),
            ("w_gate", (d, s.ffn), (2 / d) ** 0.5),
            ("w_up", (d, s.ffn), (2 / d) ** 0.5),
            ("w_down", (s.ffn, d), (2 / s.ffn) ** 0.5))


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too): the seed's two low 32-bit words."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_weights(s: Sizes, key, layer):
    """One layer's bf16 weights; ``layer`` may be traced."""
    return {name: _draw(jax.random.fold_in(_leaf_key(key, name), layer),
                        shape, std)
            for name, shape, std in _layer_shapes(s)}


def top_weights(s: Sizes, key):
    return {"embed": _draw(_leaf_key(key, "embed"), (s.vocab, s.d),
                           s.d ** -0.5),
            "final_norm": _draw(_leaf_key(key, "final_norm"), (s.d,), 0.1)}


def init_weights(cfg: Dict, seed: int):
    """The program's params pytree, made on the default device in one
    jitted call from the seed."""
    s = sizes(cfg)

    @jax.jit
    def make(key):
        stacked = jax.vmap(lambda i: layer_weights(s, key, i))(
            jnp.arange(s.layers))
        top = top_weights(s, key)
        block = {"norm1": {"scale": stacked["norm1"]},
                 "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
                 "norm2": {"scale": stacked["norm2"]},
                 "mlp": {k: stacked[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}
        return {"embed": top["embed"],
                "final_norm": {"scale": top["final_norm"]},
                "blocks": [block], "extra": []}
    return make(seed_key(seed))


# --------------------------------------------------------------------------
# plain reference (float32, HIGHEST) and its float8 control
# --------------------------------------------------------------------------

def _fq(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis`` (the
    reduction axis of the matmul it feeds), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = 448.0 / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(x, w, low):
    """x [..., k] @ w [k, n] in float32; ``low`` rounds both operands to
    float8 first."""
    if low:
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, w_minus_1, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w_minus_1.astype(jnp.float32))


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..T-1; x [B,T,H,D]."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv    # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(s: Sizes, w, x, low: bool):
    """One pre-norm decoder block over x [B, T, d] float32."""
    B, T, _ = x.shape
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, w["norm1"], s.eps)
    q = _mm(h, f32["wq"], low).reshape(B, T, s.heads, s.head_dim)
    k = _mm(h, f32["wk"], low).reshape(B, T, s.kv_heads, s.head_dim)
    v = _mm(h, f32["wv"], low).reshape(B, T, s.kv_heads, s.head_dim)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    g = s.heads // s.kv_heads
    q = q.reshape(B, T, s.kv_heads, g, s.head_dim)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                    precision=HIGHEST) * s.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, NEG_INF), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, T, s.heads * s.head_dim), f32["wo"], low)
    h = _rms(x, w["norm2"], s.eps)
    u = jax.nn.silu(_mm(h, f32["w_gate"], low)) * _mm(h, f32["w_up"], low)
    return x + _mm(u, f32["w_down"], low)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_step(s: Sizes, key, layer, xs, with_control: bool):
    w = layer_weights(s, key, layer)
    out = [_block(s, w, xs[0], False)]
    if with_control:
        out.append(_block(s, w, xs[1], True))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _embed(s: Sizes, key, tokens, _unused, with_control: bool):
    x = top_weights(s, key)["embed"][tokens].astype(jnp.float32)
    return (x, x) if with_control else (x,)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _gaps(s: Sizes, key, xs, targets, with_control: bool):
    """Per position: how far the reference's logit of ``targets`` lies
    below its best logit, and (with the control) how far the token the
    control ranks first lies below it."""
    top = top_weights(s, key)
    emb = top["embed"].astype(jnp.float32)
    logits = jnp.einsum("btd,vd->btv", _rms(xs[0], top["final_norm"], s.eps),
                        emb, precision=HIGHEST)
    best = logits.max(-1)
    tgt = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                              -1)[..., 0]
    out = [best - tgt]
    if with_control:
        low = _mm(_rms(xs[1], top["final_norm"], s.eps), emb.T, True)
        first = jnp.argmax(low, -1)
        out.append(best - jnp.take_along_axis(logits, first[..., None],
                                              -1)[..., 0])
    return tuple(out)


def reference_gaps(cfg: Dict, seed: int, tokens: np.ndarray,
                   targets: np.ndarray, *, control: bool = False,
                   rows_per_call: int = 2):
    """tokens/targets [n, T] int32; ``targets[i, t]`` is the token the
    program served after position t, or -1.  Returns the reference's gap
    at every position with a target (and the control's, with
    ``control``), as float32 arrays [n, T] (nan where no target)."""
    s = sizes(cfg)
    key = seed_key(seed)
    n = tokens.shape[0]
    pad = (-n) % rows_per_call
    tok = np.concatenate([tokens, np.zeros((pad,) + tokens.shape[1:],
                                           tokens.dtype)])
    tgt = np.concatenate([targets, np.full((pad,) + targets.shape[1:], -1,
                                           targets.dtype)])
    served, ctl = [], []
    for r in range(0, tok.shape[0], rows_per_call):
        t = jnp.asarray(tok[r:r + rows_per_call])
        xs = _embed(s, key, t, None, control)
        for layer in range(s.layers):
            xs = _layer_step(s, key, jnp.int32(layer), xs, control)
        g = _gaps(s, key, xs, jnp.asarray(tgt[r:r + rows_per_call]),
                  control)
        served.append(np.asarray(g[0]))
        if control:
            ctl.append(np.asarray(g[1]))
    mask = tgt[:n] >= 0

    def cut(parts):
        a = np.concatenate(parts)[:n].astype(np.float32)
        return np.where(mask, a, np.nan)
    return (cut(served), cut(ctl)) if control else (cut(served), None)


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------

def layer_params(s: Sizes) -> int:
    return sum(math.prod(shape) for _, shape, _ in _layer_shapes(s))


def param_count(s: Sizes) -> int:
    return s.layers * layer_params(s) + s.vocab * s.d + s.d


def matmul_params(s: Sizes) -> int:
    """Weights a token multiplies through in the layers (norms excluded)."""
    return s.layers * (layer_params(s) - 2 * s.d)


def kv_bytes_per_token(s: Sizes, itemsize: int = 2) -> int:
    return s.layers * 2 * s.kv_heads * s.head_dim * itemsize


def param_bytes(s: Sizes, itemsize: int = 2) -> int:
    return param_count(s) * itemsize


def _attn_flops(s: Sizes, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` positions."""
    return 4 * s.layers * s.heads * s.head_dim * keys


def prompt_flops(s: Sizes, length: int) -> int:
    """Forward FLOPs of a prompt of ``length`` real tokens, causal, with
    logits for the last position only."""
    return (2 * matmul_params(s) * length
            + _attn_flops(s, length * (length + 1) // 2)
            + 2 * s.d * s.vocab)


def decode_token_flops(s: Sizes, keys: int) -> int:
    """One generated token attending ``keys`` positions (itself included)."""
    return 2 * matmul_params(s) + _attn_flops(s, keys) + 2 * s.d * s.vocab


def decode_step_bytes(s: Sizes, rows: float, keys_per_row: float) -> float:
    """Bytes a decode step needs: the params read once, the valid KV of
    each active row read, one new KV row per active row written."""
    kv = kv_bytes_per_token(s)
    return param_bytes(s) + rows * keys_per_row * kv + rows * kv


def train_flops(s: Sizes, batch: int, seq: int) -> int:
    """Forward and backward FLOPs of one step: 6 x params x tokens plus
    causal attention (three times its forward)."""
    tokens = batch * seq
    return (6 * param_count(s) * tokens
            + 3 * batch * _attn_flops(s, seq * (seq + 1) // 2))


def train_state_bytes(s: Sizes) -> int:
    """bf16 params, f32 master copy and two f32 AdamW moments."""
    return param_count(s) * (2 + 4 + 4 + 4)
