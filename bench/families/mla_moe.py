"""DeepSeek-V2 decoder (latent attention, dropless experts held in part):
weights, plain reference, counts.

Everything here belongs to the benchmark, not to the program under test:

* ``init_weights`` makes the weights from the seed on the device, in one
  jitted call, in the layout the program takes (the leading dense layers
  under ``lead``, the expert layers stacked on a leading axis under
  ``blocks``) and in the type they are served in.  Of each expert layer
  only the held experts are made: ``experts_first`` and on,
  ``n_routed_experts`` of them; the router spans the published count
  (``published.n_routed_experts``).
* ``reference`` is the published forward pass, not absorbed: the latent
  expanded into per-head keys and values, float32, ``HIGHEST`` matmul
  precision, no kernels, no cache, no batching tricks.  It regenerates
  each layer's weights from the seed itself, one layer at a time, and
  each routed expert from its global index, so any chip's share can be
  drawn.  Only the held experts and the shared experts contribute to an
  expert layer, as on the chip.  It imports nothing of the program.
* ``control`` is the same reference computed in float8 (e4m3, scaled per
  output channel and per token), the step below the bfloat16 the
  configuration states.  It is what a comparison has to fail.
* the count functions give the FLOPs and bytes the algorithm needs for
  this chip's share, from shapes alone.

Rope is rotate-half on the stored order of the rope dims; the published
model first de-interleaves them, a fixed permutation of the columns of
random weights (the configuration file lists this under ``assumed``).
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
LOGIT_CHUNK = 256       # positions per head matmul in the gap reduction


class Sizes(NamedTuple):
    layers: int
    lead: int               # leading dense layers
    d: int
    heads: int
    vocab: int
    eps: float
    rope_theta: float
    r: int                  # kv_lora_rank
    dn: int                 # qk_nope_head_dim
    dr: int                 # qk_rope_head_dim
    dv: int                 # v_head_dim
    dense_ffn: int
    moe_ffn: int
    shared: int             # shared experts, run as one SwiGLU
    experts: int            # routed experts the router spans (published)
    first: int              # first held expert
    held: int               # routed experts held on this chip
    top_k: int
    norm_topk: bool
    scaling: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def sizes(cfg: Dict) -> Sizes:
    y = cfg["rope_scaling"]
    return Sizes(
        int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"]),
        int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
        int(cfg["vocab_size"]), float(cfg["rms_norm_eps"]),
        float(cfg["rope_theta"]), int(cfg["kv_lora_rank"]),
        int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]), int(cfg["intermediate_size"]),
        int(cfg["moe_intermediate_size"]), int(cfg["n_shared_experts"]),
        int(cfg.get("published", cfg)["n_routed_experts"]),
        int(cfg["experts_first"]),
        int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"]),
        bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]),
        float(y["factor"]), int(y["original_max_position_embeddings"]),
        float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
        float(y["mscale_all_dim"]))


def program_config(cfg: Dict):
    """The program's ModelConfig for this file: its named architecture
    with every size taken from the file, holding this chip's experts."""
    import dataclasses
    from repro.configs import get_config
    s = sizes(cfg)
    assert not s.norm_topk, "the program's softmax_topk router keeps " \
        "the top-k weights as they are"
    return dataclasses.replace(
        get_config(cfg["program_arch"]), n_layers=s.layers, d_model=s.d,
        n_heads=s.heads, n_kv_heads=s.heads, head_dim=s.dn + s.dr,
        d_ff=s.dense_ffn, vocab=s.vocab, rope_theta=s.rope_theta,
        lead_blocks=("mla",) * s.lead, block_pattern=("mla_moe",),
        n_experts=s.experts, experts_first=s.first, experts_held=s.held,
        top_k=s.top_k, n_shared_experts=s.shared, moe_d_ff=s.moe_ffn,
        router="softmax_topk", routed_scaling=s.scaling, kv_lora_rank=s.r,
        qk_nope_head_dim=s.dn, qk_rope_head_dim=s.dr, v_head_dim=s.dv,
        yarn_factor=s.yarn_factor, yarn_original_max_len=s.yarn_original,
        yarn_beta_fast=s.beta_fast, yarn_beta_slow=s.beta_slow,
        yarn_mscale=s.mscale, yarn_mscale_all_dim=s.mscale_all_dim,
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def _attn_shapes(s: Sizes):
    """(leaf name, shape, init std) of a layer's attention and norms; norms
    store weight - 1."""
    d, H = s.d, s.heads
    return (("norm1", (d,), 0.1),
            ("wq", (d, H * (s.dn + s.dr)), (2 / d) ** 0.5),
            ("wkv_a", (d, s.r + s.dr), (2 / d) ** 0.5),
            ("kv_norm", (s.r,), 0.1),
            ("wkv_b", (s.r, H * (s.dn + s.dv)), (2 / s.r) ** 0.5),
            ("wo", (H * s.dv, d), (2 / (H * s.dv)) ** 0.5),
            ("norm2", (d,), 0.1))


def _ffn_shapes(prefix: str, d: int, f: int):
    return ((prefix + "w_gate", (d, f), (2 / d) ** 0.5),
            (prefix + "w_up", (d, f), (2 / d) ** 0.5),
            (prefix + "w_down", (f, d), (2 / f) ** 0.5))


def _dense_shapes(s: Sizes):
    return _attn_shapes(s) + _ffn_shapes("", s.d, s.dense_ffn)


def _moe_shapes(s: Sizes):
    """An expert layer's leaves, its routed experts aside."""
    return (_attn_shapes(s)
            + (("w_router", (s.d, s.experts), (1 / s.d) ** 0.5),)
            + _ffn_shapes("shared_", s.d, s.moe_ffn * s.shared))


def _expert_shapes(s: Sizes):
    return _ffn_shapes("expert_", s.d, s.moe_ffn)


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


def _weights(shapes, key, layer):
    return {name: _draw(jax.random.fold_in(_leaf_key(key, name), layer),
                        shape, std)
            for name, shape, std in shapes}


def dense_weights(s: Sizes, key, layer):
    """A leading dense layer's bf16 weights; ``layer`` may be traced."""
    return _weights(_dense_shapes(s), key, layer)


def moe_weights(s: Sizes, key, layer):
    """An expert layer's bf16 weights, its held routed experts stacked
    [held, ...] (global index ``first + i``); ``layer`` may be traced."""
    w = _weights(_moe_shapes(s), key, layer)
    ids = jnp.arange(s.first, s.first + s.held)
    for name, shape, std in _expert_shapes(s):
        k = jax.random.fold_in(_leaf_key(key, name), layer)
        w[name] = jax.vmap(lambda e, k=k, shape=shape, std=std: _draw(
            jax.random.fold_in(k, e), shape, std))(ids)
    return w


def top_weights(s: Sizes, key):
    return {"embed": _draw(_leaf_key(key, "embed"), (s.vocab, s.d),
                           s.d ** -0.5),
            "lm_head": _draw(_leaf_key(key, "lm_head"), (s.vocab, s.d),
                             s.d ** -0.5),
            "final_norm": _draw(_leaf_key(key, "final_norm"), (s.d,), 0.1)}


def _program_block(s: Sizes, w, moe: bool):
    H = s.heads
    kv_b = w["wkv_b"].reshape(w["wkv_b"].shape[:-1] + (H, s.dn + s.dv))
    lead = kv_b.shape[:-2]
    attn = {"wq": w["wq"], "wkv_a": w["wkv_a"], "kv_norm": w["kv_norm"],
            "w_uk": kv_b[..., :s.dn].reshape(lead + (H * s.dn,)),
            "w_uv": kv_b[..., s.dn:].reshape(lead + (H * s.dv,)),
            "wo": w["wo"]}
    block = {"norm1": {"scale": w["norm1"]}, "attn": attn,
             "norm2": {"scale": w["norm2"]}}
    if moe:
        block["moe"] = {
            "w_router": w["w_router"], "w_gate": w["expert_w_gate"],
            "w_up": w["expert_w_up"], "w_down": w["expert_w_down"],
            "shared": {k: w["shared_" + k]
                       for k in ("w_gate", "w_up", "w_down")}}
    else:
        block["mlp"] = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    return block


def init_weights(cfg: Dict, seed: int):
    """The program's params pytree, made on the default device in one
    jitted call from the seed."""
    s = sizes(cfg)

    @jax.jit
    def make(key):
        lead = [_program_block(s, dense_weights(s, key, i), False)
                for i in range(s.lead)]
        stacked = jax.vmap(lambda i: moe_weights(s, key, i))(
            jnp.arange(s.lead, s.layers))
        top = top_weights(s, key)
        return {"embed": top["embed"], "lm_head": top["lm_head"],
                "final_norm": {"scale": top["final_norm"]}, "lead": lead,
                "blocks": [_program_block(s, stacked, True)], "extra": []}
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


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_inv_freq(s: Sizes):
    """DeepseekV2YarnRotaryEmbedding's frequencies over the rope dims."""
    dim, base = s.dr, s.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(s.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(s.beta_fast)), 0)
    high = min(math.ceil(correction_dim(s.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (s.yarn_factor
                   * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return jnp.asarray(inter * (1 - mask) + extra * mask, jnp.float32)


def _rope(s: Sizes, x):
    """Rotate-half rope with YaRN frequencies over positions 0..T-1;
    x [B, T, H, dr]."""
    T = x.shape[1]
    half = s.dr // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * _yarn_inv_freq(s)
    m = (_yarn_get_mscale(s.yarn_factor, s.mscale)
         / _yarn_get_mscale(s.yarn_factor, s.mscale_all_dim))
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(s: Sizes) -> float:
    scale = (s.dn + s.dr) ** -0.5
    if s.mscale_all_dim:
        m = _yarn_get_mscale(s.yarn_factor, s.mscale_all_dim)
        scale *= m * m
    return scale


def _mla(s: Sizes, w, h, low: bool):
    """The published (expanded) latent attention over h [B, T, d]."""
    B, T, _ = h.shape
    H = s.heads
    q = _mm(h, w["wq"], low).reshape(B, T, H, s.dn + s.dr)
    ckv = _mm(h, w["wkv_a"], low)
    c = _rms(ckv[..., :s.r], w["kv_norm"], s.eps)
    kv = _mm(c, w["wkv_b"], low).reshape(B, T, H, s.dn + s.dv)
    q_pe = _rope(s, q[..., s.dn:])
    k_pe = _rope(s, ckv[..., None, s.r:])
    q = jnp.concatenate([q[..., :s.dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :s.dn],
                         jnp.broadcast_to(k_pe, (B, T, H, s.dr))], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=HIGHEST) * softmax_scale(s)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, NEG_INF), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., s.dn:], precision=HIGHEST)
    return _mm(o.reshape(B, T, H * s.dv), w["wo"], low)


def _swiglu(h, wg, wu, wd, low):
    return _mm(jax.nn.silu(_mm(h, wg, low)) * _mm(h, wu, low), wd, low)


def _experts(s: Sizes, w, h, low: bool):
    """Softmax over all routed experts, greedy top-k (renormalised only
    where the file says so, times the scaling factor); the held experts'
    share of the routed sum, plus the shared experts."""
    probs = jax.nn.softmax(_mm(h, w["w_router"], low), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * s.scaling
    held = s.first + jnp.arange(s.held)
    gate = jnp.sum(jnp.where(top_i[..., None] == held, top_w[..., None],
                             0.0), axis=-2)                  # [B, T, held]
    routed = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd, low))(
        w["expert_w_gate"], w["expert_w_up"], w["expert_w_down"])
    out = jnp.einsum("bte,ebtd->btd", gate, routed, precision=HIGHEST)
    return out + _swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                         w["shared_w_down"], low)


def _block(s: Sizes, w, x, low: bool, moe: bool):
    """One pre-norm decoder layer over x [B, T, d] float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + _mla(s, w, _rms(x, w["norm1"], s.eps), low)
    h = _rms(x, w["norm2"], s.eps)
    if moe:
        return x + _experts(s, w, h, low)
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], low)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_step(s: Sizes, key, layer, xs, with_control: bool):
    """An expert layer (``layer`` >= ``s.lead``, may be traced)."""
    w = moe_weights(s, key, layer)
    out = [_block(s, w, xs[0], False, True)]
    if with_control:
        out.append(_block(s, w, xs[1], True, True))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _dense_step(s: Sizes, key, layer, xs, with_control: bool):
    """A leading dense layer (``layer`` < ``s.lead``)."""
    w = dense_weights(s, key, layer)
    out = [_block(s, w, xs[0], False, False)]
    if with_control:
        out.append(_block(s, w, xs[1], True, False))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _embed(s: Sizes, key, tokens, _unused, with_control: bool):
    x = top_weights(s, key)["embed"][tokens].astype(jnp.float32)
    return (x, x) if with_control else (x,)


def _logits(s: Sizes, top, x, low: bool):
    """Head logits [B, T, vocab] of x [B, T, d]."""
    h = _rms(x, top["final_norm"], s.eps)
    return _mm(h, top["lm_head"].astype(jnp.float32).T, low)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _gaps(s: Sizes, key, xs, targets, with_control: bool):
    """Per position: how far the reference's logit of ``targets`` lies
    below its best logit, and (with the control) how far the token the
    control ranks first lies below it.  The head runs over chunks of
    ``LOGIT_CHUNK`` positions, so no [T, vocab] block is held whole."""
    top = top_weights(s, key)
    T = xs[0].shape[1]
    n = -(-T // LOGIT_CHUNK)
    pad = n * LOGIT_CHUNK - T

    def chunks(a, fill):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2),
                    constant_values=fill)
        a = a.reshape((a.shape[0], n, LOGIT_CHUNK) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    def one(args):
        x, tgt = args[0], args[1]
        logits = _logits(s, top, x, False)
        best = logits.max(-1)
        t = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[..., None],
                                -1)[..., 0]
        out = [best - t]
        if with_control:
            first = jnp.argmax(_logits(s, top, args[2], True), -1)
            out.append(best - jnp.take_along_axis(logits, first[..., None],
                                                  -1)[..., 0])
        return tuple(out)

    args = (chunks(xs[0], 0.0), chunks(targets, -1))
    if with_control:
        args += (chunks(xs[1], 0.0),)
    outs = jax.lax.map(one, args)
    return tuple(jnp.moveaxis(o, 0, 1).reshape(o.shape[1], -1)[:, :T]
                 for o in outs)


def reference_logits(cfg: Dict, seed: int, tokens, *, low: bool = False):
    """Logits [n, T, vocab] of the reference over tokens [n, T] (of the
    float8 control with ``low``), whole: for small sizes."""
    s = sizes(cfg)
    key = seed_key(seed)
    xs = _embed(s, key, jnp.asarray(tokens), None, low)
    for layer in range(s.layers):
        step = _dense_step if layer < s.lead else _layer_step
        xs = step(s, key, jnp.int32(layer), xs, low)
    return _logits(s, top_weights(s, key), xs[-1], low)


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
            step = _dense_step if layer < s.lead else _layer_step
            xs = step(s, key, jnp.int32(layer), xs, control)
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
# counts from shapes (this chip's share of the experts)
# --------------------------------------------------------------------------

def _count(shapes) -> int:
    return sum(math.prod(shape) for _, shape, _ in shapes)


def expert_params(s: Sizes) -> int:
    """One routed expert's weights."""
    return _count(_expert_shapes(s))


def held_expert_params(s: Sizes) -> int:
    """Every held routed expert of every expert layer."""
    return (s.layers - s.lead) * s.held * expert_params(s)


def param_count(s: Sizes) -> int:
    """Parameters held on this chip."""
    return (s.lead * _count(_dense_shapes(s))
            + (s.layers - s.lead) * _count(_moe_shapes(s))
            + held_expert_params(s) + 2 * s.vocab * s.d + s.d)


def param_bytes(s: Sizes, itemsize: int = 2) -> int:
    return param_count(s) * itemsize


def latent_bytes_per_token(s: Sizes, itemsize: int = 2) -> int:
    """One token's cached [c ; k_pe] rows over every layer."""
    return s.layers * (s.r + s.dr) * itemsize


def matmul_params(s: Sizes) -> float:
    """Weights a token multiplies through in the layers (norms excluded):
    of an expert layer's routed experts, top_k x held / experts, the
    share of its routed pairs that this chip computes."""
    norms = 2 * s.d + s.r
    attn = _count(_attn_shapes(s)) - norms
    dense = attn + _count(_ffn_shapes("", s.d, s.dense_ffn))
    moe = (_count(_moe_shapes(s)) - norms
           + s.top_k * s.held / s.experts * expert_params(s))
    return s.lead * dense + (s.layers - s.lead) * moe


def prompt_flops(s: Sizes, length: int) -> float:
    """Forward FLOPs of a prompt of ``length`` real tokens, causal, with
    logits for the last position only; attention expanded (per head,
    scores over nope + rope and values over v)."""
    pairs = length * (length + 1) // 2
    attn = 2 * s.layers * s.heads * (s.dn + s.dr + s.dv) * pairs
    return 2 * matmul_params(s) * length + attn + 2 * s.d * s.vocab


def decode_token_flops(s: Sizes, keys: int) -> float:
    """One generated token attending ``keys`` positions (itself included),
    absorbed: per key and head, a score over the latent and rope key and
    a weighted sum over the latent."""
    attn = 2 * s.layers * s.heads * (2 * s.r + s.dr) * keys
    return 2 * matmul_params(s) + attn + 2 * s.d * s.vocab


def decode_step_bytes(s: Sizes, rows: float, keys_per_row: float) -> float:
    """Bytes a decode step needs: every weight but the routed experts read
    once, each held expert read where some active row routes to it
    (1 - (1 - top_k / experts) ** rows of the time), the valid latent rows
    of each active row read, one new latent row per active row written."""
    lat = latent_bytes_per_token(s)
    touched = 1.0 - (1.0 - s.top_k / s.experts) ** rows
    experts = 2 * held_expert_params(s)
    return (param_bytes(s) - experts + experts * touched
            + rows * keys_per_row * lat + rows * lat)
