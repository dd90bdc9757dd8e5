"""DeepSeek-V2's decoder on the serving path, against the plain reference.

At a tiny size (d 64, 4 heads, latent 32, rope 16, nope and v 32, 16
routed experts of which 4 are held, top-3, one leading dense layer and 4
expert layers) the program's logits are compared, in float32, with
``bench/families/mla_moe.py``'s reference on the same seeded weights:
the published, non-absorbed latent attention, with only the held experts
and the shared experts in each expert layer.

Tolerances:

* ``TOL`` (1e-3 on logits of magnitude ~4): program and reference are
  both float32; they differ only in the order of summation (the CPU's
  matmuls against ``HIGHEST``, the absorbed decode against the expanded
  form), which moves logits by ~1e-5.  A bfloat16 program or the float8
  control moves them by 1e-1 or more, and fails it.
* served tokens are held to a reference gap of at most ``TOL``: a float32
  program serves the reference's best token, or one within summation
  order of it.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MOE
from repro.models.mla import mla_attention
from repro.serve.engine import Request
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.serve.scheduler import pool_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "bench") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "bench"))
import spec  # noqa: E402

TOL = 1e-3
SEED = 2 ** 33 + 5

# jitted whole, so that each check compiles once instead of running op
# by op on the CPU
forward = jax.jit(M.forward, static_argnums=0)
dropless = jax.jit(MOE.moe_dropless, static_argnums=2)
capacity_bound = jax.jit(MOE.moe_block, static_argnums=2)
prefill = jax.jit(M.prefill, static_argnums=(0, 3))
decode_step = jax.jit(M.decode_step, static_argnums=0)
attention = jax.jit(mla_attention, static_argnums=2)


@pytest.fixture(scope="module")
def fam():
    return spec.Benchmark(ROOT).family("mla_moe")


def tiny(first=4, held=4, **kw):
    """deepseek-v2-lite-serve's file at test widths: 16 routed experts,
    ``held`` of them from ``first`` held here."""
    cfg = spec.Benchmark(ROOT).config("deepseek-v2-lite-serve")
    cfg.update(num_hidden_layers=5, first_k_dense_replace=1, hidden_size=64,
               num_attention_heads=4, kv_lora_rank=32, qk_rope_head_dim=16,
               qk_nope_head_dim=32, v_head_dim=32, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=held,
               experts_first=first, num_experts_per_tok=3, vocab_size=256)
    cfg["published"] = {"n_routed_experts": 16}
    cfg.update(kw)
    return cfg


def program_cfg(fam, cfg, dtype=jnp.float32):
    name = jnp.dtype(dtype).name
    return dataclasses.replace(fam.program_config(cfg), dtype=name,
                               param_dtype=name)


def program(fam, cfg, seed=SEED, dtype=jnp.float32):
    """The program's config and the seeded weights, in ``dtype``."""
    params = jax.tree.map(lambda a: a.astype(dtype),
                          fam.init_weights(cfg, seed))
    return program_cfg(fam, cfg, dtype), params


def tokens(n, T, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n, T)).astype(
        np.int32)


def gap(a, b):
    return float(jnp.abs(jnp.asarray(a, jnp.float32)
                         - jnp.asarray(b, jnp.float32)).max())


def test_full_forward_matches_reference(fam):
    cfg = tiny()
    mcfg, params = program(fam, cfg)
    tok = tokens(2, 24)
    want = fam.reference_logits(cfg, SEED, tok)
    assert gap(forward(mcfg, params, tok), want) <= TOL


def test_prefill_then_decode_matches_reference(fam):
    """Prefill 10 tokens, then decode 6 through the latent cache: each
    step's logits are the reference's full forward at that position."""
    cfg = tiny()
    mcfg, params = program(fam, cfg)
    tok = tokens(2, 16, seed=1)
    want = fam.reference_logits(cfg, SEED, tok)
    logits, cache = prefill(mcfg, params, tok[:, :10], 32)
    assert gap(logits, want[:, 9]) <= TOL
    for t in range(10, 16):
        logits, cache = decode_step(mcfg, params, cache, tok[:, t:t + 1])
        assert gap(logits, want[:, t]) <= TOL, t
    assert set(cache["lead"][0]) == {"ckv"}
    assert cache["layers"][0]["ckv"].shape == (4, 2, 32, 32 + 16)


def test_absorbed_decode_matches_expanded(fam):
    """The absorbed single-token step (per-row positions, stale rows past
    each position in the cache) gives the expanded attention's output."""
    cfg = tiny()
    mcfg, params = program(fam, cfg)
    p = params["lead"][0]["attn"]
    B, T = 3, 12
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, 64), jnp.float32)
    full, _ = attention(p, x, mcfg, positions=jnp.arange(T)[None])
    cache = {"ckv": jnp.zeros((B, 16, 48), jnp.float32),
             "len": jnp.int32(0)}
    _, cache = attention(p, x, mcfg, positions=jnp.arange(T)[None],
                             cache=cache)
    pos = jnp.array([11, 7, 4])
    xq = x[jnp.arange(B), pos][:, None]
    out, row = attention(p, xq, mcfg, positions=pos[:, None],
                             cache={"ckv": cache["ckv"], "len": pos})
    assert gap(out[:, 0], full[jnp.arange(B), pos]) <= 1e-5
    # the row handed back is the one the expanded pass wrote there
    assert gap(row["ckv_row"], cache["ckv"][jnp.arange(B), pos]) <= 1e-6


def _serve(fam, cfg, reqs, use_terra=True, cap=2):
    mcfg, params = program(fam, cfg)
    sch = ContinuousBatchingScheduler(mcfg, params, max_slots=4, max_len=48,
                                      prefill_batch_cap=cap,
                                      use_terra=use_terra)
    sch.serve(reqs)
    st = sch.stats
    sch.close()
    return st


def _requests(n=10, seed=1, lengths=None):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(3, 30, n) if lengths is None else lengths
    return [Request(prompt=rng.integers(0, 256, int(L), dtype=np.int32),
                    max_new_tokens=int(m), arrival_time=0.0)
            for L, m in zip(lengths, rng.integers(2, 12, len(lengths)))]


def test_scheduler_serves_the_reference(fam):
    """Co-executed slot-pool serving, prompts of mixed lengths padded to
    their buckets: every served token is the reference's best (within
    TOL), through prefill and decode."""
    cfg = tiny()
    reqs = _requests()
    st = _serve(fam, cfg, reqs)
    assert st["phase"] == "co-execution"
    assert st["kv_append_leaves"] == 2      # the leading and stacked leaf
    assert st["experts_held"] == 4 and st["expert_dropped"] == 0
    import check

    class Served:
        def __init__(self, r):
            self.prompt, self.tokens = r.prompt, r.out_tokens
    g = check.served_gaps(fam, cfg, SEED, [Served(r) for r in reqs], 48)
    assert g["tokens"] == sum(r.max_new_tokens for r in reqs)
    assert g["max_logit_gap"] <= TOL, g


def _moe_params(fam, cfg, layer=1):
    s = fam.sizes(cfg)
    w = jax.jit(fam.moe_weights, static_argnums=0)(s, fam.seed_key(SEED),
                                                   layer)
    block = fam._program_block(s, w, True)
    return jax.tree.map(lambda a: a.astype(jnp.float32), block["moe"]), w


def _ref_experts(fam, cfg, w, x):
    """The reference's expert layer (held and shared experts), float32."""
    f = jax.jit(fam._experts, static_argnums=(0, 3))
    return f(fam.sizes(cfg), {k: v.astype(jnp.float32)
                              for k, v in w.items()}, x, False)


@pytest.mark.parametrize("T", [16, 80], ids=["dense", "grouped"])
def test_chip_share_sum_is_the_uncut_layer(fam, T):
    """The four held ranges' outputs, with the shared experts (which every
    chip computes) counted once, add up to the uncut layer: in the
    program, on its dense (decode) and grouped (prefill) paths, and in
    the reference."""
    x = jax.random.normal(jax.random.PRNGKey(T), (2, T // 2, 64),
                          jnp.float32)
    mcfg = program_cfg(fam, tiny(0, 16))
    p, w = _moe_params(fam, tiny(0, 16))
    uncut, n_all = dropless(p, x, mcfg)
    ref_uncut = _ref_experts(fam, tiny(0, 16), w, x)
    assert gap(uncut, ref_uncut) <= 1e-4
    parts, refs, n_parts = [], [], []
    for first in (0, 4, 8, 12):
        cfg = tiny(first, 4)
        mc = program_cfg(fam, cfg)
        ps, ws = _moe_params(fam, cfg)
        y, n = dropless(ps, x, mc)
        parts.append(y)
        n_parts.append(n)
        refs.append(_ref_experts(fam, cfg, ws, x))
    shared = L.mlp_swiglu(p["shared"], x)
    assert gap(sum(parts) - 3 * shared, uncut) <= 1e-4
    assert gap(sum(refs) - 3 * shared, ref_uncut) <= 1e-4
    np.testing.assert_array_equal(np.concatenate(n_parts, axis=1), n_all)
    assert int(n_all.sum()) == T * 3


def test_dropless_under_a_skewed_router(fam):
    """Every token routes to held expert 4: the dropless layer computes
    all of them (the reference's answer); a capacity-bounded layer at
    capacity factor 1.25 drops most."""
    cfg = tiny(4, 4)
    mcfg = program_cfg(fam, cfg)
    p, w = _moe_params(fam, cfg)
    p["w_router"] = p["w_router"].at[:, 4].set(1.0)
    w = dict(w, w_router=w["w_router"].at[:, 4].set(1.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 48, 64))) + 1
    y, n = dropless(p, x, mcfg)
    assert int(n[:, 0].sum()) == 96         # every token at expert 4
    ref = _ref_experts(fam, cfg, w, x)
    assert gap(y, ref) <= 1e-4
    # the same routing through the capacity-bounded layer, all 16 held
    full = dataclasses.replace(mcfg, experts_first=0, experts_held=0,
                               capacity_factor=1.25)
    pf, _ = _moe_params(fam, tiny(0, 16))
    pf["w_router"] = pf["w_router"].at[:, 4].set(1.0)
    yd, _ = dropless(pf, x, full)
    assert gap(capacity_bound(pf, x, full), yd) > 1e-2


@pytest.mark.parametrize("use_terra", [True, False],
                         ids=["co-execution", "jitted"])
def test_expert_counter_counts_routed_pairs(fam, use_terra):
    """With every expert held, each real token a step computes is routed
    to top_k of them in each of the 4 expert layers: prompt tokens (not
    their bucket padding, nor the row a 3-request prefill repeats to make
    4) at prefill, one token per active row at decode.  Nothing is
    dropped."""
    lengths = [10, 12, 9, 30, 5, 20, 14, 7]     # first group: 3 at 16
    reqs = _requests(seed=2, lengths=lengths)
    st = _serve(fam, tiny(0, 16), reqs, use_terra, cap=4)
    computed = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert sum(st["expert_tokens"]) == 4 * 3 * computed
    assert len(st["expert_tokens"]) == 16 and st["expert_dropped"] == 0
    part = _serve(fam, tiny(4, 4), _requests(seed=2, lengths=lengths),
                  use_terra, cap=4)
    assert 0 < sum(part["expert_tokens"]) < 4 * 3 * computed
    assert len(part["expert_tokens"]) == 4


def test_lower_precision_fails_the_tolerance(fam):
    """TOL separates the float32 program from a bfloat16 program and from
    the float8 control on the same tokens."""
    cfg = tiny()
    tok = tokens(2, 24, seed=4)
    want = fam.reference_logits(cfg, SEED, tok)
    mcfg, params = program(fam, cfg)
    assert gap(forward(mcfg, params, tok), want) <= TOL
    bcfg, bparams = program(fam, cfg, dtype=jnp.bfloat16)
    assert gap(forward(bcfg, bparams, tok), want) > 10 * TOL
    assert gap(fam.reference_logits(cfg, SEED, tok, low=True), want) \
        > 10 * TOL


def test_paged_pool_refuses_the_latent_cache(fam):
    mcfg = program_cfg(fam, tiny())
    with pytest.raises(NotImplementedError, match="latent"):
        pool_ops.check_supported(mcfg, page_size=16)
    pool_ops.check_supported(mcfg)
