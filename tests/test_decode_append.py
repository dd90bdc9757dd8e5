"""Single-token decode appends its K/V instead of rewriting the cache
(models/transformer.py ``append_rows``, DESIGN.md §11).

The reference is today's write-then-attend decode, built here from
``decode_attention``: write the new K/V at each row's position with a
``dynamic_update_slice`` (vmapped for per-row positions), then attend over
the written cache.  The append path must give the same logits (float32
tolerance) and, where the rows are computed from the same inputs, the same
cache bit for bit — including the clamped write of a stale row whose
position is at or beyond ``max_len``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import layers as L
from repro.models import model as M
from repro.models import transformer as T
from repro.models.attention import decode_attention
from repro.serve.engine import Request
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.serve.scheduler import pool_ops

ATTN = ("attn", "attn_swa", "attn_local", "moe")


def _cfg(arch, dtype="float32"):
    return dataclasses.replace(smoke_config(arch), dtype=dtype,
                               param_dtype=dtype)


def _random_cache(cfg, B, max_len, key):
    """A pool cache full of noise, so any unmasked stale entry shows."""
    cache = M.init_cache(cfg, B, max_len)
    leaves, tdef = jax.tree_util.tree_flatten(
        {"layers": cache["layers"], "extra": cache["extra"]})
    keys = jax.random.split(key, len(leaves))
    leaves = [jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
              for k, x in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tdef, leaves)


def _ref_attention(cfg, p, x, c, pos, window):
    """Today's decode: write the new K/V at ``pos``, then attend."""
    B = x.shape[0]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = pos[:, None] if jnp.ndim(pos) else pos + jnp.zeros((1, 1),
                                                                   int)
    q = L.rope(L.dense(x, p["wq"], p.get("bq")).reshape(B, 1, H, D),
               positions, cfg.rope_theta)
    k = L.rope(L.dense(x, p["wk"], p.get("bk")).reshape(B, 1, Hkv, D),
               positions, cfg.rope_theta).astype(c["k"].dtype)
    v = L.dense(x, p["wv"], p.get("bv")).reshape(B, 1, Hkv, D).astype(
        c["v"].dtype)
    if jnp.ndim(pos):
        upd = jax.vmap(functools.partial(
            jax.lax.dynamic_update_slice_in_dim, axis=0))
        kc, vc = upd(c["k"], k, pos), upd(c["v"], v, pos)
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(c["k"], k, pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(c["v"], v, pos, axis=1)
    out = decode_attention(q, kc, vc, pos + 1, window=window)
    return L.dense(out.reshape(B, 1, H * D), p["wo"]), {"k": kc, "v": vc}


def _ref_block(cfg, kind, p, x, c, pos):
    if kind not in ATTN:
        positions = pos[:, None] if jnp.ndim(pos) else pos + jnp.zeros(
            (1, 1), int)
        return T.block_forward(cfg, kind, p, x, positions=positions,
                               cache=c, cache_len=pos)
    window = {"attn_swa": cfg.window,
              "attn_local": cfg.local_window}.get(kind, 0)
    h, nc = _ref_attention(cfg, p["attn"], T._norm(cfg, p["norm1"], x), c,
                           pos, window)
    x = x + h
    return x + L.mlp_swiglu(p["mlp"], T._norm(cfg, p["norm2"], x)), nc


def ref_step(cfg, params, cache, tokens, pos):
    """Write-then-attend decode step, layer by layer in Python."""
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    layers = []
    for i in range(cfg.n_pattern_blocks):
        outs = []
        for slot, kind in enumerate(cfg.block_pattern):
            take = functools.partial(jax.tree.map, lambda a: a[i])
            x, nc = _ref_block(cfg, kind, take(params["blocks"][slot]), x,
                               take(cache["layers"][slot]), pos)
            outs.append(nc)
        layers.append(outs)
    new_layers = [jax.tree.map(lambda *zs: jnp.stack(zs),
                               *[outs[s] for outs in layers])
                  for s in range(len(cfg.block_pattern))]
    new_extra = []
    for i, kind in enumerate(cfg.extra_blocks):
        x, nc = _ref_block(cfg, kind, params["extra"][i], x,
                           cache["extra"][i], pos)
        new_extra.append(nc)
    x = T._norm(cfg, params["final_norm"], x)
    return (pool_ops._head_logits(cfg, params, x[:, 0]),
            {"layers": new_layers, "extra": new_extra})


def append_step(cfg, params, cache, tokens, pos, record=None):
    """The program's decode step (as ``_pool_decode`` runs it)."""
    positions = pos[:, None] if jnp.ndim(pos) else pos + jnp.arange(1)[None]
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x, new = T.run_stack(cfg, params, x, positions=positions,
                         caches={**cache, "len": pos}, record=record)
    x = T._norm(cfg, params["final_norm"], x)
    return pool_ops._head_logits(cfg, params, x[:, 0]), new


def _kinds_of_leaves(cfg, cache):
    """Each flat cache leaf's block kind (pool-leaf order)."""
    tagged = {"layers": [jax.tree.map(lambda _, k=k: k, c)
                         for k, c in zip(cfg.block_pattern,
                                         cache["layers"])],
              "extra": [jax.tree.map(lambda _, k=k: k, c)
                        for k, c in zip(cfg.extra_blocks, cache["extra"])]}
    return jax.tree_util.tree_leaves(tagged)


def _leaves(cache):
    return jax.tree_util.tree_leaves({"layers": cache["layers"],
                                      "extra": cache["extra"]})


# (arch, extra blocks, max_len, row positions); rows of the first
# attention layer come from the same inputs on both sides, so its cache
# leaves must match bit for bit where it is the only attention layer
CASES = {
    # max_len above one 128-position tile: both sides of a tile edge, the
    # last position, and a stale inactive row beyond max_len (clamped)
    "llama-256": ("llama3-8b", (), 256, [0, 127, 128, 255, 300]),
    # smoke pools are shorter than a tile: the tile is the whole row
    "llama-64": ("llama3-8b", (), 64, [0, 31, 63, 64, 90]),
    # an attention block among the unscanned extras
    "llama+extra-attn-256": ("llama3-8b", ("attn",), 256,
                             [0, 127, 128, 255, 300]),
    # recurrent blocks around a local-attention block, with extras; the
    # stale row stays within its 16-position window of the clamped write
    # (beyond it, today's decode masks every position of that row)
    "recurrentgemma-256": ("recurrentgemma-2b", None, 256,
                           [0, 127, 128, 255, 260]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_append_matches_write_then_attend(case):
    arch, extra, max_len, positions = CASES[case]
    cfg = _cfg(arch)
    if extra is not None:
        cfg = dataclasses.replace(cfg, extra_blocks=extra,
                                  n_layers=cfg.n_layers + len(extra))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B = len(positions)
    cache = _random_cache(cfg, B, max_len, jax.random.PRNGKey(1))
    pos = jnp.asarray(positions, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)

    record = {}
    got, new = jax.jit(functools.partial(append_step, cfg, record=record))(
        params, cache, tokens, pos)
    want, ref = jax.jit(functools.partial(ref_step, cfg))(
        params, cache, tokens, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    kinds = _kinds_of_leaves(cfg, cache)
    assert record["kv_append_leaves"] == sum(k in ATTN for k in kinds)
    exact = sum(k in ATTN for k in kinds) == 2 and arch == "llama3-8b"
    for kind, a, b in zip(kinds, _leaves(new), _leaves(ref)):
        if kind in ATTN and exact:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_append_leaves_untouched_beyond_the_new_rows():
    """Only each row's own (clamped) position changes, bit for bit: the
    rows written are exactly those the step's attention blocks emitted."""
    cfg = _cfg("recurrentgemma-2b", dtype="bfloat16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    positions = [0, 127, 128, 255, 300]
    B, max_len = len(positions), 256
    cache = _random_cache(cfg, B, max_len, jax.random.PRNGKey(3))
    pos = jnp.asarray(positions, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, 1), 0, cfg.vocab)
    _, new = jax.jit(functools.partial(append_step, cfg))(
        params, cache, tokens, pos)
    clamped = np.minimum(positions, max_len - 1)
    for kind, a, b in zip(_kinds_of_leaves(cfg, cache), _leaves(new),
                          _leaves(cache)):
        if kind not in ATTN:
            continue
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        keep = np.ones(a.shape[1:3], bool)              # [B, Smax]
        keep[np.arange(B), clamped] = False
        np.testing.assert_array_equal(a[:, keep], b[:, keep])
        assert not np.array_equal(a[:, ~keep], b[:, ~keep])


@pytest.mark.parametrize("pos", [0, 5, 63])
def test_lockstep_scalar_len_appends(pos):
    """Lock-step decode (scalar ``len``, every row at one fill) takes the
    same append path as the slot pool."""
    cfg = _cfg("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cache = _random_cache(cfg, 3, 64, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (3, 1), 0, cfg.vocab)
    got, new = jax.jit(functools.partial(M.decode_step, cfg))(
        params, {**cache, "len": jnp.int32(pos)}, tokens)
    want, ref = jax.jit(functools.partial(ref_step, cfg))(
        params, cache, tokens, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert int(new["len"]) == pos + 1
    for a, b in zip(_leaves(new), _leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_does_not_append():
    cfg = _cfg("llama3-8b", dtype="bfloat16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    record = {}
    x = L.embed(params["embed"], jnp.zeros((2, 8), jnp.int32))
    T.run_stack(cfg, params, x, positions=jnp.arange(8)[None],
                caches=M.init_cache(cfg, 2, 64), record=record)
    assert record == {"kv_append_leaves": 0}


def _serve(cfg, params, **kw):
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2, max_len=64,
                                      **kw)
    rng = np.random.RandomState(0)
    before = sch.stats["kv_append_leaves"]
    sch.serve([Request(prompt=rng.randint(0, cfg.vocab, 9).astype(np.int32),
                       max_new_tokens=4, arrival_time=0.0)
               for _ in range(3)])
    after = sch.stats["kv_append_leaves"]
    sch.close()
    return before, after


@pytest.mark.parametrize("arch,extra,want", [
    ("llama3-8b", (), 2),
    ("llama3-8b", ("attn",), 4),
    ("recurrentgemma-2b", None, 2),
], ids=["llama", "llama+extra-attn", "recurrentgemma"])
def test_kv_append_leaves_counts_attention_leaves(arch, extra, want):
    """``kv_append_leaves`` in ``sch.stats``: 0 until the decode step is
    traced, then the dense pool's attention cache leaves."""
    cfg = smoke_config(arch)
    if extra is not None:
        cfg = dataclasses.replace(cfg, extra_blocks=extra,
                                  n_layers=cfg.n_layers + len(extra))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert _serve(cfg, params) == (0, want)


def test_kv_append_leaves_zero_on_paged_pool():
    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert _serve(cfg, params, page_size=16) == (0, 0)
