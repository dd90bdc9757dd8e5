"""Substrate tests: optimizer, checkpoint, data pipeline, sharding specs,
pipeline parallelism, gradient compression."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis "
                           "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ckpt
from repro.train import data as data_mod
from repro.train import optimizer as opt


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    oc = opt.OptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                       weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0, 1.0])}
    state = opt.init(params)
    for _ in range(50):
        grads = {"w": 2 * params["w"]}        # d/dw ||w||^2
        params, state, m = opt.apply(oc, state, grads, params)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_adamw_bf16_params_keep_f32_master():
    oc = opt.OptConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = opt.init(params)
    assert state["master"]["w"].dtype == jnp.float32
    params2, state2, _ = opt.apply(oc, state, {"w": jnp.ones(4, jnp.bfloat16)},
                                   params)
    assert params2["w"].dtype == jnp.bfloat16
    assert state2["master"]["w"].dtype == jnp.float32


def test_grad_clipping_bounds_update():
    oc = opt.OptConfig(lr=1.0, warmup_steps=0, total_steps=10,
                       clip_norm=1e-3, weight_decay=0.0)
    params = {"w": jnp.zeros((2,))}
    state = opt.init(params)
    _, _, m = opt.apply(oc, state, {"w": jnp.full((2,), 1e6)}, params)
    assert float(m["grad_norm"]) > 1e5       # raw norm reported


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_schedule_monotone_warmup_and_bounded(step):
    oc = opt.OptConfig(lr=3e-4, warmup_steps=100, total_steps=1000)
    lr = float(opt.schedule(oc, jnp.asarray(step, jnp.float32)))
    assert 0.0 <= lr <= oc.lr + 1e-9


# --------------------------------------------------------------------------
# checkpoint
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_bf16():
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.ones((3,), jnp.float32),
                  "d": jnp.zeros((), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 7, tree)
        assert ckpt.latest_step(d) == 7
        out = ckpt.restore(d, 7, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_crash_safety_keeps_previous():
    tree = {"a": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        ckpt.save(d, 2, jax.tree.map(lambda x: x * 2, tree))
        assert ckpt.latest_step(d) == 2
        # step_1 still restorable (atomic commits never corrupt old state)
        out = ckpt.restore(d, 1, tree)
        np.testing.assert_array_equal(np.asarray(out["a"]), [1.0, 1.0])


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    ds = data_mod.SyntheticLMDataset(vocab=100, seq_len=8, batch=2, seed=3)
    a = ds.batch_at(5)
    b = ds.batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    it = data_mod.PrefetchIterator(ds, start_step=0)
    first = next(it)
    it.seek(5)
    resumed = next(it)
    np.testing.assert_array_equal(resumed["tokens"], a["tokens"])
    it.close()


def test_data_shards_differ():
    d0 = data_mod.SyntheticLMDataset(100, 8, 2, seed=3, shard=0, n_shards=2)
    d1 = data_mod.SyntheticLMDataset(100, 8, 2, seed=3, shard=1, n_shards=2)
    assert not np.array_equal(d0.batch_at(0)["tokens"],
                              d1.batch_at(0)["tokens"])


# --------------------------------------------------------------------------
# sharding specs
# --------------------------------------------------------------------------

def test_param_specs_divisible_everywhere():
    from repro.configs import smoke_config, get_config
    from repro.models import model as M
    from repro.parallel import specs as S

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("llama3-8b")
    aparams = M.abstract_params(cfg)
    spec_tree = S.tree_param_specs(mesh, aparams)
    # every spec must be applicable (no divisibility violations)
    for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(aparams)[0],
            jax.tree.leaves(spec_tree,
                            is_leaf=lambda x: hasattr(x, "_normalized_spec")
                            or x.__class__.__name__ == "PartitionSpec")):
        assert len(spec) <= len(leaf.shape)


# --------------------------------------------------------------------------
# pipeline parallelism (on a host-device mesh)
# --------------------------------------------------------------------------

def test_gpipe_pipeline_matches_sequential():
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices (CI: multidevice job forces 2)")
    from repro.parallel.pipeline import make_pipelined_apply

    n_stages = jax.device_count()
    mesh = make_mesh((n_stages,), ("stage",))
    mb, d = 4, 16
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(n_stages, d, d).astype(np.float32) * 0.2)
    xs = jnp.asarray(rng.randn(2 * n_stages, mb, d).astype(np.float32))

    pipe = make_pipelined_apply(mesh, "stage",
                                lambda p, x: jnp.tanh(x @ p["w"]))
    with mesh:
        got = pipe({"w": ws}, xs)

    ref = xs
    for s in range(n_stages):
        ref = jnp.tanh(ref @ ws[s])
    assert float(jnp.abs(got - ref).max()) < 1e-5


# --------------------------------------------------------------------------
# gradient compression
# --------------------------------------------------------------------------

def test_int8_codec_roundtrip_error_small():
    from repro.parallel import compression as C
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(1000).astype(np.float32))
    packed = C.compress_int8(g)
    back = C.decompress_int8(packed)
    err = float(jnp.abs(back - g).max() / jnp.abs(g).max())
    assert err < 0.02


def test_bf16_error_feedback_unbiased():
    """With error feedback, repeated compression accumulates no bias: the
    sum of compressed updates converges to the sum of true gradients."""
    from repro.parallel import compression as C
    rng = np.random.RandomState(1)
    g_true = jnp.asarray(rng.randn(64).astype(np.float32)) * 1e-3
    r = jnp.zeros_like(g_true)
    sent = jnp.zeros_like(g_true)
    for _ in range(200):
        g = g_true + r
        c = C.compress_bf16(g)
        r = g - C.decompress_bf16(c)
        sent = sent + C.decompress_bf16(c)
    np.testing.assert_allclose(np.asarray(sent),
                               np.asarray(g_true) * 200, rtol=1e-3,
                               atol=1e-5)


def test_wire_bytes_accounting():
    from repro.parallel import compression as C
    grads = {"w": jnp.zeros((1000,)), "b": jnp.zeros((24,))}
    un, comp = C.wire_bytes_saved(grads, "bf16")
    assert un == 4096 and comp == 2048
