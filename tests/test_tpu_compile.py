"""Compile the serving path's Pallas kernels and its decode step for a
TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets XLA and Mosaic compile for the chip, and refuse
what the chip would refuse (unaligned blocks, too much VMEM), which the
interpret-mode tests in test_kernels.py cannot see.  Shapes are
granite-3-2b's (32 query heads, 8 KV heads, head_dim 64, d_model 2048),
and deepseek-v2-lite's for its latent decode step.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker
imports this file.  Nothing here runs; see chip_smoke.py for that.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.passes import kernel_sub  # noqa: F401 - kernel.slot_decode
from repro.core.persist.aot import segment_specs
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import model as M
from repro.serve.engine import Request
from repro.serve.scheduler import ContinuousBatchingScheduler, pool_ops

Hq, Hkv, D, DM = 32, 8, 64, 2048        # granite-3-2b
ROWS, TOKENS = 8, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("block_size", [16, 64])
def test_paged_attention_compiles(one_chip, block_size):
    nbps = TOKENS // block_size
    arena = _spec(one_chip, ROWS * nbps + 1, block_size, Hkv, D)
    text = _compiled_text(
        lambda q, kp, vp, bt, valid: paged_attention(
            q, kp, vp, bt, valid, interpret=False),
        _spec(one_chip, ROWS, 1, Hq, D), arena, arena,
        _spec(one_chip, ROWS, nbps, dtype=jnp.int32),
        _spec(one_chip, ROWS, dtype=jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    kv = _spec(one_chip, 1, Hkv, TOKENS, D)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        _spec(one_chip, 1, Hq, TOKENS, D), kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(DM, DM), (ROWS, 1, DM), (12, DM)],
                         ids=["2048x2048", "8x1x2048", "12x2048"])
def test_rmsnorm_compiles(one_chip, shape):
    text = _compiled_text(
        lambda x, g: rmsnorm(x, g, interpret=False),
        _spec(one_chip, *shape), _spec(one_chip, DM))
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------
# the dense slot-pool decode step: appends, never re-lays out its cache
# --------------------------------------------------------------------------

SLOTS, POOL_LEN, LAYERS = 16, 2048, 2
MIB = 2 ** 20


def _slot_decode(sharding, donate: bool):
    """Compile ``serve.slot_decode`` over a dense 16 x 2048 pool at
    granite-3-2b widths (``LAYERS`` layers); returns (compiled, cache
    leaf shapes)."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=LAYERS)
    compiled, shapes, mid = _compile_decode(sharding, cfg, SLOTS, donate)
    assert pool_ops.kv_append_leaves(mid) == len(shapes) == 2
    return compiled, shapes


def _compile_decode(sharding, cfg, slots: int, donate: bool,
                    op: str = "serve.slot_decode"):
    """Compile ``op`` (a slot-pool decode) over a dense ``slots`` x 2048
    pool; returns (compiled, cache leaf shapes, pool meta id)."""

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    p_leaves, p_def = jax.tree_util.tree_flatten(M.abstract_params(cfg))
    c_leaves = jax.eval_shape(
        lambda: pool_ops.build_pool_cache(cfg, slots, POOL_LEN)[0])
    _, c_def, axes, paged = pool_ops.build_pool_cache(cfg, 1, 8)
    mid = pool_ops.register_pool_meta(cfg, p_def, c_def, axes, 0.0,
                                      POOL_LEN, 0, 0, paged)
    attrs = dict(_meta=mid, _n_params=len(p_leaves),
                 _n_cache=len(c_leaves), _has_rng=False)
    state = [spec(x) for x in p_leaves + c_leaves] + [
        _spec(sharding, slots, dtype=jnp.int32),
        _spec(sharding, slots, 1, dtype=jnp.int32)]
    donated = (tuple(range(len(p_leaves), len(state))) if donate else ())
    step = jax.jit(pool_ops.program(op),
                   static_argnames=tuple(attrs), donate_argnums=donated)
    compiled = step.lower(*state, _spec(sharding, slots, dtype=jnp.bool_),
                          **attrs).compile()
    return compiled, [tuple(x.shape) for x in c_leaves], mid


def _copied_shapes(text: str):
    """Result shape of every copy op in compiled HLO text."""
    return [tuple(int(d) for d in m.group(1).split(","))
            for m in re.finditer(
                r"= \w+\[([\d,]+)\]\S* copy(?:-start)?\(", text)]


@pytest.mark.parametrize("donate", [False, True],
                         ids=["co-execution", "donated"])
def test_dense_slot_decode_appends_without_relayout(one_chip, donate):
    compiled, leaf_shapes = _slot_decode(one_chip, donate)
    copies = _copied_shapes(compiled.as_text())
    layer = leaf_shapes[0][1:]
    # no layer's K/V slice is copied (re-laid out) inside the layer loop
    assert not [c for c in copies if c in (layer, (1,) + layer)], copies
    whole = [c for c in copies if c in leaf_shapes]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if donate:
        assert not whole and temp < 4 * MIB, (whole, temp / MIB)
    else:
        # the step may not write its input: one input-to-output copy each
        assert len(whole) <= len(leaf_shapes), whole


def test_steady_segment_writes_the_pool_in_place(one_chip):
    """The co-executed decode segment's steady form, which donates every
    pool leaf (DESIGN.md §12), moves no whole cache leaf on the chip; the
    walker's form of the same segment copies its input leaves.  The
    segment is built by a scheduler serving on the CPU (granite widths,
    ``LAYERS`` layers) and compiled here for the described chip."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=LAYERS)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=SLOTS,
                                      max_len=POOL_LEN, prefill_batch_cap=1)
    sch.serve([Request(prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=12, arrival_time=0.0)])
    eng = sch._tf.engine
    gp, plan = eng.gp, eng.family.steady
    leaf = gp.var_avals[sch._cache_vars[0].var_id].shape
    sch.close()

    def compiled(sp):
        specs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            segment_specs(gp, sp))
        return gp._compile_segment(sp, jit_each=True).lower(*specs).compile()
    steady = compiled(plan.sp)
    assert leaf not in _copied_shapes(steady.as_text())
    assert steady.memory_analysis().temp_size_in_bytes < 4 * MIB
    assert leaf in _copied_shapes(compiled(gp.seg_progs[0]).as_text())


# --------------------------------------------------------------------------
# DeepSeek-V2's absorbed latent decode: appends its latent rows in place
# --------------------------------------------------------------------------

MLA_SLOTS = 32


@pytest.mark.parametrize("donate", [False, True],
                         ids=["co-execution", "donated"])
def test_latent_decode_appends_without_relayout(one_chip, donate):
    """deepseek-v2-lite at full width (the leading dense layer, two expert
    layers holding 8 of 64 experts), a dense 32 x 2048 pool: the step
    appends one latent row per slot to the leading leaf and the stacked
    leaf, and copies no layer's slice of the latent cache."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=3,
                              experts_held=8)
    compiled, shapes, mid = _compile_decode(one_chip, cfg, MLA_SLOTS,
                                            donate)
    assert pool_ops.kv_append_leaves(mid) == 2
    assert pool_ops.pool_meta(mid).trace["experts_held"] == 8
    stacked = (2, MLA_SLOTS, POOL_LEN, 576)
    assert stacked in shapes and stacked[1:] in shapes
    copies = _copied_shapes(compiled.as_text())
    # a stacked layer's slice (1, ...) is never copied; (32, 2048, 576) is
    # both a layer's slice and the leading block's whole leaf
    assert (1,) + stacked[1:] not in copies, copies
    whole = [c for c in copies if c in shapes]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if donate:
        assert not whole and temp < 4 * MIB, (whole, temp / MIB)
    else:
        # the step may not write its input: one input-to-output copy each
        assert len(whole) <= len(shapes), whole
        assert temp < 8 * MIB, temp / MIB


# --------------------------------------------------------------------------
# the kernels pass's decode: appended rows through the kv_append kernel
# --------------------------------------------------------------------------

KERNEL_DECODES = {
    # (config, slots, leaves the kernel writes)
    "granite": (dataclasses.replace(get_config("granite-3-2b"),
                                    n_layers=LAYERS), SLOTS, 2),
    "dsv2": (dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=3,
                                 experts_held=8), MLA_SLOTS, 2),
    # head_dim 128: the leaf is stored position-major, so the loop stays
    "head_dim-128": (dataclasses.replace(get_config("granite-3-2b"),
                                         n_layers=LAYERS, head_dim=128),
                     SLOTS, 0),
}


@pytest.mark.parametrize("case", sorted(KERNEL_DECODES))
def test_kernel_slot_decode_appends_in_place(one_chip, monkeypatch, case):
    """``kernel.slot_decode``, donated, as the ``kernels`` pass leaves the
    co-executed decode on a TPU: granite's K/V leaves and DeepSeek-V2's two
    latent leaves are written by the ``kv_append`` Pallas call, in their
    stored layout; no copy as large as a layer's slice of a leaf appears,
    and the temp stays small.  A head_dim-128 leaf keeps the loop and
    copies no whole leaf (its layer slices are re-laid out for attention
    inside the scan, with or without the kernel)."""
    cfg, slots, want = KERNEL_DECODES[case]
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)
    compiled, shapes, mid = _compile_decode(one_chip, cfg, slots, True,
                                            op="kernel.slot_decode")
    text = compiled.as_text()
    assert pool_ops.kv_append_leaves(mid) == 2
    assert pool_ops.pool_meta(mid).trace["kv_append_kernel_leaves"] == want
    calls = re.findall(r"%kv_append\S* = \S+ custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == want, calls
    copies = _copied_shapes(text)
    assert not [c for c in copies if c in shapes], copies
    if want:
        slice_size = min(np.prod(s[1:]) for s in shapes if len(s) >= 4)
        big = [c for c in copies if np.prod(c) >= slice_size]
        assert not big, big
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * MIB, temp / MIB
