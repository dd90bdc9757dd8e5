"""Compile the serving path's Pallas kernels for a TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets XLA and Mosaic compile for the chip, and refuse
what the chip would refuse (unaligned blocks, too much VMEM), which the
interpret-mode tests in test_kernels.py cannot see.  Shapes are
granite-3-2b's (32 query heads, 8 KV heads, head_dim 64, d_model 2048).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker
imports this file.  Nothing here runs; see chip_smoke.py for that.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm

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
