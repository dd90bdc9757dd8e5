"""Per-architecture smoke tests: reduced same-family configs, one forward
and one train step on CPU, asserting output shapes and no NaNs.  Full
widths run on the chip (``bench/``, ``chip_smoke.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import model as M

pytestmark = pytest.mark.slow       # multi-minute suite; see pytest.ini

ARCH_IDS = sorted(ARCHS.keys())


def _inputs(cfg, B=2, S=32):
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (B, S)).astype(np.int32)
    kw = {}
    if cfg.family == "vlm":
        kw["cross_states"] = jnp.asarray(
            rng.randn(B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        kw["frontend_embeds"] = jnp.asarray(
            rng.randn(B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return tokens, kw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens, kw = _inputs(cfg)
    logits = M.forward(cfg, params, tokens, **kw)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), \
        f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_no_nans(arch):
    cfg = smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens, kw = _inputs(cfg)
    labels = np.roll(tokens, -1, axis=1)

    def loss_fn(p):
        logits = M.forward(cfg, p, tokens, **kw).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)
        return -ll.mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss)), f"{arch}: NaN loss"
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)), f"{arch}: NaN grads"
    assert float(gnorm) > 0, f"{arch}: zero grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    """Prefill+decode must agree with the full forward pass on the next-token
    logits (KV-cache correctness)."""
    cfg = smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens, kw = _inputs(cfg, B=2, S=16)

    full = M.forward(cfg, params, tokens, **kw)
    # serve path: prefill on the first 15, then decode token 15
    pre_logits, cache = M.prefill(cfg, params, tokens[:, :15], max_len=32,
                                  **kw)
    dec_kw = {k: v for k, v in kw.items() if k != "frontend_embeds"}
    if cfg.family == "audio":
        dec_kw["cross_states"] = None  # recomputed below
        from repro.models import transformer as T
        dec_kw["cross_states"] = T.encode(cfg, params, kw["frontend_embeds"])
    dec_logits, cache = M.decode_step(cfg, params, cache,
                                      tokens[:, 15:16], **dec_kw)

    want = full[:, 15].astype(jnp.float32)
    got = dec_logits.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.15, atol=0.15)
    # ranking agreement on the argmax
    assert bool((jnp.argmax(got, -1) == jnp.argmax(want, -1)).mean() >= 0.5)
