"""Train-step builder: loss, microbatched gradient accumulation, remat,
mixed precision, and the pjit shardings for a (data, model) mesh.

``build_train_step(cfg, opt_cfg, microbatches=k)`` returns a pure function
    step(params, opt_state, batch) -> (params, opt_state, metrics)
suitable for jax.jit with in/out shardings or for registration as a
single Terra composite op (train/trainer.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.parallel.sharding import logical
from repro.train import optimizer as opt


def lm_loss(cfg: ModelConfig, params, tokens, labels, *, extras=None,
            z_loss: float = 1e-4):
    """Next-token cross-entropy with z-loss, in f32.

    The label logit is extracted with a one-hot contraction rather than
    take_along_axis: a gather across the vocab-sharded axis forces XLA to
    all-gather the full logits (~17 GB/device/step on llama3-8b at 4k
    tokens), while the one-hot einsum stays local
    and reduces with a scalar psum."""
    kw = extras or {}
    logits = M.forward(cfg, params, tokens, **kw).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = (lse - ll).mean()
    zl = z_loss * jnp.square(lse).mean()
    return nll + zl, {"nll": nll}


def build_train_step(cfg: ModelConfig, opt_cfg: opt.OptConfig,
                     microbatches: int = 1, z_loss: float = 1e-4):
    def grads_of(params, tokens, labels, extras):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, tokens, labels, extras=extras,
                              z_loss=z_loss), has_aux=True)(params)
        return loss, grads

    def step(params, opt_state, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        extras = {k: v for k, v in batch.items()
                  if k not in ("tokens", "labels")}

        if microbatches == 1:
            loss, grads = grads_of(params, tokens, labels, extras)
        else:
            # gradient accumulation over the leading batch axis
            B = tokens.shape[0]
            mb = B // microbatches

            def re(x):
                return x.reshape((microbatches, mb) + x.shape[1:])

            mtok, mlab = re(tokens), re(labels)
            mext = {k: re(v) for k, v in extras.items()}

            def body(carry, xs):
                acc, lsum = carry
                t, l = xs[0], xs[1]
                e = {k: xs[2 + i] for i, k in enumerate(sorted(mext))}
                loss, g = grads_of(params, t, l, e)
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, lsum + loss), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            xs = (mtok, mlab) + tuple(mext[k] for k in sorted(mext))
            (gsum, lsum), _ = jax.lax.scan(body, (zero, 0.0), xs)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches

        new_params, new_state, om = opt.apply(opt_cfg, opt_state, grads,
                                              params)
        metrics = {"loss": loss, **om}
        return new_params, new_state, metrics

    return step


def eval_step(cfg: ModelConfig, params, batch, z_loss: float = 0.0):
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    loss, aux = lm_loss(cfg, params, batch["tokens"], batch["labels"],
                        extras=extras, z_loss=z_loss)
    return {"loss": loss, **aux}
