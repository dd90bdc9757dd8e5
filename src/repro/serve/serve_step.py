"""Serving steps: jit-compiled prefill and single-token decode.

``serve_step`` (decode) is one new token against a KV/recurrent cache of
seq_len, with the cache donated for in-place buffer reuse.  Sampling is
greedy or temperature-categorical; the batched engine drives continuous
decoding.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M


def build_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, tokens, *, cross_states=None,
                     frontend_embeds=None):
        logits, cache = M.prefill(cfg, params, tokens, max_len,
                                  cross_states=cross_states,
                                  frontend_embeds=frontend_embeds)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache
    return prefill_step


def build_decode_step(cfg: ModelConfig, temperature: float = 0.0):
    def decode_step(params, cache, tokens, rng=None, *, cross_states=None):
        logits, cache = M.decode_step(cfg, params, cache, tokens,
                                      cross_states=cross_states)
        if temperature > 0.0 and rng is not None:
            next_tok = jax.random.categorical(
                rng, logits.astype(jnp.float32) / temperature, axis=-1)
        else:
            next_tok = jnp.argmax(logits, axis=-1)
        return next_tok.astype(jnp.int32)[:, None], cache
    return decode_step


def jit_serve_steps(cfg: ModelConfig, max_len: int, temperature: float = 0.0,
                    donate_cache: bool = True):
    prefill = jax.jit(build_prefill_step(cfg, max_len))
    decode = jax.jit(build_decode_step(cfg, temperature),
                     donate_argnums=(1,) if donate_cache else ())
    return prefill, decode
