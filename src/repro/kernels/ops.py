"""Jit'd wrappers for the Pallas kernels with automatic CPU fallback.

On a TPU backend the kernels compile natively; everywhere else (this
container) ``interpret=True`` executes the kernel body faithfully for
correctness validation, or callers can use the pure-jnp reference path.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import kv_append as _kv
from repro.kernels import paged_attention as _pa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ss
from repro.kernels import ref as _ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "use_ref"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512,
                    use_ref: bool = False):
    if use_ref:
        return _ref.ref_attention(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block,
                               interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("window", "use_ref"))
def paged_attention(q, kp, vp, bt, valid, *, window: int = 0,
                    use_ref: bool = False):
    if use_ref:
        return _ref.ref_paged_attention(q, kp, vp, bt, valid, window=window)
    return _pa.paged_attention(q, kp, vp, bt, valid, window=window,
                               interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("chunk", "use_ref"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, use_ref: bool = False):
    if use_ref:
        return _ref.ref_ssd(x, dt, A, Bm, Cm)
    return _ss.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                        interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("eps", "row_block", "use_ref"))
def rmsnorm(x, g, *, eps: float = 1e-6, row_block: int = 256,
            use_ref: bool = False):
    if use_ref:
        return _ref.ref_rmsnorm(x, g, eps)
    return _rn.rmsnorm(x, g, eps=eps, row_block=row_block,
                       interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("lead",))
def kv_append(cache, rows, pos, *, lead: int):
    return _kv.kv_append(cache, rows, pos, lead=lead,
                         interpret=_interpret_default())
