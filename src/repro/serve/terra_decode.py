"""Serving decode under Terra co-execution.

The serving engine's decode loop is an imperative Python program —
per-request bookkeeping, EOS early-exits, detokenizers — which is exactly
the workload class Terra targets (paper §2: serving is the other
first-class imperative program).  This module routes it through the Terra
runtime instead of a hand-jitted step:

* the whole jitted decode step becomes a **single DL op** (the paper's
  framework-granularity segment model, DESIGN.md §2: "TF ops = graph
  nodes" — op granularity is whatever the op registry says it is),
* model parameters and the KV/recurrent cache live as framework
  :class:`Variable`\\ s, so their buffers stay device-resident in the
  engine's VariableStore and thread segment-to-segment without bouncing
  through Python,
* only the sampled token crosses back per step (an Output Fetching point),
  leaving Python free for retirement bookkeeping while the GraphRunner
  queues the next step.

Pytrees are flattened at the boundary: ``_META`` keeps the (static)
treedefs out of band so the op's attributes stay hashable.
"""

from __future__ import annotations

import os
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import function as terra_function
from repro.core import ops as ops_mod
from repro.core.ops import def_op
from repro.core.tensor import Variable
from repro.serve.meta import MetaRegistry
from repro.serve.serve_step import build_decode_step

# meta id -> (params_treedef, cache_treedef, decode_fn)
_META = MetaRegistry()


def _register_meta(params_def, cache_def, decode_fn) -> int:
    return _META.register((params_def, cache_def, decode_fn))


def _decode_impl(*leaves, _meta: int, _n_params: int, _n_cache: int,
                 _has_rng: bool, _has_cross: bool):
    params_def, cache_def, decode_fn = _META.get(_meta)
    params = jax.tree_util.tree_unflatten(params_def, leaves[:_n_params])
    cache = jax.tree_util.tree_unflatten(
        cache_def, leaves[_n_params:_n_params + _n_cache])
    rest = list(leaves[_n_params + _n_cache:])
    tokens = rest.pop(0)
    rng = rest.pop(0) if _has_rng else None
    cross = rest.pop(0) if _has_cross else None
    tok, new_cache = decode_fn(params, cache, tokens, rng=rng,
                               cross_states=cross)
    return (tok,) + tuple(jax.tree_util.tree_leaves(new_cache))


_decode_op = def_op("serve.decode_step", _decode_impl)


class TerraDecoder:
    """Drives lock-step decode through a ``terra.function``.

    One call of the wrapped step function is one Terra iteration: the first
    two steps of the first batch trace, every later step co-executes.  The
    KV cache is rebound (``reset_variable``) from the prefill output at
    each batch start and the *same* cache variables are recycled across
    batches even when the batch size or sequence bucket changes: a new
    shape rebinds the variables to new avals, which selects (or traces) the
    matching shape-class TraceGraph family (DESIGN.md §8).  Each observed
    shape traces and compiles exactly once; alternating batch shapes after
    that flip between sibling graphs with zero retraces and zero
    recompiles.  Fresh variables are only minted when the cache *structure*
    (treedef / leaf count) changes — a different model, not a different
    batch.
    """

    def __init__(self, cfg, params, temperature: float = 0.0,
                 optimize: Optional[str] = None):
        if optimize is None:
            # serving's default is the SAFE pipeline, but the
            # $TERRA_OPTIMIZE kill-switch (e.g. "none") must stay able to
            # disable passes here too
            optimize = os.environ.get("TERRA_OPTIMIZE") or "safe"
        self.cfg = cfg
        self.temperature = temperature
        self._decode_fn = build_decode_step(cfg, temperature)
        leaves, self._params_def = jax.tree_util.tree_flatten(params)
        self._param_vars: List[Variable] = [
            Variable(l, name=f"srv.p{i}") for i, l in enumerate(leaves)]
        self._cache_vars: Optional[List[Variable]] = None
        self._cache_def = None
        self._meta: Optional[int] = None
        # serving pins the SAFE pipeline explicitly (DESIGN.md §10): the
        # decode step's token feed changes every call, so constant-feed
        # folding must never bake one batch's tokens into the graph —
        # "safe" excludes the fold pass while keeping DCE/CSE/coalescing
        # (and, on TPU, kernel substitution)
        self._tf = terra_function(self._step, optimize=optimize)

    # ------------------------------------------------------------------
    @property
    def phase(self) -> str:
        return self._tf.phase

    @property
    def stats(self):
        return self._tf.stats

    # ------------------------------------------------------------------
    def begin_batch(self, cache) -> None:
        """Bind the prefilled cache into the engine's variable store.

        Shape changes (batch size, sequence bucket) REUSE the existing
        cache variables: ``reset_variable`` rebinds them to the new avals
        and the engine's shape-class signature flips to the matching
        TraceGraph family — no divergence, no retrace of known shapes.
        Only a cache-structure change (different treedef) mints fresh
        variables, retiring the old set so its buffers don't stay pinned
        in the device-resident store forever."""
        leaves, cache_def = jax.tree_util.tree_flatten(cache)
        leaves = [jnp.asarray(l) for l in leaves]
        reuse = (self._cache_vars is not None
                 and cache_def == self._cache_def
                 and len(leaves) == len(self._cache_vars))
        eng = self._tf.engine
        if reuse:
            for var, leaf in zip(self._cache_vars, leaves):
                eng.reset_variable(var, leaf)
        else:
            if self._cache_vars is not None:
                for var in self._cache_vars:
                    eng.release_variable(var)
            # _META entries stay: retired decode nodes survive in their
            # TraceGraph families as dead branches and still trace through
            # their meta id (the entries are treedefs — tiny)
            self._cache_vars = [Variable(l, name=f"srv.c{i}")
                                for i, l in enumerate(leaves)]
            self._cache_def = cache_def
            self._meta = _register_meta(self._params_def, cache_def,
                                        self._decode_fn)

    # ------------------------------------------------------------------
    def step(self, tokens, cross_states=None):
        """One decode step; returns a (possibly placeholder) token tensor."""
        return self._tf(jnp.asarray(tokens), cross_states)

    def _step(self, tokens, cross_states):
        args = [v.read() for v in self._param_vars]
        args += [v.read() for v in self._cache_vars]
        args.append(tokens)
        has_rng = self.temperature > 0.0
        if has_rng:
            args.append(ops_mod._next_key())    # iteration-stable key feed
        has_cross = cross_states is not None
        if has_cross:
            args.append(cross_states)
        outs = _decode_op(*args, _meta=self._meta,
                          _n_params=len(self._param_vars),
                          _n_cache=len(self._cache_vars),
                          _has_rng=has_rng, _has_cross=has_cross)
        tok, cache_leaves = outs[0], outs[1:]
        for var, leaf in zip(self._cache_vars, cache_leaves):
            var.assign(leaf)
        return tok

    # ------------------------------------------------------------------
    def wait(self):
        self._tf.wait()

    def close(self):
        self._tf.close()
