"""The imperative training driver, executed through Terra co-execution.

This is the paper's technique integrated as a first-class framework
feature: the user-visible training loop is ordinary imperative Python
(logging, checkpointing, adaptive hyper-parameters, third-party calls all
work), while the heavy ``train_step`` — a single composite Terra op wrapping
the pjit-ready step function — runs on the GraphRunner asynchronously.
Python-side overhead (data staging, bookkeeping, checkpoint scheduling) is
hidden behind device execution exactly as in the paper's Fig. 6.

Fault tolerance:
  * periodic checkpoints (async commit, atomic rename) + auto-resume,
  * a step watchdog flags stragglers (slow steps) and records them — the
    mitigation hook for a real cluster scheduler,
  * the data pipeline reseeks deterministically on restart.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import Variable, function as terra_function, ops as terra_ops
from repro.core.ops import def_op
from repro.models import model as M
from repro.parallel import specs as S
from repro.parallel.sharding import ShardingPolicy, use_policy
from repro.train import checkpoint as ckpt
from repro.train import data as data_mod
from repro.train import optimizer as opt
from repro.train.train_step import build_train_step


def init_state(cfg: ModelConfig, key, mesh=None):
    """(params, opt_state) for a fresh run.  With a mesh both are created
    inside one jit whose ``out_shardings`` follow parallel/specs.py, so
    every leaf is born sharded: nothing is built whole on one device."""
    def build(k):
        params = M.init_params(cfg, k)
        return params, opt.init(params)

    if mesh is None:
        return build(key)
    pspecs = S.tree_param_specs(mesh, M.abstract_params(cfg))
    ospecs = S.opt_state_specs(mesh, None, pspecs)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             (pspecs, ospecs),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)(key)


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: Optional[opt.OptConfig] = None,
                 *, ckpt_dir: Optional[str] = None, seed: int = 0,
                 batch: int = 8, seq_len: int = 128, microbatches: int = 1,
                 mesh=None, log_every: int = 10, ckpt_every: int = 100,
                 straggler_factor: float = 3.0, use_terra: bool = True):
        self.cfg = cfg
        self.opt_cfg = opt_cfg or opt.OptConfig()
        self.ckpt_dir = ckpt_dir
        self.batch, self.seq_len = batch, seq_len
        self.log_every, self.ckpt_every = log_every, ckpt_every
        self.straggler_factor = straggler_factor
        self.mesh = mesh
        self.policy = ShardingPolicy(mesh)
        self.use_terra = use_terra
        self.history: list = []
        self.straggler_events: list = []

        params, opt_state = init_state(cfg, jax.random.PRNGKey(seed), mesh)
        self.start_step = 0
        if ckpt_dir is not None:
            last = ckpt.latest_step(ckpt_dir)
            if last is not None:
                # auto-resume: params+opt are stored together as one tree,
                # and each leaf is put back on its fresh twin's sharding
                tree = {"params": params, "opt": opt_state}
                place = (None if mesh is None else
                         jax.tree.map(lambda x: x.sharding, tree))
                tree = ckpt.restore(ckpt_dir, last, tree, shardings=place)
                params, opt_state = tree["params"], tree["opt"]
                self.start_step = last

        # flatten state into Terra Variables (graph-resident); they are
        # its only holders, so a step's old state can be freed
        p_leaves, self._p_def = jax.tree_util.tree_flatten(params)
        o_leaves, self._o_def = jax.tree_util.tree_flatten(opt_state)
        self.p_vars = [Variable(x, f"p{i}") for i, x in enumerate(p_leaves)]
        self.o_vars = [Variable(x, f"o{i}") for i, x in enumerate(o_leaves)]

        step_fn = build_train_step(cfg, self.opt_cfg,
                                   microbatches=microbatches)
        n_p, n_o = len(p_leaves), len(o_leaves)
        p_def, o_def = self._p_def, self._o_def

        def flat_step(*args):
            p = jax.tree_util.tree_unflatten(p_def, args[:n_p])
            o = jax.tree_util.tree_unflatten(o_def, args[n_p:n_p + n_o])
            tokens, labels = args[n_p + n_o], args[n_p + n_o + 1]
            new_p, new_o, metrics = step_fn(p, o, {"tokens": tokens,
                                                   "labels": labels})
            return (tuple(jax.tree.leaves(new_p))
                    + tuple(jax.tree.leaves(new_o))
                    + (metrics["loss"], metrics["grad_norm"]))

        self._flat_step_op = def_op(f"train_step::{cfg.name}", flat_step)
        self.dataset = data_mod.SyntheticLMDataset(
            cfg.vocab, seq_len, batch, seed=seed)

        def train_iteration(tokens, labels):
            args = ([v.read() for v in self.p_vars]
                    + [v.read() for v in self.o_vars]
                    + [tokens, labels])
            outs = self._flat_step_op(*args)
            for v, o in zip(self.p_vars, outs[:n_p]):
                v.assign(o)
            for v, o in zip(self.o_vars, outs[n_p:n_p + n_o]):
                v.assign(o)
            return outs[-2], outs[-1]          # loss, grad_norm

        if use_terra:
            self._iteration = terra_function(train_iteration, seed=seed)
        else:
            self._iteration = train_iteration     # plain eager-via-jit path

    # ------------------------------------------------------------------
    def state_tree(self):
        params = jax.tree_util.tree_unflatten(
            self._p_def, [v.value() for v in self.p_vars])
        ostate = jax.tree_util.tree_unflatten(
            self._o_def, [v.value() for v in self.o_vars])
        return {"params": params, "opt": ostate}

    # ------------------------------------------------------------------
    def train(self, num_steps: int, verbose: bool = True):
        it = data_mod.PrefetchIterator(self.dataset,
                                       start_step=self.start_step)
        step_times: list = []
        ctx = use_policy(self.policy)
        ctx.__enter__()
        mesh_ctx = self.mesh if self.mesh is not None else None
        if mesh_ctx is not None:
            mesh_ctx.__enter__()
        try:
            for step in range(self.start_step, self.start_step + num_steps):
                batch = next(it)
                t0 = time.perf_counter()
                loss_t, gnorm_t = self._iteration(batch["tokens"],
                                                  batch["labels"])
                dt = time.perf_counter() - t0
                step_times.append(dt)
                # straggler watchdog (mitigation hook)
                med = float(np.median(step_times[-50:]))
                if len(step_times) > 10 and dt > self.straggler_factor * med:
                    self.straggler_events.append((step, dt, med))
                if (step + 1) % self.log_every == 0:
                    loss = float(loss_t)           # Output Fetching
                    self.history.append((step + 1, loss))
                    if verbose:
                        phase = (self._iteration.phase
                                 if self.use_terra else "eager")
                        print(f"step {step + 1:5d} loss {loss:.4f} "
                              f"[{phase}] {dt * 1e3:.1f}ms")
                if (self.ckpt_dir is not None
                        and (step + 1) % self.ckpt_every == 0):
                    ckpt.save(self.ckpt_dir, step + 1, self.state_tree(),
                              blocking=False)
        finally:
            if mesh_ctx is not None:
                mesh_ctx.__exit__(None, None, None)
            ctx.__exit__(None, None, None)
            it.close()
        if self.ckpt_dir is not None:
            ckpt.save(self.ckpt_dir, self.start_step + num_steps,
                      self.state_tree(), blocking=True)
        return self.history
