"""Host-clock times of the serving step programs, each jitted alone.

Times the slot-pool step programs of ``serve/scheduler/pool_ops.py`` at
granite-3-2b's full width and the serving shape of ``chip_smoke.py`` (8
slots of 2048 tokens), outside the scheduler and the co-execution engine,
so a step's time can be told apart from the time around it:

* dense decode (``serve.slot_decode``);
* paged decode (page 16) through the dense gather (``serve.slot_decode``)
  and through the Pallas paged-attention kernel
  (``kernel.slot_decode_paged``, what the ``kernels`` pass substitutes on
  TPU);
* prefill of one row at the 128 and 256 token buckets.

Each decode step runs with every row at VALID tokens, its pool state
donated as the scheduler's ``use_terra=False`` path donates it, and
``block_until_ready`` after every step.  A time is the host clock around
one step: it holds the dispatch as well as the device time, and it is not
a profiler reading.  Weights are random, from seed 0.

    python benchmarks/decode_step_times.py --out times.json   # on the chip
    python benchmarks/decode_step_times.py --smoke            # CPU check
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.configs.registry import smoke_config             # noqa: E402
from repro.core.ops import op_impl                          # noqa: E402
from repro.core.passes import kernel_sub  # noqa: E402,F401  registers kernel ops
from repro.launch import compile_cache                      # noqa: E402
from repro.models import model as M                         # noqa: E402
from repro.serve.scheduler import pool_ops                  # noqa: E402

_STATIC = ("_meta", "_n_params", "_n_cache", "_has_rng")
ARCH, SLOTS, MAX_LEN, PAGE_SIZE = "granite-3-2b", 8, 2048, 16
VALID, PREFILL_LENS, WARMUP, ITERS = (256, 1024), (128, 256), 3, 30


def _percentiles(ts):
    ms = np.asarray(ts) * 1e3
    return {"median_ms": float(np.median(ms)),
            "p10_ms": float(np.percentile(ms, 10)),
            "p90_ms": float(np.percentile(ms, 90)), "n": len(ts)}


class Pool:
    """One slot pool (dense or paged) with its registered step meta."""

    def __init__(self, cfg, params, slots: int, max_len: int,
                 page_size: int = 0):
        self.p_leaves, p_def = jax.tree_util.tree_flatten(params)
        nb = slots * max_len // page_size + 1 if page_size else 0
        leaves, c_def, axes, paged = pool_ops.build_pool_cache(
            cfg, slots, max_len, page_size, nb)
        self.c_leaves = leaves
        mid = pool_ops.register_pool_meta(cfg, p_def, c_def, axes, 0.0,
                                          max_len, page_size, nb, paged)
        self.attrs = dict(_meta=mid, _n_params=len(self.p_leaves),
                          _n_cache=len(leaves), _has_rng=False)
        self.slots = slots
        self.pos = jnp.zeros(slots, jnp.int32)
        self.tokf = jnp.zeros((slots, 1), jnp.int32)
        self.bt = None
        if page_size:
            # row r owns blocks 1 + r*nbps ... (block 0 is the trash block)
            nbps = max_len // page_size
            self.bt = jnp.asarray(1 + np.arange(slots * nbps, dtype=np.int32)
                                  .reshape(slots, nbps))
        donate = tuple(range(len(self.p_leaves),
                             len(self.p_leaves) + len(leaves) + 2))
        self._jits = {}
        for op in ("serve.slot_decode", "kernel.slot_decode_paged",
                   "serve.slot_prefill"):
            self._jits[op] = jax.jit(op_impl(op), static_argnames=_STATIC,
                                     donate_argnums=donate)

    def decode(self, op: str, valid: int):
        """One decode step with every row at ``valid`` tokens."""
        mask = jnp.ones(self.slots, bool)
        args = self.p_leaves + self.c_leaves + [
            jnp.full(self.slots, valid, jnp.int32), self.tokf, mask]
        if self.bt is not None:
            args.append(self.bt)
        outs = self._jits[op](*args, **self.attrs)
        self.c_leaves, self.tokf = list(outs[1:-2]), outs[-1]
        return outs

    def prefill(self, length: int):
        tokens = jnp.ones((1, length), jnp.int32)
        args = self.p_leaves + self.c_leaves + [
            self.pos, self.tokf, tokens, jnp.zeros(1, jnp.int32),
            jnp.full(1, length, jnp.int32)]
        if self.bt is not None:
            args.append(self.bt[:1])
        outs = self._jits["serve.slot_prefill"](*args, **self.attrs)
        self.c_leaves = list(outs[1:-2])
        self.pos, self.tokf = outs[-2], outs[-1]
        return outs


def time_steps(step, warmup: int, iters: int):
    for _ in range(warmup):
        jax.block_until_ready(step())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(step())
        ts.append(time.perf_counter() - t0)
    return _percentiles(ts)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smoke widths, 2 steps each: a CPU check of the "
                         "script, not a measurement")
    ap.add_argument("--out", default=None, help="write the results as JSON")
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = smoke_config(ARCH) if args.smoke else get_config(ARCH)
    warmup, iters = (1, 2) if args.smoke else (WARMUP, ITERS)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dense = Pool(cfg, params, SLOTS, MAX_LEN)
    paged = Pool(cfg, params, SLOTS, MAX_LEN, PAGE_SIZE)
    dev = jax.devices()[0]
    res = {"arch": cfg.name, "device_kind": dev.device_kind,
           "platform": dev.platform, "slots": SLOTS, "max_len": MAX_LEN,
           "page_size": PAGE_SIZE, "decode": [], "prefill": []}
    print(f"{cfg.name} on {dev.device_kind}: {SLOTS} slots x {MAX_LEN} "
          f"tokens, median of {iters} (p10-p90), host clock", flush=True)
    arms = (("dense decode", dense, "serve.slot_decode"),
            ("paged decode, gather", paged, "serve.slot_decode"),
            ("paged decode, kernel", paged, "kernel.slot_decode_paged"))
    for valid in VALID:
        for name, pool, op in arms:
            r = time_steps(lambda: pool.decode(op, valid), warmup, iters)
            res["decode"].append(dict(arm=name, op=op, valid=valid, **r))
            print(f"{name} ({op}) valid={valid}: {r['median_ms']:.3f} ms "
                  f"({r['p10_ms']:.3f}-{r['p90_ms']:.3f})", flush=True)
    for length in PREFILL_LENS:
        r = time_steps(lambda: dense.prefill(length), warmup, iters)
        res["prefill"].append(dict(rows=1, length=length, **r))
        print(f"prefill B=1 S={length}: {r['median_ms']:.3f} ms "
              f"({r['p10_ms']:.3f}-{r['p90_ms']:.3f})", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
