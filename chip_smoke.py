#!/usr/bin/env python3
"""Chip smoke test: Terra's serving path at the full width of granite-3-2b.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: sharded training only

One chip runs four phases, in order, each printing one summary line:

* ``device``  -- JAX must see a TPU.  There is no CPU path: anything else
  exits non-zero naming what was found.
* ``kernels`` -- the Pallas kernels of the serving path (paged attention,
  flash attention, RMSNorm), compiled and never interpreted, against
  their ``kernels/ref.py`` oracles at granite-3-2b widths.
* ``serve(dense)`` -- the continuous-batching scheduler under Terra
  co-execution at full width (40 layers, d_model 2048, vocab 49155) over
  a Poisson stream of requests, then the same requests with
  ``use_terra=False``: greedy tokens must be identical.
* ``serve(paged)`` -- the same run on the paged KV arena, whose decode
  step must reach the paged-attention kernel.

``--four-chips`` runs only the sharded training path and what it is
compared with: granite-3-2b ``Trainer`` steps on a 2x2 (data, model)
mesh with and without Terra.

Weights are random, made from ``--seed``.  Any failing check exits
non-zero.  A passing run ends with one JSON line:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
JAX's compilation cache goes where ``repro.launch.compile_cache`` says,
so a second run compiles little.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.launch import compile_cache                      # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.models import model as M                         # noqa: E402
from repro.serve.engine import Request                      # noqa: E402
from repro.serve.scheduler import ContinuousBatchingScheduler  # noqa: E402
from repro.train.optimizer import OptConfig                 # noqa: E402
from repro.train.trainer import Trainer                     # noqa: E402

ARCH = "granite-3-2b"

# Serving: 8 slots of 2048 tokens.  Prompts of 128 or 256 tokens (one
# prefill bucket each) and one row per admission keep the prefill
# compiles at one per bucket and make a request's prefill shape
# independent of arrival timing, which the terra/noterra token equality
# relies on (a row's bf16 rounding may depend on how many rows share it).
SERVE = dict(max_slots=8, max_len=2048, prefill_batch_cap=1)
N_REQUESTS, PROMPT_LENS, MAX_NEW, MEAN_GAP_S = 16, (128, 256), 32, 0.05
PAGE_SIZE = 16

# Kernel oracles run in f32 at "highest" matmul precision on the same
# bf16 inputs.  The kernels round their output to bf16 (2**-9 relative)
# and may feed bf16 softmax weights to the MXU (another 2**-9), so
# |kernel - oracle| <= KERNEL_ATOL + KERNEL_RTOL * |oracle| leaves a wide
# margin for a correct kernel and none for a wrong mask or block index.
KERNEL_ATOL = KERNEL_RTOL = 2e-2

# Training (--four-chips): full width, depth cut to TRAIN_LAYERS.  A step
# holds two copies of params + AdamW state, the state it reads and the
# state it writes (plain and terra alike; under co-execution the state it
# reads is the rollback snapshot).  At 40 layers that is 2 x 8.59 GiB per
# device on a 2x2 mesh, beyond 16 GiB; at 20 layers it is 2 x 4.6 GiB.
# The eager steps (all of plain's, terra's traced ones) peak higher: 0.84
# of the state per device at 20 layers on four v5e chips.
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 20, 3, 8, 128
LOSS_RTOL = 1e-2            # terra vs plain losses, relative
STATE_SHARE_MAX = 0.40      # bytes_in_use per device / total state


def check(ok: bool, phase: str, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED in {phase}: {what}")


class CompileCounter:
    """Counts compile requests and persistent-cache hits (jax.monitoring)."""

    def __init__(self):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def phase_device(need: int):
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", "device",
          f"no TPU: JAX sees {len(devs)} {d.platform} device(s) "
          f"({d.device_kind!r})")
    check(len(devs) >= need, "device",
          f"{need} TPU chips needed, JAX sees {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def assert_compiled(fn, *args, **kw) -> None:
    """The kernel is a Mosaic custom call in the executable, so it was
    compiled for the chip and not run through the Pallas interpreter."""
    text = fn.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in text, "kernels",
          f"{fn.__name__}: no tpu_custom_call in the compiled executable")


def _oracle(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        f32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
               for a in args]
        return np.asarray(fn(*f32, **kw), np.float32)


def _max_err(name, got, want) -> float:
    got = np.asarray(got, np.float32)
    check(got.shape == want.shape and np.isfinite(got).all(), "kernels",
          f"{name}: shape {got.shape} / finite {np.isfinite(got).all()}")
    excess = np.abs(got - want) - (KERNEL_ATOL + KERNEL_RTOL * np.abs(want))
    check(float(excess.max()) <= 0.0, "kernels",
          f"{name}: beyond tolerance by {float(excess.max()):.3g}")
    return float(np.abs(got - want).max())


def phase_kernels(cfg, seed: int, *, rows: int = 8, tokens: int = 2048,
                  block_sizes=(16, 64), rms_shapes=None) -> str:
    from repro.kernels import ops as kops
    from repro.kernels import ref
    check(kops._interpret_default() is False, "kernels",
          "kernels/ops.py chose interpret=True")
    Hq, Hkv, D, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    rms_shapes = rms_shapes or ((d, d), (rows, 1, d), (12, d))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    rng = np.random.RandomState(seed)

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    errs = {}
    for bs in block_sizes:
        nbps = tokens // bs
        q = normal(rows, 1, Hq, D)
        kp, vp = normal(rows * nbps + 1, bs, Hkv, D), \
            normal(rows * nbps + 1, bs, Hkv, D)
        bt = jnp.asarray(1 + rng.permutation(rows * nbps)
                         .reshape(rows, nbps), jnp.int32)
        valid = jnp.asarray(rng.randint(1, tokens + 1, rows), jnp.int32)
        assert_compiled(kops.paged_attention, q, kp, vp, bt, valid)
        errs[f"paged(bs={bs})"] = _max_err(
            f"paged_attention bs={bs}",
            kops.paged_attention(q, kp, vp, bt, valid),
            _oracle(ref.ref_paged_attention, q, kp, vp, bt, valid))
    q = normal(1, Hq, tokens, D)
    k, v = normal(1, Hkv, tokens, D), normal(1, Hkv, tokens, D)
    assert_compiled(kops.flash_attention, q, k, v)
    errs["flash"] = _max_err("flash_attention",
                             kops.flash_attention(q, k, v, causal=True),
                             _oracle(ref.ref_attention, q, k, v,
                                     causal=True))
    g = normal(d) * 0.1
    for shape in rms_shapes:
        x = normal(*shape)
        assert_compiled(kops.rmsnorm, x, g)
        errs["rmsnorm" + "x".join(map(str, shape))] = _max_err(
            f"rmsnorm {shape}", kops.rmsnorm(x, g),
            _oracle(ref.ref_rmsnorm, x, g))
    # the row append writes what the portable loop writes, bit for bit
    from repro.models.transformer import append_rows
    cache, new = normal(4, rows, tokens, Hkv, D), normal(4, rows, Hkv, D)
    pos = jnp.asarray(([0, 127, 128, tokens - 1, tokens + 5]
                       + list(rng.permutation(tokens)))[:rows], jnp.int32)
    assert_compiled(kops.kv_append, cache, new, pos, lead=1)
    same = np.array_equal(
        np.asarray(kops.kv_append(cache, new, pos, lead=1)).view(np.uint16),
        np.asarray(append_rows(cache, new, pos, lead=1)).view(np.uint16))
    check(same, "kernels", "kv_append differs from the append loop")
    return (f"interpret=False, tpu_custom_call in {len(errs) + 1}/"
            f"{len(errs) + 1} executables, kv_append == loop bit for bit, "
            f"max|kernel-oracle| "
            + " ".join(f"{k}={v:.3g}" for k, v in errs.items())
            + f" (tol {KERNEL_ATOL}+{KERNEL_RTOL}*|oracle|)")


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def make_workload(cfg, seed: int, n: int, lens, max_new: int,
                  mean_gap_s: float):
    """(arrival offset, prompt, max_new) triples with Poisson arrivals."""
    rng = np.random.RandomState(seed)
    offsets = np.cumsum(rng.exponential(mean_gap_s, size=n))
    return [(float(offsets[i]),
             rng.randint(0, cfg.vocab, int(rng.choice(lens))).astype(
                 np.int32), max_new) for i in range(n)]


def serve_arm(cfg, params, workload, *, warm_lens, inspect=None, **knobs):
    """Warm every prefill bucket (tracing, compiles, the move into
    co-execution), then serve ``workload`` against the wall clock.
    Returns (requests, wall seconds, stats after warmup, final stats,
    ``inspect(scheduler)``); the scheduler is closed and freed."""
    sch = ContinuousBatchingScheduler(cfg, params, **knobs)
    rng = np.random.RandomState(len(warm_lens))
    sch.serve([Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                       max_new_tokens=4, arrival_time=0.0)
               for L in warm_lens])
    stats0 = dict(sch.stats)
    t0 = time.perf_counter()
    reqs = [Request(prompt=p, max_new_tokens=mn, arrival_time=t0 + off)
            for off, p, mn in workload]
    sch.serve(reqs)
    wall = time.perf_counter() - t0
    st = dict(sch.stats)
    seen = inspect(sch) if inspect is not None else None
    sch.close()
    del sch
    gc.collect()            # free its device buffers before the next arm
    return reqs, wall, stats0, st, seen


def reading(reqs, wall: float) -> str:
    toks = sum(len(r.out_tokens or ()) for r in reqs)
    ttft = np.median([r.first_token_time - r.arrival_time for r in reqs])
    return f"{toks / wall:.1f} tok/s, TTFT p50 {ttft * 1e3:.1f} ms"


def check_served(phase: str, reqs, stats0, st) -> str:
    done = sum(len(r.out_tokens or ()) == r.max_new_tokens for r in reqs)
    check(done == len(reqs), phase, f"{done}/{len(reqs)} requests complete")
    check(st["phase"] == "co-execution", phase, f"phase={st['phase']}")
    check(st["steady_entries"] >= 1, phase,
          f"steady_entries={st['steady_entries']}")
    retraces = st["retraces"] - stats0["retraces"]
    check(retraces == 0, phase, f"{retraces} retraces after warmup")
    return (f"{done}/{len(reqs)} complete, phase={st['phase']}, "
            f"steady_entries={st['steady_entries']}, retraces after "
            f"warmup=0")


def phase_serve_dense(cfg, params, workload, warm_lens, knobs):
    tre, twall, s0, st, _ = serve_arm(cfg, params, workload,
                                      warm_lens=warm_lens, use_terra=True,
                                      **knobs)
    summary = check_served("serve(dense)", tre, s0, st)
    check(st["kv_append_kernel_leaves"] == st["kv_append_leaves"] == 2,
          "serve(dense)", f"kv_append_kernel_leaves="
          f"{st['kv_append_kernel_leaves']} of {st['kv_append_leaves']}")
    nre, nwall, _, _, _ = serve_arm(cfg, params, workload,
                                    warm_lens=warm_lens, use_terra=False,
                                    **knobs)
    print(f"serve(dense) smoke reading, not a metric: terra "
          f"{reading(tre, twall)} | noterra {reading(nre, nwall)}",
          flush=True)
    same = sum(a.out_tokens == b.out_tokens for a, b in zip(tre, nre))
    check(same == len(tre), "serve(dense)",
          f"greedy tokens differ terra vs noterra in "
          f"{len(tre) - same}/{len(tre)} requests")
    ntok = sum(len(r.out_tokens) for r in tre)
    return tre, (f"{summary}, kv_append writes 2/2 leaves, greedy tokens "
                 f"terra (kernel) == noterra (loop) ({ntok}/{ntok})")


def segment_text(sch, op_name: str) -> str:
    """Compiled text of the co-executed segment holding ``op_name``
    (lowered from its abstract arguments; the persistent compilation
    cache makes the compile a lookup)."""
    eng = sch._tf.engine
    for sp in eng.gp.seg_progs:
        plan = sp.plan
        if op_name not in plan.kernel_ops:
            continue

        def var_avals(ids):
            return tuple(jax.ShapeDtypeStruct(eng.vars[v].aval.shape,
                                              eng.vars[v].aval.dtype)
                         for v in ids)
        feeds = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for _, _, a in plan.feed_keys)
        sels = jax.ShapeDtypeStruct((len(plan.sel_uids),), jnp.int32)
        trips = jax.ShapeDtypeStruct((len(plan.trip_uids),), jnp.int32)
        check(not plan.carries_in, "serve(paged)",
              "decode segment takes carries")
        return sp.fn.lower(var_avals(plan.don_var_ids),
                           var_avals(plan.keep_var_ids), feeds, sels, trips,
                           ()).compile().as_text()
    check(False, "serve(paged)", f"no segment holds {op_name}")


def phase_serve_paged(cfg, params, workload, warm_lens, knobs, dense_reqs):
    reqs, wall, s0, st, text = serve_arm(
        cfg, params, workload, warm_lens=warm_lens, use_terra=True,
        page_size=PAGE_SIZE,
        inspect=lambda sch: segment_text(sch, "kernel.slot_decode"),
        **knobs)
    summary = check_served("serve(paged)", reqs, s0, st)
    check(st["kernels_substituted"] >= 1, "serve(paged)",
          f"kernels_substituted={st['kernels_substituted']}")
    check("tpu_custom_call" in text, "serve(paged)",
          "decode segment has no tpu_custom_call")
    print(f"serve(paged) smoke reading, not a metric: {reading(reqs, wall)}",
          flush=True)
    first = sum(a.out_tokens[0] == b.out_tokens[0]
                for a, b in zip(reqs, dense_reqs))
    check(first == len(reqs), "serve(paged)",
          f"first tokens differ from dense in {len(reqs) - first} requests")
    later = sum(x != y for a, b in zip(reqs, dense_reqs)
                for x, y in zip(a.out_tokens[1:], b.out_tokens[1:]))
    nlater = sum(len(a.out_tokens) - 1 for a in reqs)
    # a greedy stream that flips once differs from there on: where each
    # request first differs says more than the count
    split = sorted(next(i for i, (x, y) in enumerate(zip(a.out_tokens,
                                                           b.out_tokens))
                        if x != y)
                   for a, b in zip(reqs, dense_reqs)
                   if a.out_tokens != b.out_tokens)
    return (f"{summary}, kernels_substituted={st['kernels_substituted']}, "
            f"tpu_custom_call in the decode segment, first tokens == dense "
            f"({first}/{len(reqs)}); reading: later tokens differing from "
            f"dense {later}/{nlater}, {len(reqs) - len(split)}/{len(reqs)} "
            f"requests identical, first difference at token {split}")


# --------------------------------------------------------------------------
# four chips: sharded training
# --------------------------------------------------------------------------

def _bytes_in_use(devs, key="bytes_in_use"):
    return [d.memory_stats()[key] for d in devs]


def _shares(used, total):
    return "[" + ", ".join(f"{u / total:.3f}" for u in used) + "]"


def phase_train(cfg, seed: int, devs, *, steps: int, batch: int,
                seq_len: int) -> str:
    mesh = make_mesh((2, 2), ("data", "model"), devices=devs)
    abstract = M.abstract_params(cfg)
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree.leaves(abstract))
    # bf16 params + f32 master copy + two f32 moments (see optimizer.py)
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(abstract)) + 12 * n_params
    opt_cfg = OptConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    losses, lines = {}, []
    for use_terra in (False, True):
        arm = "terra" if use_terra else "plain"
        t0 = time.perf_counter()
        base = _bytes_in_use(devs)
        tr = Trainer(cfg, opt_cfg, seed=seed, batch=batch, seq_len=seq_len,
                     mesh=mesh, log_every=1, use_terra=use_terra)
        born = [u - b for u, b in zip(_bytes_in_use(devs), base)]
        check(max(born) < STATE_SHARE_MAX * total, "train",
              f"{arm}: state at creation per device {_shares(born, total)}"
              f" of the total state")
        hist = tr.train(steps, verbose=False)
        losses[arm] = [loss for _, loss in hist]
        state = [v.value() for v in tr.p_vars + tr.o_vars]
        spread = min(len(x.sharding.device_set) for x in state)
        check(spread == len(devs), "train",
              f"{arm}: a state leaf lives on {spread} device(s) after steps")
        del state
        after = _bytes_in_use(devs)
        check(max(after) < STATE_SHARE_MAX * total, "train",
              f"{arm}: after {steps} steps per device "
              f"{_shares(after, total)} of the total state")
        if use_terra:
            tr._iteration.close()
        # a reading: the process-wide peak, so the second arm shows only
        # where it rose above the first
        peak = _bytes_in_use(devs, "peak_bytes_in_use")
        lines.append(f"{arm}: created {_shares(born, total)}, after steps "
                     f"{_shares(after, total)}, peak so far "
                     f"{_shares(peak, total)}, every leaf on {spread} "
                     f"devices, {time.perf_counter() - t0:.1f} s")
        del tr
        gc.collect()
    a, b = np.asarray(losses["terra"]), np.asarray(losses["plain"])
    check(len(a) == len(b) == steps and np.isfinite(a).all()
          and np.isfinite(b).all(), "train", f"losses {losses}")
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    check(rel <= LOSS_RTOL, "train",
          f"terra vs plain losses differ by {rel:.3g} relative: {losses}")
    return (f"{cfg.n_layers} layers, {n_params / 1e9:.3f} B params, "
            f"state {total / 2**30:.2f} GiB on a 2x2 (data, model) mesh; "
            f"losses terra {[round(float(x), 4) for x in a]} plain "
            f"{[round(float(x), 4) for x in b]} (max rel diff {rel:.2e}, tol "
            f"{LOSS_RTOL}); bytes_in_use share per device: "
            + "; ".join(lines))


# --------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on 4 chips")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache_dir = compile_cache.enable()
    compiles = CompileCounter()
    device = phase_device(4 if args.four_chips else 1)
    cfg = get_config(ARCH)

    def done(name: str, t0: float, line: str) -> None:
        print(f"{name}: {line} [{time.perf_counter() - t0:.1f} s]",
              flush=True)

    if args.four_chips:
        t0 = time.perf_counter()
        tcfg = dataclasses.replace(cfg, n_layers=TRAIN_LAYERS)
        done("train(4 chips)", t0,
             phase_train(tcfg, args.seed, jax.devices()[:4],
                         steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                         seq_len=TRAIN_SEQ))
    else:
        t0 = time.perf_counter()
        done("kernels", t0, phase_kernels(cfg, args.seed))
        t0 = time.perf_counter()
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        workload = make_workload(cfg, args.seed, N_REQUESTS, PROMPT_LENS,
                                 MAX_NEW, MEAN_GAP_S)
        dense, line = phase_serve_dense(cfg, params, workload, PROMPT_LENS,
                                        SERVE)
        done("serve(dense)", t0, line)
        t0 = time.perf_counter()
        done("serve(paged)", t0,
             phase_serve_paged(cfg, params, workload, PROMPT_LENS, SERVE,
                               dense))
    print(f"total: {time.perf_counter() - t_start:.1f} s wall; compile "
          f"cache {cache_dir}: {compiles.hits}/{compiles.requests} compile "
          f"requests hit", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
