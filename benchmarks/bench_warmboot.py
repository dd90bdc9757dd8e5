"""Warm-boot benchmark: persistent artifact store + checkpoint/restore.

Measures and gates the ISSUE-9 contract (DESIGN.md §14) across real
process boundaries:

* **warm boot** — a training-style workload runs twice in fresh
  subprocesses sharing one ``$TERRA_CACHE_DIR``.  ``tts`` is the
  time-to-steady-state: wall time from the first ``step()`` call until
  the call that completes in co-execution returns (cold: trace + pass
  pipeline + XLA compile; warm: hydrate + AOT deserialize + first walker
  validation).  Gates: the warm run does zero retraces and zero segment
  recompiles, hydrates at least one family, loads at least one AOT
  segment, produces bit-identical outputs, and reaches steady state
  >= 5x faster than the cold run (full mode only; ``--smoke`` records
  without enforcing the speedup on shared CI machines).
* **checkpoint/restore** — a continuous-batching scheduler is stopped
  mid-decode (requests in flight AND queued), checkpointed, and restored
  in a fresh process; every request must finish with exactly the greedy
  tokens an uninterrupted reference produced.

CI's ``warm-cache`` job uses ``--cache-run`` (one training run against
the ambient ``$TERRA_CACHE_DIR``, no tempdir) twice: the second
invocation adds ``--expect-warm``, which fails the job if anything was
retraced or recompiled.

Unlike the other benchmarks, this one leaves JAX's persistent compilation
cache (``repro.launch.compile_cache``) off: its cold arm has to compile
cold, and XLA's cache would hide the compile the store exists to skip.

Writes ``BENCH_warmboot.json``.

Usage:
    python -m benchmarks.bench_warmboot [--smoke] [--out BENCH_warmboot.json]
    python -m benchmarks.bench_warmboot --cache-run [--expect-warm]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# --------------------------------------------------------------------------
# child roles (run in fresh subprocesses)
# --------------------------------------------------------------------------

def _role_train(args) -> None:
    """Training-style workload: several matmul layers with gating fetches
    (multiple compiled segments), variables updated every iteration."""
    import numpy as np
    from repro.core import Variable, function, ops

    dim, iters = args.dim, args.iters
    ws = [Variable(np.eye(dim, dtype=np.float32) * (0.9 + 0.05 * i),
                   name=f"w{i}") for i in range(args.layers)]

    @function
    def step(x):
        h = x
        for w in ws:
            h = ops.matmul(h, w.read())
            # gating fetch: a host-visible scalar per layer forces a
            # segment boundary, so the cold run compiles several segments
            g = float(ops.reduce_sum(h)) * 0.0
            w.assign(ops.add(w.read(), ops.mul(h, 1e-4 + g)))
        return float(ops.reduce_sum(h))

    outs, tts = [], None
    t0 = time.perf_counter()
    for i in range(iters):
        outs.append(step(np.full((dim, dim), 0.01 * (i + 1), np.float32)))
        if tts is None and step.phase == "co-execution":
            tts = time.perf_counter() - t0
    step.wait()
    if tts is None:                     # never transitioned: report total
        tts = time.perf_counter() - t0
    st = step.stats
    print(json.dumps({
        "tts_s": tts, "outs": outs,
        "retraces": st["retraces"],
        "segments_recompiled": st["segments_recompiled"],
        "artifact_hits": st["artifact_hits"],
        "artifact_misses": st["artifact_misses"],
        "artifacts_stored": st["artifacts_stored"],
        "warm_families": st["warm_families"],
        "aot_loads": st["aot_loads"]}))
    step.close()


def _role_sched(args) -> None:
    """Scheduler roles: ref (uninterrupted), ckpt (stop mid-decode and
    checkpoint), resume (restore in a fresh process and drain)."""
    import numpy as np
    import jax
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request
    from repro.serve.scheduler import ContinuousBatchingScheduler

    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, 4 + i)
                    .astype(np.int32),
                    max_new_tokens=args.max_new, arrival_time=0.0)
            for i in range(args.requests)]
    kw = dict(max_slots=4, max_len=128, temperature=0.0)

    if args.role == "sched-ref":
        sch = ContinuousBatchingScheduler(cfg, params, **kw)
        sch.serve(reqs)
        print(json.dumps({"toks": [r.out_tokens for r in reqs]}))
    elif args.role == "sched-ckpt":
        sch = ContinuousBatchingScheduler(cfg, params, **kw)
        for r in reqs:
            sch.submit(r)
        sch.run(max_steps=args.ckpt_steps)      # stop mid-decode
        sch.checkpoint(args.ckpt)
        print(json.dumps({"partial": {r.rid: r.out_tokens or []
                                      for r in reqs},
                          "in_flight": sch.pool.active_count,
                          "queued": len(sch.queue)}))
    else:                                       # sched-resume
        sch = ContinuousBatchingScheduler.restore(args.ckpt, cfg, params)
        with open(os.path.join(args.ckpt, "partial.json")) as f:
            partial = {int(k): v for k, v in json.load(f).items()}
        tracked = {r.rid: r for _, r in sch.pool.active_items()}
        tracked.update({r.rid: r for r in sch.queue._queue})
        sch.run()
        for rid, r in tracked.items():
            partial[rid] = r.out_tokens
        print(json.dumps({"toks": [partial[k] for k in sorted(partial)],
                          "restores": sch.sched_stats.get(
                              "checkpoint_restores", 0)}))
    sch.close()


# --------------------------------------------------------------------------
# orchestration
# --------------------------------------------------------------------------

def _spawn(role: str, cache_dir: str, extra) -> dict:
    env = {**os.environ, "PYTHONPATH": f"{os.path.join(ROOT, 'src')}:{ROOT}"}
    if cache_dir:
        env["TERRA_CACHE_DIR"] = cache_dir
    else:
        env.pop("TERRA_CACHE_DIR", None)
    cmd = [sys.executable, "-m", "benchmarks.bench_warmboot",
           "--role", role] + extra
    out = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                         text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"{role} failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_warmboot(smoke: bool) -> dict:
    # full mode sizes the workload so XLA compile dominates the cold
    # boot (the regime the store exists for); smoke just checks wiring.
    # tts is best-of-2 per side: process wall times on a shared machine
    # carry 2x noise tails that would make a single-shot ratio flaky.
    dim, layers, iters = (64, 3, 6) if smoke else (512, 12, 8)
    extra = ["--dim", str(dim), "--iters", str(iters),
             "--layers", str(layers)]
    with tempfile.TemporaryDirectory() as c1, \
            tempfile.TemporaryDirectory() as c2:
        cold = _spawn("train", c1, extra)
        cold2 = _spawn("train", c2, extra)
        warm = _spawn("train", c1, extra)
        warm2 = _spawn("train", c1, extra)
    cold_tts = min(cold["tts_s"], cold2["tts_s"])
    warm_tts = min(warm["tts_s"], warm2["tts_s"])
    cold["tts_s"], warm["tts_s"] = cold_tts, warm_tts
    speedup = cold_tts / max(warm_tts, 1e-9)
    gates = {
        "warm_zero_retraces": warm["retraces"] == 0,
        "warm_zero_recompiles": warm["segments_recompiled"] == 0,
        "warm_hydrated": warm["warm_families"] >= 1,
        "warm_aot_loaded": warm["aot_loads"] >= 1,
        "outputs_equal": warm["outs"] == cold["outs"],
        "speedup_5x": speedup >= 5.0,
    }
    return {"cold": cold, "warm": warm,
            "tts_speedup": round(speedup, 2), "gates": gates}


def run_checkpoint(smoke: bool) -> dict:
    # 5 requests over 4 slots: the checkpoint catches 4 in flight AND one
    # still queued, covering both restore paths
    n_req, max_new, steps = (5, 8, 4) if smoke else (6, 10, 7)
    extra = ["--requests", str(n_req), "--max-new", str(max_new),
             "--ckpt-steps", str(steps)]
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "sched_ck")
        ref = _spawn("sched-ref", "", extra)
        part = _spawn("sched-ckpt", "", extra + ["--ckpt", ck])
        with open(os.path.join(ck, "partial.json"), "w") as f:
            json.dump(part["partial"], f)
        res = _spawn("sched-resume", "", extra + ["--ckpt", ck])
    return {"requests": n_req,
            "in_flight_at_ckpt": part["in_flight"],
            "queued_at_ckpt": part["queued"],
            "restores": res["restores"],
            "gates": {"token_equal": res["toks"] == ref["toks"],
                      "ckpt_mid_decode": part["in_flight"] > 0}}


def run_cache_run(args) -> dict:
    """One training run against the ambient $TERRA_CACHE_DIR (CI job)."""
    if not os.environ.get("TERRA_CACHE_DIR"):
        raise SystemExit("--cache-run requires $TERRA_CACHE_DIR")
    extra = ["--dim", "64", "--iters", "6"]
    res = _spawn("train", os.environ["TERRA_CACHE_DIR"], extra)
    res["gates"] = {}
    if args.expect_warm:
        res["gates"] = {
            "warm_zero_retraces": res["retraces"] == 0,
            "warm_zero_recompiles": res["segments_recompiled"] == 0,
            "warm_hits": res["artifact_hits"] > 0,
        }
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--role", default=None,
                    help="internal: subprocess role")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-steps", type=int, default=7)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes; record the 5x speedup, don't gate it")
    ap.add_argument("--cache-run", action="store_true",
                    help="one run against $TERRA_CACHE_DIR (CI warm-cache)")
    ap.add_argument("--expect-warm", action="store_true",
                    help="with --cache-run: fail unless fully warm")
    ap.add_argument("--out", default=None, help="write report JSON here")
    args = ap.parse_args(argv)

    if args.role == "train":
        return _role_train(args)
    if args.role in ("sched-ref", "sched-ckpt", "sched-resume"):
        return _role_sched(args)

    if args.cache_run:
        report = {"mode": "cache-run", "run": run_cache_run(args)}
        gates = report["run"]["gates"]
    else:
        report = {"mode": "smoke" if args.smoke else "full",
                  "warmboot": run_warmboot(args.smoke),
                  "checkpoint": run_checkpoint(args.smoke)}
        gates = {**report["warmboot"]["gates"],
                 **report["checkpoint"]["gates"]}
        if args.smoke:      # shared CI machines: record, don't enforce
            gates.pop("speedup_5x")
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    failed = sorted(k for k, ok in gates.items() if not ok)
    if failed:
        raise SystemExit(f"warm-boot gates failed: {failed}")
    print("all warm-boot gates passed:", sorted(gates))


if __name__ == "__main__":
    main()
