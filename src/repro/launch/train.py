"""Training launcher: config-driven entry point wiring the mesh, sharding
policy, Terra-driven Trainer, checkpointing and elastic restart.

    # single-process (CPU dev / one accelerator):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

    # elastic: the launcher builds a mesh from whatever devices exist and
    # reshards the checkpoint on load (data x model factorization chosen by
    # --model-parallel)
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 100 --model-parallel 2

On a real TPU slice this process is started once per host by the cluster
scheduler (GKE/Borg); jax.distributed.initialize() is invoked when the
standard TPU env vars are present.  Fault tolerance: crash at any point and
re-launch with the same --ckpt-dir — training resumes from the last
committed step with the data stream reseeked deterministically.
"""

from __future__ import annotations

import argparse
import os

import jax

from repro.configs import get_config, smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer


def build_mesh(model_parallel: int):
    n = jax.device_count()
    if n == 1 or model_parallel <= 1:
        return None
    assert n % model_parallel == 0, \
        f"{n} devices not divisible by model_parallel={model_parallel}"
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--no-terra", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    if "TPU_WORKER_ID" in os.environ or "MEGASCALE_COORDINATOR_ADDRESS" \
            in os.environ:
        jax.distributed.initialize()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = build_mesh(args.model_parallel)
    print(f"launch: arch={cfg.name} devices={jax.device_count()} "
          f"mesh={'1-device' if mesh is None else dict(mesh.shape)}")

    trainer = Trainer(
        cfg,
        OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                  total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, batch=args.batch, seq_len=args.seq_len,
        microbatches=args.microbatches, mesh=mesh,
        log_every=args.log_every, ckpt_every=args.ckpt_every,
        use_terra=not args.no_terra, seed=args.seed)
    if trainer.start_step:
        print(f"auto-resumed from step {trainer.start_step}")
    hist = trainer.train(args.steps)
    if hist:
        print(f"done: loss {hist[0][1]:.4f} -> {hist[-1][1]:.4f}")
    if trainer.straggler_events:
        print(f"stragglers flagged: {len(trainer.straggler_events)}")
    if not args.no_terra:
        print("terra:", {k: v for k, v in trainer._iteration.stats.items()
                         if isinstance(v, int)})
        trainer._iteration.close()


if __name__ == "__main__":
    main()
