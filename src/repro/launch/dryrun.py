import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on placeholder host devices and extract memory / cost / roofline
data from the AOT artifacts.  No arrays are ever allocated — parameters,
optimizer state, caches and batches are ShapeDtypeStructs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json

The very first lines of this file force 512 host devices BEFORE any jax
import (jax locks the device count on first init).  Do not import this
module from code that needs a single-device view.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.registry import ARCHS, LONG_CONTEXT_ARCHS
from repro.launch import roofline as RL
from repro.launch.mesh import (HBM_PER_CHIP, make_mesh,
                               make_production_mesh)
from repro.models import model as M
from repro.parallel import specs as S
from repro.parallel.sharding import ShardingPolicy, use_policy
from repro.serve.serve_step import build_decode_step, build_prefill_step
from repro.train import optimizer as opt
from repro.train.train_step import build_train_step


def cell_is_defined(arch: str, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return arch in LONG_CONTEXT_ARCHS
    return True


# train cells use gradient accumulation (production-realistic): global batch
# 256 x 4096 tokens does not fit activations otherwise.
TRAIN_MICROBATCHES = 8


def reduced_cfg(cfg, k: int):
    """Same architecture with k super-blocks (and k encoder layers) — used
    for the two-point cost extrapolation: XLA's cost_analysis counts a
    while-loop body ONCE regardless of trip count (verified empirically),
    so per-layer marginal cost = F(2) - F(1), total = F(1) + (nb-1)*(F2-F1).
    Exact for homogeneous scanned stacks."""
    import dataclasses
    repl = {"n_layers": k * len(cfg.block_pattern) + len(cfg.extra_blocks),
            "unroll": True}
    if cfg.enc_layers:
        repl["enc_layers"] = k
    # keep the unrolled attention-block count small: FLOPs are invariant to
    # the block size (fully-masked blocks are still computed), so probes use
    # coarse blocks for compile speed.
    repl["q_block"] = 8192
    repl["kv_block"] = 16384
    repl["ssd_chunk"] = 4096
    return dataclasses.replace(cfg, **repl)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N*D for single-pass inference
    (N = active params, D = tokens processed in the step)."""
    n_active = M.active_param_count(cfg)
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_active * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_active * toks
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, *, microbatches: int = 1,
               donate: bool = True, cost_probe: bool = False,
               opts: Optional[Dict[str, Any]] = None):
    """Build, lower and return (lowered, aux) for one cell."""
    opts = opts or {}
    cfg = get_config(arch)
    if opts.get("remat_policy") or opts.get("moe_impl"):
        import dataclasses as _dc
        repl = {}
        if opts.get("remat_policy"):
            repl["remat_policy"] = opts["remat_policy"]
        if opts.get("moe_impl"):
            repl["moe_impl"] = opts["moe_impl"]
        cfg = _dc.replace(cfg, **repl)
    shape = SHAPES[shape_name]
    aparams = M.abstract_params(cfg)
    fsdp = opts.get("serve_fsdp", True) if shape_name != "train_4k" else True
    pspecs = S.tree_param_specs(mesh, aparams, fsdp=fsdp)
    psh = _ns(mesh, pspecs)

    extras_specs: Dict[str, Any] = {}
    if cfg.family == "vlm":
        extras_specs["cross_states"] = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        extras_specs["frontend_embeds"] = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.frontend_tokens, cfg.d_model),
            jnp.float32)

    if shape.kind == "train":
        oc = opt.OptConfig()
        aopt = jax.eval_shape(opt.init, aparams)
        ospecs = S.opt_state_specs(mesh, aopt, pspecs)
        osh = _ns(mesh, ospecs)
        batch = {"tokens": jax.ShapeDtypeStruct(
                     (shape.global_batch, shape.seq_len), jnp.int32),
                 "labels": jax.ShapeDtypeStruct(
                     (shape.global_batch, shape.seq_len), jnp.int32),
                 **extras_specs}
        bsh = _ns(mesh, {k: S.batch_spec(mesh, v.shape)
                         for k, v in batch.items()})
        mb = (microbatches if cost_probe else
              max(microbatches, opts.get("microbatches",
                                         TRAIN_MICROBATCHES)))
        step = build_train_step(cfg, oc, microbatches=mb)
        msh = {"loss": NamedSharding(mesh, P()),
               "grad_norm": NamedSharding(mesh, P()),
               "lr": NamedSharding(mesh, P())}
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, msh))
        lowered = jitted.lower(aparams, aopt, batch)
        return lowered, {"cfg": cfg, "shape": shape}

    if shape.kind == "prefill":
        tokens = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                      jnp.int32)
        acache = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
        csh = _ns(mesh, S.tree_cache_specs(mesh, acache))
        tsh = NamedSharding(mesh, S.batch_spec(mesh, tokens.shape))
        esh = {k: NamedSharding(mesh, S.batch_spec(mesh, v.shape))
               for k, v in extras_specs.items()}
        fn = build_prefill_step(cfg, shape.seq_len)

        def prefill_pos(params, tokens, *extra_vals):
            kw = dict(zip(sorted(extras_specs), extra_vals))
            return fn(params, tokens, **kw)

        jitted = jax.jit(
            prefill_pos,
            in_shardings=(psh, tsh) + tuple(esh[k]
                                            for k in sorted(extras_specs)),
            out_shardings=(NamedSharding(
                mesh, S.batch_spec(mesh, (shape.global_batch,))), csh))
        lowered = jitted.lower(aparams, tokens,
                               *[extras_specs[k]
                                 for k in sorted(extras_specs)])
        return lowered, {"cfg": cfg, "shape": shape}

    # decode
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    acache = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
    csh = _ns(mesh, S.tree_cache_specs(mesh, acache))
    tsh = NamedSharding(mesh, S.batch_spec(mesh, tokens.shape))
    esh = tuple(NamedSharding(mesh,
                              S.batch_spec(mesh, extras_specs[k].shape))
                for k in sorted(extras_specs) if k != "frontend_embeds")
    dec_extra_keys = [k for k in sorted(extras_specs)
                      if k != "frontend_embeds"]
    fn = build_decode_step(cfg)

    def decode_pos(params, cache, tokens, *extra_vals):
        kw = dict(zip(dec_extra_keys, extra_vals))
        return fn(params, cache, tokens, **kw)

    # audio decode attends to encoder states: supply them as cross_states
    extra_vals = []
    if cfg.family == "audio":
        dec_extra_keys = ["cross_states"]
        esh = (NamedSharding(mesh, S.batch_spec(
            mesh, (shape.global_batch, cfg.frontend_tokens, cfg.d_model))),)
        extra_vals = [jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype))]
    elif cfg.family == "vlm":
        extra_vals = [extras_specs["cross_states"]]

    jitted = jax.jit(
        decode_pos,
        in_shardings=(psh, csh, tsh) + esh,
        out_shardings=(NamedSharding(
            mesh, S.batch_spec(mesh, (shape.global_batch, 1))), csh),
        donate_argnums=(1,) if donate else ())
    lowered = jitted.lower(aparams, acache, tokens, *extra_vals)
    return lowered, {"cfg": cfg, "shape": shape}


def _cost_tuple(arch, shape_name, mesh, cfg_override, opts=None):
    """(flops, bytes, per-collective wire bytes) for a reduced config.

    Cost probes run at MICROBATCH scale with no accumulation loop (the
    grad-accum scan body would also be counted once); the caller multiplies
    train-cell results by TRAIN_MICROBATCHES — matching the real step,
    whose per-microbatch backward includes its gradient reduction."""
    import dataclasses as _dc
    import repro.configs.registry as REG
    orig = REG.ARCHS[arch]
    REG.ARCHS[arch] = cfg_override
    shape = SHAPES[shape_name]
    opts = opts or {}
    n_mb = opts.get("microbatches", TRAIN_MICROBATCHES)
    probe_shape = shape
    if shape.kind == "train":
        probe_shape = _dc.replace(
            shape, name=shape.name + "-probe",
            global_batch=shape.global_batch // n_mb)
    SHAPES[probe_shape.name] = probe_shape
    try:
        lowered, _ = lower_cell(arch, probe_shape.name, mesh,
                                microbatches=1, cost_probe=True, opts=opts)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        coll = RL.collective_bytes(compiled.as_text())
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)), coll)
    finally:
        REG.ARCHS[arch] = orig
        if probe_shape.name != shape.name:
            del SHAPES[probe_shape.name]


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             verbose: bool = True, extrapolate: bool = True,
             opts: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    opts = opts or {}
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "opts": opts}
    if not cell_is_defined(arch, shape_name):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §5)")
        return rec
    if opts.get("mesh_shape"):
        mesh = make_mesh(opts["mesh_shape"], ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    # the roofline table is single-pod only (per the brief); the multi-pod
    # pass proves the pod axis shards (lower+compile+memory), no probes
    if mesh_kind == "multi":
        extrapolate = False
    t0 = time.perf_counter()
    roof = None
    try:
        with mesh, use_policy(ShardingPolicy(mesh)):
            lowered, aux = lower_cell(arch, shape_name, mesh, opts=opts)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            mem = RL.memory_report(compiled)
            mf = model_flops(aux["cfg"], aux["shape"])
            # ---- two-point extrapolation over scanned layers -----------
            # k=2,3 (a scan of length 1 gets inlined by XLA, breaking
            # linearity); train costs are per-microbatch, scaled back up.
            if extrapolate:
                cfg = aux["cfg"]
                nb = cfg.n_pattern_blocks
                f2, b2, c2 = _cost_tuple(arch, shape_name, mesh,
                                         reduced_cfg(cfg, 2), opts=opts)
                f3, b3, c3 = _cost_tuple(arch, shape_name, mesh,
                                         reduced_cfg(cfg, 3), opts=opts)
                scale = (opts.get("microbatches", TRAIN_MICROBATCHES)
                         if aux["shape"].kind == "train" else 1)
                flops = (f2 + (nb - 2) * (f3 - f2)) * scale
                byt = (b2 + (nb - 2) * (b3 - b2)) * scale
                per_coll = {k: (c2[k] + (nb - 2) * (c3[k] - c2[k])) * scale
                            for k in c2}
                wire = sum(v for k, v in per_coll.items()
                           if k != "n_collectives")
                from repro.launch.mesh import (HBM_BW, ICI_BW,
                                               PEAK_FLOPS_BF16)
                amem = RL.analytic_memory_bytes(
                    cfg, aux["shape"], n_chips,
                    microbatches=opts.get("microbatches",
                                          TRAIN_MICROBATCHES))
                rec["analytic_memory"] = {k: round(v)
                                          for k, v in amem.items()}
                rec["xla_bytes_upper_bound"] = byt
                roof = RL.Roofline(
                    flops=flops, bytes_accessed=amem["total"],
                    wire_bytes=wire,
                    compute_s=flops / PEAK_FLOPS_BF16,
                    memory_s=amem["total"] / HBM_BW,
                    collective_s=wire / ICI_BW, per_coll=per_coll,
                    model_flops_per_device=mf / n_chips)
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), memory=mem,
                   fits_hbm=mem["total_nonalias_bytes"] <= HBM_PER_CHIP,
                   model_flops_total=mf, n_chips=n_chips)
        if roof is not None:
            rec["roofline"] = roof.as_dict()
    except Exception as e:  # noqa: BLE001 — failures ARE the result here
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    if verbose:
        status = rec["status"]
        extra = ""
        if status == "ok" and "roofline" in rec:
            r = rec["roofline"]
            extra = (f" compute={r['compute_s']:.3e}s "
                     f"memory={r['memory_s']:.3e}s "
                     f"coll={r['collective_s']:.3e}s dom={r['dominant']}"
                     f" fits={rec['fits_hbm']}")
        elif status == "ok":
            extra = (f" compiled; fits={rec['fits_hbm']} "
                     f"(compile {rec['compile_s']}s)")
        elif status == "error":
            extra = " " + rec["error"][:140]
        print(f"[{arch} x {shape_name} x {mesh_kind}] {status}{extra}",
              flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])
    if args.all:
        archs, shapes, meshes = sorted(ARCHS), list(SHAPES), ["single",
                                                              "multi"]
    results = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh_kind)
                results.append(rec)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
