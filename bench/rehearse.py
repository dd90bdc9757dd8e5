#!/usr/bin/env python3
"""Compile a serving configuration's step programs for a described TPU
v5e chip, without the chip, and print what each needs in memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config granite-3-2b-serve

It compiles, at the configuration's own sizes: the decode step over the
whole pool as co-execution runs it (its iteration-start state is kept,
so nothing is donated), the prefill at the largest row count and
prompt bucket, and the reference's layer step.  Nothing runs, so there
are no times: only ``memory_analysis()`` per program, and the reckoning
of what lives at once.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import sys                                                  # noqa: E402

import run                                                  # noqa: E402
import spec                                                 # noqa: E402

GIB = 2 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prefill-bucket", type=int, default=2048)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.ops import op_impl
    from repro.kernels import ops as kops
    from repro.serve.scheduler import pool_ops

    kops._interpret_default = lambda: False
    bench = spec.Benchmark(run.ROOT)
    cfg = bench.config(args.config)
    family = bench.family(cfg["family"])
    mcfg = family.program_config(cfg)
    sv = cfg["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.eval_shape(lambda: family.init_weights(cfg, 0))
    p_leaves, p_def = jax.tree_util.tree_flatten(params)
    c_leaves = jax.eval_shape(lambda: pool_ops.build_pool_cache(
        mcfg, sv["max_slots"], sv["max_len"])[0])
    # the tree structure does not depend on the sizes
    _, c_def, axes, paged = pool_ops.build_pool_cache(mcfg, 1, 8)
    mid = pool_ops.register_pool_meta(mcfg, p_def, c_def, axes, 0.0,
                                      sv["max_len"], 0, 0, paged)
    attrs = dict(_meta=mid, _n_params=len(p_leaves),
                 _n_cache=len(c_leaves), _has_rng=False)
    S = sv["max_slots"]
    i32 = jnp.int32
    state = ([sds(x) for x in p_leaves] + [sds(x) for x in c_leaves]
             + [sds(jax.ShapeDtypeStruct((S,), i32)),
                sds(jax.ShapeDtypeStruct((S, 1), i32))])
    out = {}

    def report(name, compiled):
        m = compiled.memory_analysis()
        out[name] = {k: round(getattr(m, k) / GIB, 3) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}
        print(name, json.dumps(out[name]), flush=True)

    decode = jax.jit(op_impl("serve.slot_decode"),
                     static_argnames=tuple(attrs))
    report("serve.slot_decode", decode.lower(
        *state, sds(jax.ShapeDtypeStruct((S,), jnp.bool_)),
        **attrs).compile())
    B, L = sv["prefill_batch_cap"], args.prefill_bucket
    prefill = jax.jit(op_impl("serve.slot_prefill"),
                      static_argnames=tuple(attrs))
    report(f"serve.slot_prefill B={B} S={L}", prefill.lower(
        *state, sds(jax.ShapeDtypeStruct((B, L), i32)),
        sds(jax.ShapeDtypeStruct((B,), i32)),
        sds(jax.ShapeDtypeStruct((B,), i32)), **attrs).compile())
    s = family.sizes(cfg)
    key = sds(jax.ShapeDtypeStruct((2,), jnp.uint32))
    x = sds(jax.ShapeDtypeStruct((2, sv["max_len"], s.d), jnp.float32))
    report("reference layer step (2 rows)", family._layer_step.lower(
        s, key, sds(jax.ShapeDtypeStruct((), i32)), (x,), False).compile())
    cache = sum(x.size * x.dtype.itemsize for x in c_leaves) / GIB
    prm = sum(x.size * x.dtype.itemsize for x in p_leaves) / GIB
    print(json.dumps({"params_gib": round(prm, 3),
                      "pool_cache_gib": round(cache, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
