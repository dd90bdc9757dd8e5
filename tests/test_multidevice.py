"""Multi-device tests (pipeline parallelism, compressed DP all-reduce,
sharded train step) — run in a subprocess with 8 forced host devices so the
main pytest process keeps its single-device jax state."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow       # multi-minute suite; see pytest.ini

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> dict:
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "(os.environ.get('XLA_FLAGS','') + "
            "' --xla_force_host_platform_device_count=8')\n"
            + textwrap.dedent(code))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_pipeline_matches_sequential():
    res = run_sub("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import make_pipelined_apply

    mesh = make_mesh((8,), ("stage",))
    S, M, mb, d = 8, 16, 4, 32
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.2)
    xs = jnp.asarray(rng.randn(M, mb, d).astype(np.float32))

    pipe = make_pipelined_apply(mesh, "stage",
                                lambda p, x: jnp.tanh(x @ p["w"]))
    with mesh:
        got = pipe({"w": ws}, xs)

    # sequential reference
    ref = xs
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    err = float(jnp.abs(got - ref).max())
    print(json.dumps({"err": err}))
    """)
    assert res["err"] < 1e-5


def test_shardmap_ep_moe_matches_pjit_path():
    """The explicit all_to_all expert-parallel MoE (models/moe_ep.py) must
    agree exactly with the pjit capacity-scatter path."""
    res = run_sub("""
    import json, dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel.sharding import ShardingPolicy, use_policy

    cfg = smoke_config("deepseek-moe-16b")
    cfg = dataclasses.replace(cfg, n_experts=8, top_k=2,
                              capacity_factor=8.0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (4, 32)).astype(np.int32)
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh, use_policy(ShardingPolicy(mesh)):
        ref = M.forward(cfg, params, tokens)
        cfg2 = dataclasses.replace(cfg, moe_impl="shard_map")
        got = jax.jit(lambda p, t: M.forward(cfg2, p, t))(params, tokens)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    print(json.dumps({"err": err}))
    """)
    assert res["err"] == 0.0


def test_compressed_dp_allreduce_matches_mean():
    res = run_sub("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.compression import dp_allreduce, zero_residuals

    mesh = make_mesh((8,), ("data",))
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    grads = {"w": g}
    red = dp_allreduce(mesh, "data", compression="bf16")
    with mesh:
        out, resid = red(grads, zero_residuals(grads))
    want = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
    err = float(jnp.abs(out["w"] - want).max() / jnp.abs(want).max())
    print(json.dumps({"err": err}))
    """)
    assert res["err"] < 1e-2        # bf16 quantization noise only


def test_sharded_train_step_runs_on_8_devices():
    res = run_sub("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel import specs as S
    from repro.parallel.sharding import ShardingPolicy, use_policy
    from repro.train import optimizer as opt
    from repro.train.train_step import build_train_step

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ost = opt.init(params)
    pspecs = S.tree_param_specs(mesh, params)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    osh = {"step": NamedSharding(mesh, P()),
           "m": psh, "v": psh, "master": psh}
    params = jax.device_put(params, psh)
    ost = jax.device_put(ost, osh)
    step = build_train_step(cfg, opt.OptConfig(lr=1e-3, warmup_steps=2,
                                               total_steps=10),
                            microbatches=2)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (8, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (8, 32)),
                                   jnp.int32)}
    with mesh, use_policy(ShardingPolicy(mesh)):
        jstep = jax.jit(step)
        losses = []
        for i in range(4):
            params, ost, m = jstep(params, ost, batch)
            losses.append(float(m["loss"]))
    print(json.dumps({"losses": losses}))
    """)
    import numpy as np
    losses = res["losses"]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_trainer_state_stays_sharded_terra_and_plain():
    """Trainer(mesh=...) creates params and optimizer state sharded over
    the mesh, and the co-executed and plain paths both keep every leaf
    spread over all 8 devices after steps, with matching losses."""
    res = run_sub("""
    import json
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import OptConfig
    from repro.train.trainer import Trainer

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = smoke_config("granite-3-2b")
    out = {}
    for use_terra in (True, False):
        tr = Trainer(cfg, OptConfig(lr=1e-3, warmup_steps=1, total_steps=3),
                     mesh=mesh, batch=8, seq_len=32, log_every=1,
                     use_terra=use_terra)
        state = tr.p_vars + tr.o_vars
        born = min(len(v.value().sharding.device_set) for v in state)
        hist = tr.train(3, verbose=False)
        read = (tr._iteration.engine.variable_value if use_terra
                else lambda v: v.value())
        after = min(len(read(v).sharding.device_set) for v in state)
        if use_terra:
            tr._iteration.close()
        out["terra" if use_terra else "plain"] = {
            "born": born, "after": after,
            "losses": [loss for _, loss in hist]}
    print(json.dumps(out))
    """)
    import numpy as np
    for arm in res.values():
        assert arm["born"] == 8 and arm["after"] == 8, res
    np.testing.assert_allclose(res["terra"]["losses"],
                               res["plain"]["losses"], rtol=1e-3)
