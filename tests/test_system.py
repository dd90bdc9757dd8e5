"""End-to-end behaviour tests for the full system: the imperative Trainer
driven through Terra co-execution (checkpoint/resume included) and the
batched serving engine."""

import tempfile

import numpy as np
import jax
import pytest

pytestmark = pytest.mark.slow       # multi-minute suite; see pytest.ini

from repro.configs import smoke_config
from repro.models import model as M
from repro.serve.engine import Request, ServingEngine
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer


def test_trainer_coexec_converges_and_resumes():
    cfg = smoke_config("granite-3-2b")
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg, OptConfig(lr=1e-3, warmup_steps=5,
                                    total_steps=100),
                     ckpt_dir=d, batch=4, seq_len=32, log_every=5,
                     ckpt_every=10)
        init = np.asarray(tr.p_vars[0].value())
        hist = tr.train(20, verbose=False)
        assert tr._iteration.phase == "co-execution"
        assert hist[-1][1] < hist[0][1]
        # outside a call a Variable reads the engine's committed state, so
        # the checkpoint holds the trained params, not the initial ones
        final = np.asarray(jax.tree.leaves(tr.state_tree()["params"])[0])
        np.testing.assert_array_equal(final, np.asarray(
            tr._iteration.engine.variable_value(tr.p_vars[0])))
        assert not np.array_equal(final, init)
        tr._iteration.close()

        tr2 = Trainer(cfg, OptConfig(lr=1e-3, warmup_steps=5,
                                     total_steps=100),
                      ckpt_dir=d, batch=4, seq_len=32, log_every=5,
                      ckpt_every=100)
        assert tr2.start_step == 20        # auto-resume (fault tolerance)
        np.testing.assert_array_equal(
            np.asarray(tr2.p_vars[0].value()), final)
        h2 = tr2.train(10, verbose=False)
        assert np.isfinite(h2[-1][1])
        tr2._iteration.close()


def test_trainer_straggler_watchdog_fields():
    cfg = smoke_config("mamba2-130m")
    tr = Trainer(cfg, OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
                 batch=2, seq_len=32, log_every=50)
    tr.train(12, verbose=False)
    assert isinstance(tr.straggler_events, list)   # watchdog active
    tr._iteration.close()


@pytest.mark.parametrize("arch", ["llama3-8b", "recurrentgemma-2b",
                                  "mixtral-8x22b"])
def test_serving_engine_generates(arch):
    cfg = smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=64)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, 16).astype(np.int32),
                    max_new_tokens=8) for _ in range(4)]
    out = engine.run_batch(reqs)
    for r in out:
        assert len(r.out_tokens) == 8
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)
    assert engine.stats["decode_steps"] >= 7


def test_serving_matches_forward_greedy():
    """Greedy decode through the engine must equal argmax over the full
    forward logits recomputed offline (system-level KV-cache check)."""
    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=32)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab, 8).astype(np.int32)
    out = engine.run_batch([Request(prompt=prompt, max_new_tokens=4)])
    seq = list(prompt)
    import jax.numpy as jnp
    for t in range(4):
        logits = M.forward(cfg, params, np.asarray([seq], np.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == out[0].out_tokens[t], f"mismatch at step {t}"
        seq.append(nxt)
