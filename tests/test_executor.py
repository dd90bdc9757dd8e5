"""Behaviour tests for the executor package: compatibility shim, the
cross-version compiled-segment cache, donated variable buffers, and the
divergence fallback's replay contract."""

import numpy as np
import pytest

from repro.core import Variable, function, ops


def test_runner_shim_reexports():
    """Historical import paths keep working after the decomposition."""
    from repro.core.runner import (SKELETON, TRACING, DivergenceError,
                                   GraphRunner, TerraEngine, Walker)
    from repro.core.executor import TerraEngine as NewEngine
    assert TerraEngine is NewEngine
    assert isinstance(TRACING, str) and isinstance(SKELETON, str)
    assert DivergenceError is not None and Walker is not None
    assert GraphRunner is not None


def test_executor_modules_stay_small():
    """The decomposition contract: no executor (or passes, serving
    scheduler, events, or kernels) module regrows past ~350 lines, and
    the shim stays under 50."""
    import os
    import repro.core.events as events
    import repro.core.executor as ex
    import repro.core.passes as passes
    import repro.core.persist as persist
    import repro.kernels as kern
    import repro.obs as obs
    import repro.serve.scheduler as sched
    for pkg in (ex, passes, sched, kern, events, persist, obs):
        pkg_dir = os.path.dirname(pkg.__file__)
        pkg_name = os.path.basename(pkg_dir)
        for name in os.listdir(pkg_dir):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(pkg_dir, name)) as f:
                n = sum(1 for _ in f)
            assert n <= 360, f"{pkg_name}/{name} has {n} lines"
    import repro.core.runner as shim
    with open(shim.__file__.replace(".pyc", ".py")) as f:
        assert sum(1 for _ in f) < 50, "runner.py shim regrew"


def test_segment_cache_hit_after_divergence():
    """A TraceGraph version bump that leaves a segment structurally
    unchanged must reuse its jitted fn (observable as a cache hit)."""
    class Cfg:
        scale = 1.0
    cfg = Cfg()

    @function
    def step(x):
        y = ops.mul(x, 2.0)
        s = float(ops.reduce_sum(y))       # gating fetch: segment boundary
        z = ops.mul(y, cfg.scale)          # baked const -> diverges on change
        return float(ops.reduce_sum(z)) + 0.0 * s

    for i in range(4):
        step(np.full(4, i + 1.0, np.float32))
    assert step.phase == "co-execution"
    base_hits = step.stats["segment_cache_hits"]
    base_recompiled = step.stats["segments_recompiled"]

    cfg.scale = 3.0                        # forced divergence (Fig. 1c class)
    for i in range(4, 9):
        x = np.full(4, i + 1.0, np.float32)
        got = step(x)
        assert got == pytest.approx(float((x * 2 * 3.0).sum())), f"iter {i}"
    assert step.phase == "co-execution"
    assert step.stats["replays"] >= 1
    # the pre-fetch segment did not change: its compiled fn was reused ...
    assert step.stats["segment_cache_hits"] >= base_hits + 1
    # ... and only the changed region recompiled (not the whole program)
    assert step.stats["segments_recompiled"] == base_recompiled + 1
    step.close()


def test_segment_cache_reuses_fn_object():
    """Same-structure regeneration returns the identical compiled callable."""
    from repro.core.graphgen import GraphProgram

    @function
    def step(x):
        y = ops.mul(x, 2.0)
        s = float(ops.reduce_sum(y))
        z = ops.add(y, 1.0)
        return float(ops.reduce_sum(z)) + 0.0 * s

    for i in range(3):
        step(np.full(4, i + 1.0, np.float32))
    eng = step.engine
    old_fns = [sp.fn for sp in eng.gp.seg_progs]
    # regeneration carries the pass results (opt) of the live program:
    # same optimized structure -> identical cached callables
    regen = GraphProgram(eng.tg, {vid: v.aval for vid, v in eng.vars.items()},
                         seg_cache=eng.seg_cache, opt=eng.gp.opt)
    assert [sp.fn for sp in regen.seg_progs] == old_fns
    step.close()


def test_coalesced_segments_not_recompiled_on_regeneration():
    """Segment signatures are computed strictly POST-pass: regenerating a
    program whose optimized (coalesced) form is unchanged must be a pure
    cache hit — the pre-pass layout never reaches the cache key, so the
    coalesced segment cannot be spuriously recompiled."""
    from repro.core.graphgen import GraphProgram

    @function(optimize="all")
    def step(x):
        a = ops.mul(x, 2.0)
        sa = ops.reduce_sum(a)
        b = ops.mul(a, 3.0)
        sb = ops.reduce_sum(b)
        return float(sa) + float(sb)       # late reads -> boundary coalesces

    r = np.random.RandomState(0)
    for _ in range(6):
        step(r.randn(4).astype(np.float32))
    eng = step.engine
    assert step.phase == "co-execution"
    assert step.stats["segments_coalesced"] >= 1
    base_misses = eng.seg_cache.misses
    regen = GraphProgram(eng.tg, {vid: v.aval for vid, v in eng.vars.items()},
                         seg_cache=eng.seg_cache, opt=eng.gp.opt)
    assert eng.seg_cache.misses == base_misses, \
        "identical optimized segments recompiled on regeneration"
    assert [sp.fn for sp in regen.seg_progs] == \
        [sp.fn for sp in eng.gp.seg_progs]
    step.close()


def test_divergence_replays_validated_prefix_exactly_once():
    class Cfg:
        k = 1.0
    cfg = Cfg()

    @function
    def step(x):
        a = ops.mul(x, 2.0)
        b = ops.add(a, 1.0)
        c = ops.mul(b, cfg.k)              # divergence point when k changes
        return ops.reduce_sum(c)

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    assert step.phase == "co-execution"
    assert step.stats["replays"] == 0

    cfg.k = 2.0
    x = np.full(4, 1.0, np.float32)
    got = float(step(x))
    assert got == pytest.approx(float(((x * 2 + 1) * 2).sum()))
    # exactly one fallback, replaying exactly the 2-entry validated prefix
    assert step.stats["replays"] == 1
    assert step.stats["replayed_entries"] == 2
    step.close()


def test_donated_variable_buffers_fire_and_stay_correct():
    """A segment that rewrites a variable first written by an earlier
    segment of the same iteration donates the intermediate buffer."""
    w = Variable(np.ones(1024, np.float32))

    @function
    def step(x):
        w.assign(ops.mul(w.read(), 2.0))
        s = float(ops.reduce_sum(w.read()))  # boundary between the writes
        w.assign(ops.mul(x, 3.0))
        return s

    eng = step.engine
    for i in range(6):
        x = np.full(1024, float(i + 1), np.float32)
        s = step(x)
        # s fetches w*2 where w committed as 3*i at the previous iteration
        want = (1.0 if i == 0 else 3.0 * i) * 2 * 1024
        assert s == pytest.approx(want), f"iter {i}"
        # the committed store value stays correct after donation
        np.testing.assert_allclose(np.asarray(eng.variable_value(w)),
                                   np.full(1024, 3.0 * (i + 1)))
    step.wait()
    assert step.stats["donated_bytes"] > 0
    # iteration-start buffers are snapshot-protected: only the intermediate
    # (first-write) buffer is donated, once per co-executed iteration
    assert step.stats["donated_bytes"] % 4096 == 0
    step.close()


def test_donation_never_marks_snapshot_buffers():
    """Static eligibility: a variable whose only write in the program is
    its first write must never be marked donatable (the snapshot owns the
    iteration-start buffer)."""
    w = Variable(np.ones(8, np.float32))

    @function
    def step(x):
        y = ops.mul(w.read(), x)
        w.assign(ops.add(w.read(), 1.0))
        return ops.reduce_sum(y)

    for i in range(4):
        step(np.full(8, 1.0, np.float32))
    assert step.phase == "co-execution"
    assert step.engine.gp.donatable_var_ids == set()
    assert step.stats["donated_bytes"] == 0
    step.close()


def test_divergence_after_donating_segments_rolls_back():
    """Divergence cancellation must survive donation: the snapshot holds
    the iteration-start buffers, which are never donated."""
    class Cfg:
        flip = False
    cfg = Cfg()
    w = Variable(np.full(256, 2.0, np.float32))

    @function
    def step(x):
        w.assign(ops.mul(w.read(), 2.0))
        s = float(ops.reduce_sum(w.read()))
        w.assign(ops.mul(x, 3.0))
        if cfg.flip:                      # Python-level change -> divergence
            w.assign(ops.add(w.read(), 1.0))
        return s

    for i in range(4):
        step(np.full(256, float(i + 1), np.float32))
    assert step.stats["donated_bytes"] > 0
    cfg.flip = True
    x = np.full(256, 9.0, np.float32)
    step(x)
    assert step.stats["replays"] == 1
    np.testing.assert_allclose(
        np.asarray(step.engine.variable_value(w)), np.full(256, 28.0))
    step.close()


def test_serving_decode_coexecutes():
    """The serving engine's decode loop runs under Terra co-execution and
    its TraceGraph (and compiled segments) survive batch boundaries."""
    import jax
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=48)
    rng = np.random.RandomState(0)
    for _ in range(2):
        reqs = [Request(prompt=rng.randint(0, cfg.vocab, 8).astype(np.int32),
                        max_new_tokens=6) for _ in range(2)]
        out = engine.run_batch(reqs)
        for r in out:
            assert len(r.out_tokens) == 6
    assert engine.terra.phase == "co-execution"
    stats = engine.terra.stats
    assert stats["replays"] == 0
    assert stats["graph_versions"] == 1       # one graph serves both batches
    engine.terra.close()


def test_eager_assign_keeps_the_device_buffer():
    """Outside an engine an op returns an eager TerraTensor; assigning it
    to a Variable keeps that device buffer (and so its sharding) instead
    of a round trip through the host onto the default device."""
    v = Variable(np.ones(4, np.float32))
    out = ops.add(v.read(), 1.0)
    v.assign(out)
    assert v._value is out._eager
    np.testing.assert_array_equal(np.asarray(v._value), np.full(4, 2.0))


def test_closed_iteration_frees_the_previous_state():
    """After a co-executed call returns, only the new state stays alive:
    the Variable's initial value and the iteration-start snapshot (the
    state before the call) are freed once nothing can replay the call,
    and outside a call the Variable reads its committed value."""
    import gc
    import weakref
    import jax.numpy as jnp
    w = Variable(jnp.ones(4, jnp.float32), "w")

    @function
    def step(x):
        w.assign(ops.add(w.read(), x))
        return ops.reduce_sum(w.read())

    initial = weakref.ref(w._value)
    olds = []
    for i in range(5):
        out = step(np.full(4, 1.0, np.float32))
        assert float(out) == pytest.approx(4 * (2.0 + i))
        step.wait()
        olds.append(weakref.ref(step.engine.store.buffers[w.var_id]))
        if i == 0:                  # a traced iteration committed
            gc.collect()
            assert initial() is None
    assert step.phase == "co-execution"
    gc.collect()
    assert [r() is None for r in olds] == [True] * 4 + [False]
    np.testing.assert_array_equal(np.asarray(w.value()), np.full(4, 6.0))
    w.assign(np.zeros(4, np.float32))          # out of band, via the owner
    assert float(step(np.full(4, 1.0, np.float32))) == pytest.approx(4.0)
    step.close()


def test_kept_placeholder_replays_its_closed_iteration():
    """A placeholder the graph does not output, kept past the call, still
    materializes: it holds its iteration's start snapshot, and the replay
    from it leaves the committed state unchanged."""
    w = Variable(np.ones(4, np.float32), "w")
    kept = []

    @function
    def step(x):
        kept.append(ops.mul(w.read(), 2.0))
        w.assign(ops.add(w.read(), x))
        return ops.reduce_sum(w.read())

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    assert step.phase == "co-execution"
    replays = step.stats["replays"]
    np.testing.assert_array_equal(np.asarray(kept[-1]), np.full(4, 6.0))
    assert step.stats["replays"] == replays + 1
    np.testing.assert_array_equal(np.asarray(w.value()), np.full(4, 4.0))
    step.close()
