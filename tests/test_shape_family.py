"""Shape-keyed TraceGraph families (ISSUE 3, DESIGN.md §8): lifecycle,
LRU eviction, cross-family segment-cache sharing, serving batch flips —
plus the divergence-rollback / GraphRunner.cancel / strict-feeds
correctness fixes that ride along."""

import os
import re

import numpy as np
import pytest

from repro.core import Variable, function, ops


# ==========================================================================
# family lifecycle
# ==========================================================================

def test_shape_flip_zero_retrace_zero_recompile():
    """Trace shape A, trace shape B, then flip between them: every flip is
    a dictionary lookup — no retrace, no segment recompile, and the Walker
    stamp fast path resumes on the revisited family."""
    @function
    def step(x):
        y = ops.mul(x, 2.0)
        s = float(ops.reduce_sum(y))           # gating fetch -> 2 segments
        z = ops.add(y, 1.0)
        return float(ops.reduce_sum(z)) + 0.0 * s

    for i in range(3):
        step(np.full(4, i + 1.0, np.float32))
    for i in range(3):
        step(np.full(8, i + 1.0, np.float32))
    assert step.phase == "co-execution"
    st = step.stats
    eng = step.engine
    assert st["families"] == 2
    assert st["retraces"] == 1                 # shape B's first trace
    base = (st["retraces"], eng.seg_cache.misses, st["replays"],
            st["walker_fast_hits"])

    for i in range(10):
        n = 4 if i % 2 == 0 else 8
        out = step(np.full(n, 9.0, np.float32))
        assert out == pytest.approx(9 * 2 * n + n), n
    step.wait()
    assert step.phase == "co-execution"
    assert st["retraces"] == base[0]           # zero retraces across flips
    assert eng.seg_cache.misses == base[1]     # zero recompiles
    assert st["segments_recompiled"] == eng.seg_cache.misses
    assert st["replays"] == base[2]            # flips are not divergences
    assert st["walker_fast_hits"] > base[3]    # stamp fast path resumed
    assert st["family_switches"] >= 10
    step.close()


def test_family_provenance_keys():
    """Each family's TraceGraph and GraphProgram record the shape-class
    key they were generated under."""
    @function
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 3.0)))

    for n in (4, 4, 8, 8):
        step(np.full(n, 1.0, np.float32))
    eng = step.engine
    assert len(eng.fm.families) == 2
    for key, fam in eng.fm.families.items():
        assert fam.tg.family_key == key
        assert fam.gp is not None and fam.gp.family_key == key
    assert eng.gp.family_key == eng.family.key
    step.close()


def test_family_lru_eviction_and_retrace():
    """Past ``max_families`` the least recently used family is evicted;
    revisiting an evicted shape class re-traces (counted in retraces)."""
    @function(max_families=2)
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 2.0)))

    for n in (4, 4, 8, 8, 16, 16):             # 16 evicts the LRU family (4)
        out = step(np.full(n, 1.0, np.float32))
        assert out == pytest.approx(2.0 * n)
    st = step.stats
    assert st["families"] == 2
    assert st["families_evicted"] == 1
    base = st["retraces"]
    step(np.full(4, 1.0, np.float32))          # evicted shape: traces again
    assert st["retraces"] == base + 1
    step(np.full(4, 1.0, np.float32))
    assert step.phase == "co-execution"
    step.close()


def test_cross_family_segment_cache_sharing():
    """A shape-invariant segment (fixed-shape variable work before the
    boundary) is shared across family members through the SegmentCache;
    only the shape-variant segment recompiles for the sibling shape."""
    w = Variable(np.ones(16, np.float32), "xf_w")

    @function
    def step(x):
        w.assign(ops.mul(w.read(), 1.5))       # shape-invariant segment
        s = float(ops.reduce_sum(w.read()))    # gating fetch -> boundary
        return float(ops.reduce_sum(ops.mul(x, 2.0))) + 0.0 * s

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    eng = step.engine
    hits, misses = eng.seg_cache.hits, eng.seg_cache.misses
    for i in range(3):
        step(np.full(8, 1.0, np.float32))
    assert step.phase == "co-execution"
    assert step.stats["families"] == 2
    # sibling family reused the invariant segment's compiled callable ...
    assert eng.seg_cache.hits > hits
    # ... and recompiled strictly fewer segments than the whole program
    assert eng.seg_cache.misses - misses < len(eng.gp.seg_progs)
    step.wait()
    step.close()


def test_divergence_stays_within_family():
    """A real control-flow divergence re-traces only its own family; the
    sibling family's graph survives untouched."""
    class Cfg:
        k = 1.0
    cfg = Cfg()

    @function
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, cfg.k)))

    for n in (4, 4, 8, 8):
        step(np.full(n, 1.0, np.float32))
    st = step.stats
    eng = step.engine
    fam8 = eng.family
    assert st["families"] == 2
    cfg.k = 2.0                                # diverges the active family
    out = step(np.full(8, 1.0, np.float32))
    assert out == pytest.approx(16.0)
    assert st["replays"] == 1
    assert st["families"] == 2                 # no family created/destroyed
    assert eng.family is fam8
    step.close()


def test_serving_decode_alternating_batch_sizes():
    """Serving decode with alternating batch sizes reaches steady state
    with exactly one trace+compile per shape class: after warmup, flips
    cause zero retraces, zero recompiles and zero divergences."""
    import jax
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=48)
    rng = np.random.RandomState(0)

    def run(B):
        reqs = [Request(prompt=rng.randint(0, cfg.vocab, 8).astype(np.int32),
                        max_new_tokens=4) for _ in range(B)]
        for r in engine.run_batch(reqs):
            assert len(r.out_tokens) == 4

    for B in (2, 2, 4, 4):                     # warmup: both shape classes
        run(B)
    st = engine.terra.stats
    eng = engine.terra._tf.engine
    assert st["families"] == 2
    base = (st["retraces"], eng.seg_cache.misses, st["replays"])
    for i in range(6):                         # alternating batch sizes
        run(2 if i % 2 == 0 else 4)
    assert engine.terra.phase == "co-execution"
    assert st["retraces"] == base[0]
    assert eng.seg_cache.misses == base[1]
    assert st["replays"] == base[2]
    engine.terra.close()


def test_bucket_pow2_bounds_family_cardinality():
    from repro.core.executor.families import bucket_pow2
    assert [bucket_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    assert bucket_pow2(3, floor=4) == 4


# ==========================================================================
# divergence-rollback correctness (satellite bugfixes)
# ==========================================================================

def test_first_iteration_divergence_with_fresh_variable_rolls_back():
    """Divergence on an iteration whose snapshot is the empty store must
    still roll back: a Variable first registered (and buffer-seeded) during
    the diverging iteration must NOT survive in the store — the
    pre-iteration state had no buffers at all."""
    holder = {}

    @function
    def step(x):
        y = ops.mul(x, 2.0)
        s = float(ops.reduce_sum(y))           # boundary; snapshot is {}
        if holder:
            z = ops.add(holder["w"].read(), y)     # fresh var -> diverges
            return float(ops.reduce_sum(z)) + 0.0 * s
        return s

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    eng = step.engine
    assert step.phase == "co-execution"
    assert not eng.store.buffers               # empty pre-iteration state

    holder["w"] = Variable(np.full(4, 5.0, np.float32), "fresh_w")
    out = step(np.full(4, 1.0, np.float32))
    assert step.stats["replays"] == 1
    assert out == pytest.approx(4 * (5.0 + 2.0))
    # VariableStore is exactly at its pre-iteration state: the fresh
    # variable's seed buffer did not survive the rollback
    assert holder["w"].var_id not in eng.store
    # and the engine keeps working (re-seeds on the next registration)
    for i in range(2):
        out = step(np.full(4, 1.0, np.float32))
        assert out == pytest.approx(4 * (5.0 + 2.0))
    assert step.phase == "co-execution"
    step.close()


def test_graphrunner_cancel_is_public_and_clears_error():
    """GraphRunner.cancel() drains, closes the iteration window and clears
    the stashed error in one call — no attribute pokes required."""
    @function
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 2.0)))

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    eng = step.engine

    def boom():
        raise RuntimeError("boom")

    eng.runner.submit(boom, "test")
    eng.runner.cancel()                        # drains + clears the stash
    step.wait()                                # must NOT re-raise "boom"
    out = step(np.full(4, 3.0, np.float32))    # runner still alive
    assert out == pytest.approx(4 * 6.0)
    step.close()


def test_lazy_mode_closure_error_surfaces_at_fetch():
    """Lazy mode (serialized evaluation, no runner thread) must surface a
    queued closure's error on the calling thread at the fetch/fence point
    — not stash it silently and hand back stale buffers."""
    w = Variable(np.ones(4, np.float32), "lz_err_w")

    @function(lazy=True)
    def step(x):
        w.assign(ops.mul(w.read(), x))
        return ops.reduce_sum(w.read())

    for i in range(3):
        step(np.full(4, 2.0, np.float32))
    eng = step.engine

    def boom():
        raise RuntimeError("lazy boom")

    eng.runner.submit(boom, "test")
    with pytest.raises(RuntimeError, match="lazy boom"):
        eng.variable_value(w)                  # fence wait drains -> raises
    # error consumed; the engine keeps working afterwards
    val = np.asarray(eng.variable_value(w))
    np.testing.assert_allclose(val, np.full(4, 2.0 ** 3))
    step.close()


def test_no_private_graphrunner_access_in_sources():
    """The divergence handler (and everything else) goes through the
    public GraphRunner API: no ``runner._x`` attribute pokes and no
    external assignment to ``pending_error`` anywhere in the source tree
    outside graph_runner.py itself."""
    import repro
    root = os.path.dirname(repro.__file__)
    poke = re.compile(r"runner\._[a-z]|\.pending_error\s*=")
    offenders = []
    for dirpath, _, names in os.walk(root):
        for name in names:
            if not name.endswith(".py") or name == "graph_runner.py":
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if poke.search(line):
                        offenders.append(f"{path}:{lineno}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


def test_stamp_fast_path_rejects_ambiguous_siblings():
    """After a branch re-merge (DESIGN.md §7.1), the two per-path sibling
    nodes carry *identical* entry stamps — the stamp hashes the raw trace
    entry, and resolved srcs are the only thing telling the siblings
    apart.  The Walker fast path must fall back to the structural scan on
    an ambiguous stamp: blindly accepting the first match records the
    wrong Case Select, and the switch phi silently commits the OTHER
    branch's value into the variable (no divergence, no replay)."""
    v = Variable(np.zeros(4, np.float32), "amb_v")

    @function
    def step(x, flag):
        if flag:
            y = ops.mul(x, 2.0)
        else:
            y = ops.add(x, 3.0)
        h = ops.relu(x)                        # path-independent: re-merges
        z = ops.add(y, h)                      # per-path siblings, equal stamps
        v.assign(z)                            # switch phi output
        return float(ops.reduce_sum(h))        # path-independent fetch

    x = np.full(4, 1.0, np.float32)
    for flag in (True, False, True, False, True, False):
        step(x, flag)
    assert step.phase == "co-execution"
    for flag, want in ((False, 5.0), (True, 3.0), (False, 5.0)):
        step(x, flag)
        step.wait()
        np.testing.assert_allclose(
            np.asarray(step.engine.variable_value(v)), np.full(4, want),
            err_msg=f"flag={flag}: wrong branch committed into the phi")
    assert step.stats["replays"] == 0          # resolved without divergence
    step.close()


# ==========================================================================
# strict feeds (zeros substitution on a taken path is an error)
# ==========================================================================

def _feed_drop_program(**kw):
    hook = [None]

    @function(**kw)
    def step(x):
        y = ops.mul(x, 2.0)                    # x is an Input Feeding value
        if hook[0]:
            hook[0]()
        return float(ops.reduce_sum(y))        # fetch -> dispatch

    return step, hook


def test_strict_feeds_raises_on_taken_path_default():
    step, hook = _feed_drop_program()
    # vary the fed value so constant-feed folding never demotes the slot
    # (a folded feed has no collection to lose — it is a baked constant)
    for i in range(3):
        step(np.full(4, float(i + 1), np.float32))
    assert step.phase == "co-execution"
    eng = step.engine
    hook[0] = lambda: eng.walker.feed_vals.clear()   # lose a collected feed
    with pytest.raises(RuntimeError, match="never collected on the taken"):
        step(np.full(4, 1.0, np.float32))
    # the escaped error aborted the iteration cleanly: the engine is not
    # stuck half-open and the next calls re-trace and co-execute again
    hook[0] = None
    for i in range(2):
        out = step(np.full(4, 1.0, np.float32))
        assert out == pytest.approx(8.0)
    assert step.phase == "co-execution"
    step.wait()
    step.close()


def test_strict_feeds_opt_out_warns_per_engine_and_counts():
    # the warn-once latch is engine-lifetime, not process-global: a second
    # engine with the same defect must warn again
    for _ in range(2):
        step, hook = _feed_drop_program(strict_feeds=False)
        for i in range(3):
            step(np.full(4, float(i + 1), np.float32))   # no feed folding
        eng = step.engine
        base = step.stats["feeds_defaulted"]
        hook[0] = lambda: eng.walker.feed_vals.clear()
        with pytest.warns(RuntimeWarning, match="strict_feeds disabled"):
            step(np.full(4, 1.0, np.float32))
        assert step.stats["feeds_defaulted"] > base
        step.close()


def test_untaken_branch_feed_defaults_do_not_raise():
    """Zeros substitution stays legitimate (and silent) for feed slots of
    the branch NOT taken this iteration, also under strict feeds."""
    w = Variable(np.ones(4, np.float32), "sf_w")

    @function
    def step(x, big):
        s = float(ops.reduce_sum(ops.mul(x, 2.0)))
        if s > 10.0:
            z = ops.add(ops.mul(x, 3.0), big)  # feed only on this path
        else:
            z = ops.mul(x, 1.5)
        w.assign(z)
        return s

    big = np.full(4, 100.0, np.float32)
    for v in (0.5, 3.0, 0.5, 3.0, 0.5, 3.0):
        step(np.full(4, v, np.float32), big)
    assert step.phase == "co-execution"
    base = step.stats["feeds_defaulted"]
    step(np.full(4, 0.5, np.float32), big)     # small branch: big untaken
    step.wait()
    assert step.stats["feeds_defaulted"] > base
    step.close()
