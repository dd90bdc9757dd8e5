"""Correctness tests for the symbolic optimization pass pipeline
(core/passes/, DESIGN.md §10): legality rules per pass, the
divergence-not-crash contract of constant-feed folding, coalescing under
donation, and kernel-substitution numerics."""

import numpy as np
import pytest

from repro.core import Variable, function, ops

ALL = "all"
NONE = "none"


def _run(step, xs):
    return [float(np.asarray(step(x))) for x in xs]


def _xs(n, shape=(4,), seed=0):
    r = np.random.RandomState(seed)
    return [r.randn(*shape).astype(np.float32) for _ in range(n)]


# ==========================================================================
# DCE
# ==========================================================================

def test_dce_eliminates_dead_ops_and_preserves_values():
    def body(x):
        dead = ops.reduce_mean(ops.mul(x, 5.0))     # result discarded
        dead2 = ops.add(dead, 1.0)                  # dead consumer chain
        y = ops.mul(x, 2.0)
        return float(ops.reduce_sum(y))

    opt, ref = function(body, optimize=ALL), function(body, optimize=NONE)
    xs = _xs(6)
    assert _run(opt, xs) == pytest.approx(_run(ref, xs))
    assert opt.phase == "co-execution"
    assert opt.stats["nodes_eliminated"] >= 2
    assert ref.stats["nodes_eliminated"] == 0
    opt.close(); ref.close()


def test_dce_never_removes_variable_writes_or_fetched_values():
    w = Variable(np.ones(4, np.float32), "dce_w")

    @function(optimize=ALL)
    def step(x):
        w.assign(ops.mul(x, 3.0))          # write IS the only consumer
        m = ops.reduce_max(x)              # fetched below
        return float(m)

    xs = _xs(6, seed=1)
    for x in xs:
        got = step(x)
        assert got == pytest.approx(float(x.max()))
        step.wait()
        np.testing.assert_allclose(
            np.asarray(step.engine.variable_value(w)), x * 3.0, rtol=1e-6)
    assert step.phase == "co-execution"
    # nothing in this program is dead: both ops have observable effects
    assert step.stats["nodes_eliminated"] == 0
    step.close()


# ==========================================================================
# CSE
# ==========================================================================

def test_cse_merges_var_read_duplicates():
    w = Variable(np.full(4, 3.0, np.float32), "cse_w")

    def body(x):
        a = ops.mul(w.read(), 2.0)
        b = ops.mul(w.read(), 2.0)          # same expr, different line
        c = ops.add(a, 1.0)
        d = ops.add(b, 1.0)                 # second-level duplicate
        return float(ops.reduce_sum(ops.add(ops.mul(c, x), d)))

    opt, ref = function(body, optimize=ALL), function(body, optimize=NONE)
    xs = _xs(6, seed=2)
    assert _run(opt, xs) == pytest.approx(_run(ref, xs))
    assert opt.stats["cse_hits"] >= 2
    assert opt.stats["replays"] == 0
    opt.close(); ref.close()


def test_cse_never_merges_feed_slots():
    """Two ops consuming avals-identical feeds are NOT a common
    subexpression: the fed values are independent (per-iteration RNG keys
    are the canonical case)."""
    @function(optimize=ALL)
    def step(x):
        a = ops.random_normal((4,))          # distinct key feeds
        b = ops.random_normal((4,))
        return float(ops.reduce_sum(ops.sub(a, b)))

    outs = [step(x) for x in _xs(8, seed=3)]
    assert step.phase == "co-execution"
    assert step.stats["cse_hits"] == 0
    # if the two draws were merged the difference would be exactly zero
    assert any(abs(o) > 1e-6 for o in outs)
    step.close()


def test_cse_across_switch_branches_hoists():
    """The same pure subexpression inside both branches of a switch is
    hoisted before the fork and computed once — correct on both paths.
    Hoisting requires sources that strictly dominate the fork (variable
    reads qualify: a VarRef read always means the iteration-start value);
    a duplicate consuming the fork node's own output stays put."""
    w = Variable(np.full(4, 2.0, np.float32), "hoist_w")

    class Cfg:
        flag = False
    cfg = Cfg()

    def body(x):
        base = float(np.asarray(ops.reduce_sum(x)))   # pre-fork anchor
        if cfg.flag:                        # Python control flow -> switch
            y = ops.add(ops.mul(w.read(), 2.0), 1.0)
        else:
            y = ops.sub(ops.mul(w.read(), 2.0), 1.0)
        return float(ops.reduce_sum(ops.add(y, x))) + 0.0 * base

    opt, ref = function(body, optimize=ALL), function(body, optimize=NONE)
    xs = _xs(10, seed=4)
    outs_o, outs_r = [], []
    for i, x in enumerate(xs):
        cfg.flag = i % 2 == 1               # alternate: both branches trace
        outs_o.append(float(np.asarray(opt(x))))
        outs_r.append(float(np.asarray(ref(x))))
    assert outs_o == pytest.approx(outs_r)
    assert opt.phase == "co-execution"
    assert opt.stats["cse_hits"] >= 2       # mul(base,2.0) in both branches
    opt.close(); ref.close()


# ==========================================================================
# Constant-feed folding
# ==========================================================================

def test_feed_folding_diverges_not_crashes_on_value_change():
    m = [np.full(4, 2.0, np.float32)]

    @function(optimize=ALL)
    def step(x):
        return float(ops.reduce_sum(ops.add(x, m[0])))

    for i in range(4):                       # m stable across the streak
        step(np.full(4, float(i), np.float32))
    assert step.stats["feeds_folded"] >= 1
    assert step.phase == "co-execution"

    m[0] = np.full(4, 9.0, np.float32)       # folded value changes
    got = step(np.full(4, 1.0, np.float32))
    assert got == pytest.approx(4 * (1.0 + 9.0))     # correct, not stale
    assert step.stats["fold_divergences"] == 1

    # the slot is now varying: it unfolds, and further changes are plain
    # feed updates with no divergence
    for i in range(3):
        step(np.full(4, float(i), np.float32))
    assert step.phase == "co-execution"
    m[0] = np.full(4, 17.0, np.float32)
    got = step(np.full(4, 1.0, np.float32))
    assert got == pytest.approx(4 * (1.0 + 17.0))
    assert step.stats["fold_divergences"] == 1       # no second divergence
    step.close()


def test_feed_folding_disabled_under_safe_pipeline():
    m = np.full(4, 2.0, np.float32)

    @function(optimize="safe")
    def step(x):
        return float(ops.reduce_sum(ops.add(x, m)))

    for i in range(4):
        step(np.full(4, float(i), np.float32))
    assert step.phase == "co-execution"
    assert step.stats["feeds_folded"] == 0
    step.close()


# ==========================================================================
# Segment coalescing
# ==========================================================================

def test_coalescing_reduces_dispatches_for_late_reads():
    def body(x):
        a = ops.mul(x, 2.0)
        sa = ops.reduce_sum(a)
        b = ops.mul(a, 3.0)
        sb = ops.reduce_sum(b)
        return float(sa) + float(sb)         # both read late

    opt, ref = function(body, optimize=ALL), function(body, optimize=NONE)
    xs = _xs(8, seed=5)
    assert _run(opt, xs) == pytest.approx(_run(ref, xs))
    opt.wait(); ref.wait()
    assert opt.stats["segments_coalesced"] >= 1
    assert opt.stats["replays"] == 0
    assert opt.stats["segments_dispatched"] < ref.stats["segments_dispatched"]
    opt.close(); ref.close()


def test_coalescing_keeps_consumed_boundaries():
    """A gating fetch whose value steers Python control flow is read
    early every trace — its boundary must survive."""
    w = Variable(np.ones(4, np.float32), "co_w")

    @function(optimize=ALL)
    def step(x):
        s = float(ops.reduce_sum(ops.mul(x, 2.0)))
        if s > 0:                            # consumed by the continuation
            w.assign(ops.mul(x, 2.0))
        else:
            w.assign(ops.mul(x, -2.0))
        return s

    for i in range(8):
        sign = 1.0 if i % 2 else -1.0
        x = np.full(4, sign * (i + 1.0), np.float32)
        got = step(x)
        step.wait()
        np.testing.assert_allclose(np.asarray(step.engine.variable_value(w)),
                                   np.abs(x) * 2.0, rtol=1e-6)
    assert step.phase == "co-execution"
    assert step.stats["segments_coalesced"] == 0
    step.close()


def test_coalescing_preserves_mid_iteration_reads_under_donation():
    """Donation analysis runs post-coalescing; a mid-iteration
    variable_value read still sees the correct intermediate and the
    committed value survives."""
    w = Variable(np.full(256, 2.0, np.float32), "don_w")
    seen = []

    @function(optimize=ALL)
    def step(x):
        w.assign(ops.mul(w.read(), 2.0))
        s = ops.reduce_sum(w.read())
        w.assign(ops.mul(x, 3.0))
        t = ops.reduce_sum(w.read())
        seen.append(float(s))                # late reads -> coalescible
        return float(t)

    eng = step.engine
    for i in range(6):
        x = np.full(256, float(i + 1), np.float32)
        got = step(x)
        assert got == pytest.approx(3.0 * (i + 1) * 256)
        # mid-stream driver read of the committed value (under donation)
        np.testing.assert_allclose(np.asarray(eng.variable_value(w)),
                                   np.full(256, 3.0 * (i + 1)))
        want_s = (2.0 if i == 0 else 3.0 * i) * 2 * 256
        assert seen[-1] == pytest.approx(want_s), f"iter {i}"
    assert step.phase == "co-execution"
    step.close()


# ==========================================================================
# Kernel substitution
# ==========================================================================

KERNEL_PIPE = ("fold", "cse", "kernels", "dce", "coalesce")


def test_kernel_substitution_rmsnorm_numerics():
    g = Variable(np.linspace(0.5, 1.5, 16).astype(np.float32), "krms_g")

    def body(x):
        return float(ops.reduce_sum(ops.rms_norm(x, g.read(), eps=1e-6)))

    opt = function(body, optimize=KERNEL_PIPE)
    ref = function(body, optimize=NONE)
    xs = _xs(5, shape=(4, 16), seed=6)
    np.testing.assert_allclose(_run(opt, xs), _run(ref, xs),
                               rtol=1e-4, atol=1e-5)
    assert opt.stats["kernels_substituted"] == 1
    assert opt.stats["replays"] == 0
    opt.close(); ref.close()


def test_kernel_substitution_attention_numerics():
    D, S = 16, 8
    mask = np.tril(np.ones((S, S), np.float32))

    def body(q, k, v):
        s = ops.einsum(q, k, expr="bsd,btd->bst")
        s = ops.add(ops.mul(s, 1.0 / D ** 0.5),
                    ops.mul(ops.sub(mask, 1.0), 1e9))
        o = ops.einsum(ops.softmax(s, axis=-1), v, expr="bst,btd->bsd")
        return ops.reduce_sum(o)

    opt = function(body, optimize=KERNEL_PIPE)
    ref = function(body, optimize=NONE)
    r = np.random.RandomState(7)
    a, b = [], []
    for _ in range(5):
        q, k, v = (r.randn(2, S, D).astype(np.float32) for _ in range(3))
        a.append(float(np.asarray(opt(q, k, v).numpy())))
        b.append(float(np.asarray(ref(q, k, v).numpy())))
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)
    assert opt.stats["kernels_substituted"] == 1
    assert opt.stats["feeds_folded"] >= 1        # the causal mask folded
    assert opt.stats["nodes_eliminated"] >= 4    # unfused chain died
    opt.close(); ref.close()


def test_kernel_substitution_skips_differentiated_graphs():
    """Tape consumers keep the unfused chain alive: substitution must not
    fire when attention intermediates feed .vjp ops."""
    from repro.core import GradientTape
    D, S = 8, 4
    mask = np.tril(np.ones((S, S), np.float32))
    wv = Variable(np.eye(D).astype(np.float32), "ks_wv")

    @function(optimize=KERNEL_PIPE)
    def step(q, k, x):
        with GradientTape() as tape:
            v = ops.matmul(x, wv.read())
            s = ops.einsum(q, k, expr="bsd,btd->bst")
            s = ops.add(ops.mul(s, 1.0 / D ** 0.5),
                        ops.mul(ops.sub(mask, 1.0), 1e9))
            o = ops.einsum(ops.softmax(s, axis=-1), v, expr="bst,btd->bsd")
            loss = ops.reduce_sum(o)
        (gv,) = tape.gradient(loss, [wv])
        wv.assign_sub(ops.mul(gv, 0.01))
        return float(loss)

    r = np.random.RandomState(8)
    for _ in range(4):
        q, k, x = (r.randn(2, S, D).astype(np.float32) for _ in range(3))
        step(q, k, x)
    assert step.phase == "co-execution"
    assert step.stats["kernels_substituted"] == 0
    step.close()


# ==========================================================================
# Pipeline plumbing
# ==========================================================================

def test_optimize_none_is_inert():
    def body(x):
        dead = ops.mul(x, 5.0)
        a = ops.mul(x, 2.0)
        b = ops.mul(x, 2.0)
        return float(ops.reduce_sum(ops.add(a, b)))

    step = function(body, optimize=NONE)
    for x in _xs(5, seed=9):
        step(x)
    assert step.phase == "co-execution"
    for k in ("nodes_eliminated", "cse_hits", "feeds_folded",
              "segments_coalesced", "kernels_substituted"):
        assert step.stats[k] == 0, k
    assert step.engine.gp.opt is None
    assert step.engine.gp.otg is step.engine.gp.tg
    step.close()


def test_resolve_pipeline_validation():
    from repro.core.passes import resolve_pipeline
    assert resolve_pipeline("none") == ()
    assert resolve_pipeline("safe") == ("cse", "dce", "coalesce")
    assert "fold" in resolve_pipeline("all", backend="cpu")
    assert "kernels" not in resolve_pipeline("all", backend="cpu")
    assert "kernels" in resolve_pipeline("all", backend="tpu")
    assert "kernels" in resolve_pipeline("safe", backend="tpu")
    assert "kernels" not in resolve_pipeline("safe", backend="cpu")
    assert resolve_pipeline("none", backend="tpu") == ()
    assert resolve_pipeline(("dce", "cse")) == ("cse", "dce")
    with pytest.raises(ValueError):
        resolve_pipeline("everything")
    with pytest.raises(ValueError):
        resolve_pipeline(("dce", "nope"))


def test_passes_rerun_after_divergence_retrace():
    """A divergence that grows the graph regenerates the program and
    re-runs the pipeline over the new graph (per-family cache keyed on
    version + observation state)."""
    class Cfg:
        k = 1.0
    cfg = Cfg()

    @function(optimize=ALL)
    def step(x):
        dead = ops.reduce_mean(ops.mul(x, 5.0))
        y = ops.mul(ops.mul(x, 2.0), cfg.k)
        return float(ops.reduce_sum(y))

    xs = _xs(4, seed=10)
    for x in xs:
        step(x)
    base = step.stats["nodes_eliminated"]
    assert base >= 1
    cfg.k = 2.0                       # divergence -> retrace -> regen
    for x in xs:
        got = step(x)
        assert got == pytest.approx(float((x * 2.0 * 2.0).sum()), rel=1e-5)
    assert step.phase == "co-execution"
    assert step.stats["nodes_eliminated"] > base    # pipeline ran again
    step.close()
