"""Hot-path behaviour tests (ISSUE 2): precomputed dispatch plans,
per-value synchronization, the Walker stamp fast path, runner error
containment, and the feeds_defaulted / runner-time stat exports."""

import threading

import numpy as np
import pytest

from repro.core import Variable, function, ops


# ==========================================================================
# per-value synchronization
# ==========================================================================

def test_early_fetch_does_not_block_on_trailing_segments():
    """Reading a variable written by an early segment must not wait for a
    trailing segment of the same iteration: the GraphRunner queue is gated
    behind an Event after the early segment, and the read must return while
    the trailing writer is still pending."""
    a = Variable(np.ones(8, np.float32), "pv_a")
    b = Variable(np.ones(8, np.float32), "pv_b")
    gate = threading.Event()
    hook = [None]

    @function
    def step(x):
        a.assign(ops.mul(x, 2.0))
        s = float(ops.reduce_sum(a.read()))    # gating fetch -> boundary
        if hook[0]:
            hook[0]()                          # wedge the runner queue
        b.assign(ops.mul(x, 5.0))              # trailing segment writes b
        return s

    for i in range(3):
        step(np.full(8, float(i + 1), np.float32))
    eng = step.engine
    assert step.phase == "co-execution"

    # watchdog: a regression that reintroduces a full drain would deadlock
    # on the gate — release it after 20s so the test fails instead of hangs
    watchdog = threading.Timer(20.0, gate.set)
    watchdog.start()
    try:
        hook[0] = lambda: eng.runner.submit(gate.wait, "test")
        x = np.full(8, 7.0, np.float32)
        s = step(x)
        assert s == pytest.approx(8 * 14.0)
        # reading a blocks only on a's writer (already done), never on the
        # whole queue: b's writer must still be pending when this returns
        val = np.asarray(eng.variable_value(a))
        fence_b = eng.store.write_fence(b.var_id)
        assert fence_b is not None and not eng.runner.done(fence_b), \
            "trailing segment already ran — variable_value drained the queue"
        assert not gate.is_set(), "watchdog fired: variable_value blocked"
        np.testing.assert_allclose(val, np.full(8, 14.0))
    finally:
        gate.set()
        watchdog.cancel()
    step.wait()
    np.testing.assert_allclose(np.asarray(eng.variable_value(b)),
                               np.full(8, 35.0))
    step.close()


def test_variable_value_mid_iteration_under_donation():
    """A mid-iteration variable read of a donatable buffer returns a
    private copy of the intermediate value, and the copy survives the later
    segment donating the buffer."""
    w = Variable(np.ones(64, np.float32), "don_w")
    probe = [False]
    seen = []

    @function
    def step(x):
        w.assign(ops.mul(w.read(), 2.0))
        s = float(ops.reduce_sum(w.read()))    # boundary between the writes
        if probe[0]:
            seen.append(np.asarray(step.engine.variable_value(w)).copy())
        w.assign(ops.mul(x, 3.0))              # donates the intermediate
        return s

    for i in range(4):
        step(np.full(64, float(i + 1), np.float32))
    assert step.phase == "co-execution"
    assert step.engine.gp.donatable_var_ids == {w.var_id}

    probe[0] = True
    for i in range(4, 7):
        x = np.full(64, float(i + 1), np.float32)
        step(x)
        # mid-iteration value: committed w (= 3*x_prev) doubled by seg 0
        np.testing.assert_allclose(seen[-1], np.full(64, 3.0 * i * 2.0))
    step.wait()
    assert step.stats["donated_bytes"] > 0
    # the private copies were not clobbered by the donation
    for j, i in enumerate(range(4, 7)):
        np.testing.assert_allclose(seen[j], np.full(64, 6.0 * i))
    step.close()


def test_variable_value_after_divergence_rollback():
    """After divergence cancellation the store is rolled back and finished
    imperatively; variable_value (mid-iteration and after) must reflect the
    imperative values, not the cancelled symbolic ones."""
    class Cfg:
        k = 1.0
    cfg = Cfg()
    w = Variable(np.full(16, 2.0, np.float32), "rb_w")
    probe = [False]
    seen = []

    @function
    def step(x):
        w.assign(ops.mul(w.read(), 2.0))
        s = float(ops.reduce_sum(w.read()))
        w.assign(ops.mul(x, cfg.k))            # baked const: diverges on k
        if probe[0]:
            seen.append(np.asarray(step.engine.variable_value(w)).copy())
        return s

    for i in range(3):
        step(np.full(16, float(i + 1), np.float32))
    assert step.phase == "co-execution"

    probe[0] = True
    cfg.k = 4.0
    x = np.full(16, 9.0, np.float32)
    step(x)
    assert step.stats["replays"] == 1
    # post-divergence the iteration finished imperatively: the mid-iteration
    # read and the committed value both see the eager x*k binding
    np.testing.assert_allclose(seen[-1], x * 4.0)
    np.testing.assert_allclose(np.asarray(step.engine.variable_value(w)),
                               x * 4.0)
    step.close()


# ==========================================================================
# dispatch plans + feeds_defaulted
# ==========================================================================

def test_dispatch_plans_are_precomputed():
    """Every compiled segment carries a DispatchPlan whose tuples mirror
    the segment IO analysis and the global selector/trip slot orders."""
    w = Variable(np.ones(4, np.float32), "plan_w")

    @function
    def step(x):
        y = ops.mul(w.read(), x)
        s = float(ops.reduce_sum(y))           # boundary -> two segments
        w.assign(ops.add(w.read(), 1.0))
        return s

    for i in range(3):
        step(np.full(4, 1.0, np.float32))
    gp = step.engine.gp
    assert gp is not None and len(gp.seg_progs) >= 2
    for sp in gp.seg_progs:
        plan = sp.plan
        assert plan is not None
        assert plan.don_var_ids == tuple(sp.don_var_ids)
        assert plan.keep_var_ids == tuple(sp.keep_var_ids)
        assert plan.var_writes == tuple(sp.var_writes)
        assert plan.feed_keys == tuple(sp.feed_keys)
        assert plan.fetch_keys == tuple(sp.fetch_keys)
        # slot orders: position in the tuple == globally assigned slot
        assert [gp.selector_slot[u] for u in plan.sel_uids] == \
            list(range(len(plan.sel_uids)))
        assert [gp.trip_slot[u] for u in plan.trip_uids] == \
            list(range(len(plan.trip_uids)))
    step.close()


def test_feeds_defaulted_stays_zero_on_covered_linear_program():
    """A linear covered program must never silently substitute zeros for a
    missing Input Feeding value (the defaulting path is only legitimate for
    feed slots inside untaken branch regions)."""
    w = Variable(np.ones(8, np.float32), "fd_w")

    @function
    def step(x, y):
        h = ops.add(ops.mul(w.read(), x), y)   # x, y are Input Feeding
        s = float(ops.reduce_sum(h))
        w.assign(ops.mul(w.read(), 0.5))
        return s

    for i in range(6):
        step(np.full(8, float(i + 1), np.float32),
             np.full(8, 0.5, np.float32))
    assert step.phase == "co-execution"
    assert step.stats["feeds_defaulted"] == 0
    step.close()


def test_feeds_defaulted_counts_untaken_branch_slots():
    """Feed slots inside the branch NOT taken this iteration are filled
    with zeros when the enclosing switch region dispatches — that is the
    one legitimate defaulting case, and it is counted."""
    w = Variable(np.ones(4, np.float32), "br_w")

    @function
    def step(x, big):
        s = float(ops.reduce_sum(ops.mul(x, 2.0)))   # boundary -> seg 0
        if s > 10.0:
            z = ops.add(ops.mul(x, 3.0), big)        # feed only on this path
        else:
            z = ops.mul(x, 1.5)
        w.assign(z)                                  # phi output of the switch
        return s

    big = np.full(4, 100.0, np.float32)
    vals = [0.5, 3.0, 0.5, 3.0, 0.5, 3.0]
    for v in vals:
        step(np.full(4, v, np.float32), big)
    assert step.phase == "co-execution"
    base = step.stats["feeds_defaulted"]
    step(np.full(4, 0.5, np.float32), big)   # small branch: big not collected
    step.wait()
    assert step.stats["feeds_defaulted"] > base
    np.testing.assert_allclose(np.asarray(step.engine.variable_value(w)),
                               np.full(4, 0.75))
    step.close()


# ==========================================================================
# Walker fast path + stat exports
# ==========================================================================

def test_walker_fast_path_validates_steady_state():
    @function
    def step(x):
        return ops.reduce_sum(ops.add(ops.mul(x, 2.0), 1.0))

    for i in range(6):
        step(np.full(4, float(i + 1), np.float32))
    assert step.phase == "co-execution"
    # steady-state iterations validate every op via the stamp comparison
    assert step.stats["walker_fast_hits"] >= 6
    step.close()


def test_fast_path_falls_back_structurally_not_to_divergence():
    """Clearing every node stamp disables the fast path; validation must
    still succeed through the full structural comparison (a stamp mismatch
    is never treated as divergence)."""
    @function
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 3.0)))

    for i in range(4):
        step(np.full(4, float(i + 1), np.float32))
    assert step.phase == "co-execution"
    eng = step.engine
    for n in eng.tg.nodes.values():
        n.entry_stamp = None                   # kill all stamps
    base_replays = step.stats["replays"]
    out = step(np.full(4, 5.0, np.float32))
    assert out == pytest.approx(4 * 15.0)
    assert step.stats["replays"] == base_replays    # no divergence
    assert step.phase == "co-execution"
    step.close()


def test_runner_time_stats_exported():
    @function
    def step(x):
        w = ops.mul(x, 2.0)
        return ops.reduce_sum(w)

    for i in range(5):
        step(np.full(4, 1.0, np.float32))
    step.wait()                                # sync mirrors runner times
    assert step.stats["runner_exec_time"] == pytest.approx(
        step.engine.runner.exec_time)
    assert step.stats["runner_stall_time"] == pytest.approx(
        step.engine.runner.stall_time)
    assert step.stats["runner_exec_time"] > 0.0
    step.close()


def test_runner_survives_closure_exception():
    """A raising closure must not kill the runner thread (a dead worker
    would hang every later fence wait): its sequence still completes,
    sync() re-raises the stashed error once, and the engine keeps working."""
    @function
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 2.0)))

    for i in range(4):
        step(np.full(4, 1.0, np.float32))
    eng = step.engine

    def boom():
        raise RuntimeError("boom")

    seq = eng.runner.submit(boom, "test")
    eng.runner.wait_for(seq)                   # fence releases despite raise
    with pytest.raises(RuntimeError, match="boom"):
        step.wait()                            # sync surfaces the error once
    out = step(np.full(4, 3.0, np.float32))    # worker thread still alive
    assert out == pytest.approx(4 * 6.0)
    step.wait()
    step.close()


def test_lazy_mode_per_value_fences():
    """Lazy mode (no runner thread) must still resolve per-value fences by
    executing queued work on the calling thread."""
    w = Variable(np.ones(4, np.float32), "lz_w")

    @function(lazy=True)
    def step(x):
        w.assign(ops.mul(w.read(), x))
        return ops.reduce_sum(w.read())

    for i in range(4):
        step(np.full(4, 2.0, np.float32))
    val = np.asarray(step.engine.variable_value(w))
    np.testing.assert_allclose(val, np.full(4, 2.0 ** 4))
    step.close()
