"""Paged KV cache + zero-walker steady state (ISSUE 7).

Covers the paged arena data layer (PagedLayout / BlockAllocator /
SlotPool block tables), admission backpressure through the block-budget
checker, paged-vs-dense exact greedy equality under the serving
scheduler, high-concurrency admission beyond dense-equivalent capacity,
the Pallas paged-attention decode kernel against its dense oracle, and
the zero-walker steady-state dispatch path (executor/steady.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import Variable, function, ops
from repro.models import model as M
from repro.serve.engine import Request
from repro.serve.scheduler import (ArrivalQueue, BlockAllocator,
                                   ContinuousBatchingScheduler, PagedLayout,
                                   SlotPool)


@pytest.fixture(scope="module")
def llama():
    cfg = smoke_config("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def make_requests(cfg, lens, max_news, seed=1, **kw):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                    max_new_tokens=mn, arrival_time=0.0, **kw)
            for L, mn in zip(lens, max_news)]


# ==========================================================================
# layout + allocator
# ==========================================================================

def test_paged_layout_geometry_and_validation():
    lay = PagedLayout(block_size=16, num_blocks=9, max_len=64)
    assert lay.nbps == 4
    # prompt + budget + 1 post-EOS garbage position, ceil to blocks
    assert lay.blocks_needed(1, 0) == 1
    assert lay.blocks_needed(15, 0) == 1
    assert lay.blocks_needed(15, 1) == 2
    assert lay.blocks_needed(8, 23) == 2
    with pytest.raises(ValueError):
        PagedLayout(block_size=10, num_blocks=4, max_len=64)   # not divisor
    with pytest.raises(ValueError):
        PagedLayout(block_size=16, num_blocks=1, max_len=64)   # no trash


def test_block_allocator_lifecycle_and_guards():
    al = BlockAllocator(6)                  # capacity 5: blocks 1..5
    assert al.capacity == 5 and al.free_count == 5
    a = al.alloc(2)
    b = al.alloc(3)
    assert a == [1, 2] and b == [3, 4, 5] and al.free_count == 0
    assert al.alloc(1) is None              # all-or-nothing: no partials
    al.free(a)
    # fragmentation after early retirement: freed ids are reused lowest-
    # first, so a later alloc lands back in the gap deterministically
    assert al.alloc(2) == [1, 2]
    al.free(b)
    with pytest.raises(RuntimeError):
        al.free([3])                        # double free
    with pytest.raises(ValueError):
        al.free([0])                        # the trash block never moves


def test_slotpool_block_table_churn():
    lay = PagedLayout(block_size=8, num_blocks=9, max_len=32)   # cap 8
    pool = SlotPool(3, lay)
    r0 = Request(prompt=np.arange(7, dtype=np.int32), max_new_tokens=8)
    r1 = Request(prompt=np.arange(7, dtype=np.int32), max_new_tokens=8)
    s0 = pool.alloc(r0, 7)                  # needs ceil(16/8) = 2 blocks
    s1 = pool.alloc(r1, 7)
    assert list(pool.block_table[s0][:2]) == [1, 2]
    assert list(pool.block_table[s1][:2]) == [3, 4]
    assert pool.block_table[s0][2:].tolist() == [0, 0]   # tail -> trash
    assert pool.resident_tokens == 32
    pool.release(s0)
    # the released row is zeroed so an in-flight decode for the retired
    # slot scatters into the trash block, never another request's block
    assert pool.block_table[s0].tolist() == [0, 0, 0, 0]
    assert pool.resident_tokens == 16
    r2 = Request(prompt=np.arange(20, dtype=np.int32), max_new_tokens=3)
    s2 = pool.alloc(r2, 20)                 # ceil(24/8) = 3: reuse + fresh
    assert list(pool.block_table[s2][:3]) == [1, 2, 5]
    assert pool.peak_resident_tokens == 40
    # 3 blocks free (s1 holds 2, s2 holds 3): a 4-block head is refused
    big = Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=21)
    fits = pool.admit_checker()
    assert fits(big) is False
    small = Request(prompt=np.arange(3, dtype=np.int32), max_new_tokens=3)
    assert fits(small) is True              # 1 block: fits the remainder


def test_admission_backpressure_queues_not_crashes():
    cfg = smoke_config("llama3-8b")
    q = ArrivalQueue(clock=lambda: 0.0)
    head = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=8,
                   arrival_time=0.0)
    tail = Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=8,
                   arrival_time=1.0)
    q.submit(head), q.submit(tail)
    # head-of-line does not fit -> no admission at all (FIFO preserved)
    got = q.pop_admission(2.0, 2, cfg, 64, 2, fits=lambda r: False)
    assert got is None and len(q) == 2
    # head fits, tail does not -> tail is skipped but stays queued
    seen = []
    got = q.pop_admission(2.0, 2, cfg, 64, 2,
                          fits=lambda r: seen.append(r) or len(seen) == 1)
    assert got is not None and got[1] == [head]
    assert len(q) == 1


# ==========================================================================
# scheduler: paged vs dense
# ==========================================================================

def test_paged_equals_dense_greedy(llama):
    """Exact token equality between the paged and dense pools over a
    churn-heavy mix (admissions between decodes, early retirements)."""
    cfg, params = llama
    lens = [5, 8, 13, 8, 5, 16]
    mns = [4, 9, 3, 5, 7, 4]
    dense = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                        max_len=64)
    a = make_requests(cfg, lens, mns)
    dense.serve(a)
    paged = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                        max_len=64, page_size=16)
    b = make_requests(cfg, lens, mns)
    paged.serve(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.out_tokens == y.out_tokens, f"request {i}"
    st = paged.stats
    assert st["phase"] == "co-execution"
    assert st["retraces"] == 0 and st["replays"] == 0
    assert st["families"] == 1
    assert st["peak_resident_tokens"] > 0


def test_paged_high_concurrency_beyond_dense_capacity(llama):
    """With blocks sized to HALF the dense arena (8 slots x 64 tokens),
    the paged pool still runs 16 requests concurrently — admission is
    bounded by tokens resident, not by worst-case rows."""
    cfg, params = llama
    n, L, mn = 16, 8, 8                     # 2 blocks each at bs=16
    paged = ContinuousBatchingScheduler(
        cfg, params, max_slots=n, max_len=64, page_size=16,
        num_blocks=33)                      # capacity 32 blocks = 512 tok
    peaks = []
    reqs = make_requests(
        cfg, [L] * n, [mn] * n,
        stream=lambda r, t, i: peaks.append(paged.pool.active_count))
    paged.serve(reqs)
    assert all(len(r.out_tokens) == mn for r in reqs)
    st = paged.stats
    assert st["retired"] == n and st["retraces"] == 0
    dense_equiv_slots = (33 - 1) * 16 // 64     # same memory, dense rows
    assert max(peaks) > dense_equiv_slots       # ran past dense capacity
    assert st["peak_resident_tokens"] <= 512


def test_paged_arena_exhaustion_backpressure(llama):
    """A tiny arena (2 concurrent requests max) forces the rest of the
    queue to wait for retirements; everything completes with tokens
    identical to an uncontended paged run."""
    cfg, params = llama
    lens, mns = [8, 8, 8, 8, 8], [6, 6, 6, 6, 6]
    wide = ContinuousBatchingScheduler(cfg, params, max_slots=5,
                                       max_len=32, page_size=8)
    a = make_requests(cfg, lens, mns)
    wide.serve(a)
    tight = ContinuousBatchingScheduler(
        cfg, params, max_slots=5, max_len=32, page_size=8,
        num_blocks=4)                       # capacity 3: one 2-block req
    b = make_requests(cfg, lens, mns)
    tight.serve(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.out_tokens == y.out_tokens, f"request {i}"
    assert tight.stats["retired"] == len(lens)
    # a request that can never fit is rejected up front, not deadlocked
    with pytest.raises(ValueError):
        tight.submit(Request(prompt=np.arange(9, dtype=np.int32),
                             max_new_tokens=15))


# ==========================================================================
# Pallas paged-attention kernel
# ==========================================================================

def test_paged_attention_kernel_matches_oracle():
    from repro.kernels import ops as kops
    rng = np.random.RandomState(0)
    B, Hq, Hkv, D, bs, nbps, nblocks = 3, 4, 2, 16, 8, 4, 9
    q = jnp.asarray(rng.randn(B, 1, Hq, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(nblocks, bs, Hkv, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(nblocks, bs, Hkv, D).astype(np.float32))
    bt = np.zeros((B, nbps), np.int32)
    bt[:, :2] = rng.permutation(np.arange(1, nblocks))[:B * 2].reshape(B, 2)
    bt = jnp.asarray(bt)
    valid = jnp.asarray(np.array([5, 9, 16], np.int32))
    out = kops.paged_attention(q, kp, vp, bt, valid)
    ref = kops.paged_attention(q, kp, vp, bt, valid, use_ref=True)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    outw = kops.paged_attention(q, kp, vp, bt, valid, window=6)
    refw = kops.paged_attention(q, kp, vp, bt, valid, window=6, use_ref=True)
    np.testing.assert_allclose(outw, refw, rtol=2e-5, atol=2e-5)


def test_paged_kernel_substitution_in_scheduler(llama):
    """With the ``kernels`` pass named explicitly, the pass pipeline
    rewrites the paged ``serve.slot_decode`` node to the Pallas kernel op
    (interpret-mode off-TPU) and tokens stay identical to the dense run."""
    cfg, params = llama
    lens, mns = [5, 9], [4, 3]
    dense = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                        max_len=32)
    a = make_requests(cfg, lens, mns)
    dense.serve(a)
    paged = ContinuousBatchingScheduler(
        cfg, params, max_slots=2, max_len=32, page_size=8,
        optimize=("cse", "kernels", "dce", "coalesce"))
    b = make_requests(cfg, lens, mns)
    paged.serve(b)
    assert paged.stats["kernels_substituted"] >= 1
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.out_tokens == y.out_tokens, f"request {i}"


@pytest.mark.parametrize("max_len, kernel_leaves", [(128, 2), (64, 0)])
def test_dense_append_kernel_substitution_in_scheduler(max_len, kernel_leaves):
    """With the ``kernels`` pass named, a dense pool's decode node is
    retargeted too, and its appended K/V rows go through the Pallas
    ``kv_append`` kernel (interpret-mode off-TPU): both cache leaves of a
    granite-like pool whose 128 positions fill one tile; tokens identical
    to the loop's run, where no leaf takes the kernel.  A pool of 64
    positions, less than a tile, has no leaf the kernel writes: its
    decode node keeps its op and counts no substitution."""
    cfg = smoke_config("granite-3-2b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lens, mns = [5, 9], [4, 3]
    served = {}
    for optimize in (("cse", "kernels", "dce", "coalesce"), "none"):
        sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                          max_len=max_len, optimize=optimize)
        reqs = make_requests(cfg, lens, mns)
        sch.serve(reqs)
        st = sch.stats
        served[optimize == "none"] = (
            [r.out_tokens for r in reqs], st["kv_append_leaves"],
            st["kv_append_kernel_leaves"], st["kernels_substituted"])
        sch.close()
    kern, loop = served[False], served[True]
    assert kern[1:3] == (2, kernel_leaves), kern[1:]
    assert (kern[3] >= 1) == (kernel_leaves > 0), kern[1:]
    assert loop[1:] == (2, 0, 0), loop[1:]
    assert kern[0] == loop[0]


# ==========================================================================
# zero-walker steady state
# ==========================================================================

def test_steady_state_entry_and_exact_values():
    v = Variable(np.zeros(4, np.float32), "steady_v")

    @function(optimize="safe", steady_state=3, steady_probe=5)
    def step(x):
        y = ops.mul(x, 2.0)
        v.assign(ops.add(v.read(), y))
        return y

    outs = []
    for i in range(20):
        outs.append(np.asarray(step(np.full(4, float(i + 1), np.float32))))
    st = step.stats
    assert st["steady_entries"] == 1 and st["steady_exits"] == 0
    assert st["steady_iters"] > 0
    # every steady_probe-th call revalidates through the full walker path
    assert st["steady_iters"] < st["iterations"]
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o, np.full(4, 2.0 * (i + 1)))
    total = sum(2.0 * (i + 1) for i in range(20))
    np.testing.assert_allclose(
        np.asarray(step.engine.variable_value(v)), np.full(4, total))
    step.close()


def test_steady_state_exit_on_control_flow_change():
    """A Python-value-driven branch change misses the steady plan's baked
    constant, runs the walker, diverges, and drops the plan — slower
    never wrong: the new branch's value is exact."""
    v = Variable(np.zeros(4, np.float32), "steady_w")

    @function(optimize="safe", steady_state=3, steady_probe=100)
    def step(x, flag):
        y = ops.mul(x, 2.0) if flag else ops.add(x, 10.0)
        v.assign(ops.add(v.read(), y))
        return y

    one = np.full(4, 1.0, np.float32)
    for _ in range(8):
        np.testing.assert_allclose(np.asarray(step(one, 1)), np.full(4, 2.0))
    st = step.stats
    assert st["steady_entries"] == 1 and st["steady_iters"] > 0
    np.testing.assert_allclose(np.asarray(step(one, 0)), np.full(4, 11.0))
    st = step.stats
    assert st["steady_exits"] >= 1          # plan dropped, not reused
    np.testing.assert_allclose(
        np.asarray(step.engine.variable_value(v)), np.full(4, 8 * 2.0 + 11.0))
    step.close()


def test_steady_state_python_observation_poisons_entry():
    """An iteration whose skeleton reads device state through Python
    (variable_value) is never counted toward the steady streak — Python
    visibility means the fn cannot be skipped."""
    v = Variable(np.zeros(2, np.float32), "steady_p")

    @function(optimize="safe", steady_state=2, steady_probe=100)
    def step(x):
        v.assign(ops.add(v.read(), x))
        float(np.asarray(step.engine.variable_value(v))[0])  # Python sees
        return ops.mul(x, 1.0)

    for i in range(8):
        step(np.full(2, 1.0, np.float32))
    st = step.stats
    assert st["steady_entries"] == 0 and st["steady_iters"] == 0
    step.close()
