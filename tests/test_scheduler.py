"""Continuous-batching scheduler tests (serve/scheduler/, DESIGN.md §11):
slot alloc/free across retire-and-admit, mid-decode admission token
correctness, variable-length bucketed prefill, streaming callback
ordering, Terra-vs-baseline equality, the lock-step run_batch
satellite fixes (ragged rejection, latency fields, live-row budget), and
the steady state's donated decode step (DESIGN.md §12)."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.core.persist.aot import segment_specs
from repro.models import model as M
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import (ContinuousBatchingScheduler, SlotPool,
                                   bucket_len)

MAX_LEN = 64


@pytest.fixture(scope="module")
def llama():
    cfg = smoke_config("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def make_requests(cfg, lens, max_news, seed=1, **kw):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                    max_new_tokens=mn, arrival_time=0.0, **kw)
            for L, mn in zip(lens, max_news)]


def lockstep_reference(cfg, params, lens, max_news, seed=1):
    """Per-request lock-step decode: the exact-token oracle."""
    eng = ServingEngine(cfg, params, max_len=MAX_LEN)
    reqs = make_requests(cfg, lens, max_news, seed)
    for r in reqs:
        eng.run_batch([r])
    eng.terra.close()
    return reqs


# ==========================================================================
# SlotPool unit behaviour
# ==========================================================================

def test_slot_pool_alloc_free_across_retire_and_admit():
    pool = SlotPool(3)
    a = pool.alloc("r0", 4)
    b = pool.alloc("r1", 5)
    c = pool.alloc("r2", 6)
    assert (a, b, c) == (0, 1, 2) and pool.free_count == 0
    with pytest.raises(RuntimeError):
        pool.alloc("r3", 1)
    pool.release(b)
    assert pool.free_count == 1 and pool.requests[1] is None
    # retire-and-admit reuses the freed slot, lowest-index-first
    assert pool.alloc("r3", 7) == 1
    with pytest.raises(RuntimeError):            # double free
        pool.release(0)
        pool.release(0)
    assert pool.active_mask().tolist() == [False, True, True]
    pool.advance_active()
    assert pool.pos.tolist() == [4, 8, 7]        # only active rows advance


def test_admission_anchors_on_earliest_arrival():
    """The admission bucket follows arrival order, not submission order —
    a later-submitted-but-earlier-arrived request must not be starved by
    a stream of other-bucket requests."""
    from repro.serve.scheduler import ArrivalQueue
    cfg = smoke_config("llama3-8b")
    q = ArrivalQueue(clock=lambda: 0.0)
    late = Request(prompt=np.zeros(16, np.int32), arrival_time=1.0)
    early = Request(prompt=np.zeros(8, np.int32), arrival_time=0.5)
    q.submit(late)
    q.submit(early)
    bucket, group = q.pop_admission(2.0, free_slots=1, cfg=cfg,
                                    max_len=64, batch_cap=1)
    assert bucket == 8 and group == [early]


def test_callback_queue_raise_preserves_remainder():
    """One raising callback loses only its own delivery; other queued
    callbacks survive the exception and deliver on the next flush."""
    from repro.serve.scheduler import CallbackQueue

    delivered = []

    def boom(req, tok, idx):
        raise RuntimeError("third-party failure")

    r1 = Request(prompt=np.zeros(1, np.int32), stream=boom,
                 out_tokens=[7])
    r2 = Request(prompt=np.zeros(1, np.int32),
                 stream=lambda req, tok, idx: delivered.append(tok),
                 out_tokens=[9])
    q = CallbackQueue()
    q.push(r1, 7)
    q.push(r2, 9)
    with pytest.raises(RuntimeError):
        q.flush()
    q.flush()
    assert delivered == [9] and q.delivered == 1


def test_bucket_len_policy():
    attn = smoke_config("llama3-8b")
    rec = smoke_config("mamba2-130m")
    assert bucket_len(attn, 5, 64) == 8          # pow2 cell (floor 8)
    assert bucket_len(attn, 13, 64) == 16
    assert bucket_len(attn, 60, 64) == 64        # capped at max_len
    assert bucket_len(rec, 13, 64) == 13         # recurrent: exact length


# ==========================================================================
# Scheduler end-to-end: token equality under churn
# ==========================================================================

def test_mid_decode_admission_and_varlen_bucketed_prefill(llama):
    """Six mixed-length requests through three slots: admissions land
    between decode steps of older requests, prompts bucket to 8/16 with
    right padding, and every request's tokens equal its solo lock-step
    decode — old and new requests alike."""
    cfg, params = llama
    lens = [5, 8, 13, 8, 5, 16]
    mns = [4, 9, 3, 5, 7, 4]
    ref = lockstep_reference(cfg, params, lens, mns)

    sch = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                      max_len=MAX_LEN)
    got = make_requests(cfg, lens, mns)
    sch.serve(got)
    for i, (a, b) in enumerate(zip(ref, got)):
        assert a.out_tokens == b.out_tokens, f"request {i}"
    st = sch.stats
    # slot churn is shape-stable: one family, no retraces, no divergence
    assert st["phase"] == "co-execution"
    assert st["retraces"] == 0 and st["replays"] == 0
    assert st["families"] == 1
    assert st["prefill_steps"] >= 2              # mid-decode admissions
    assert st["retired"] == len(lens)
    # latency fields recorded on every request
    for r in got:
        assert r.first_token_time is not None
        assert r.finish_time is not None
        assert r.arrival_time <= r.first_token_time <= r.finish_time
    sch.close()


def test_terra_vs_baseline_token_equality(llama):
    """use_terra=True and use_terra=False run the identical step math."""
    cfg, params = llama
    lens, mns = [8, 5, 13, 8], [6, 8, 4, 3]
    a = make_requests(cfg, lens, mns)
    b = make_requests(cfg, lens, mns)
    s1 = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                     max_len=MAX_LEN)
    s2 = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                     max_len=MAX_LEN, use_terra=False)
    s1.serve(a)
    s2.serve(b)
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    assert s1.stats["phase"] == "co-execution"
    s1.close()
    s2.close()


def test_eos_retirement_frees_slot_for_queued_request(llama):
    """EOS mid-stream retires the request immediately and the freed slot
    admits the next queued request (retire-and-admit through the device
    pool, not just the host free list)."""
    cfg, params = llama
    probe = lockstep_reference(cfg, params, [8], [8])[0]
    eos = probe.out_tokens[2]                    # will hit at index 2

    sch = ContinuousBatchingScheduler(cfg, params, max_slots=1,
                                      max_len=MAX_LEN)
    first = make_requests(cfg, [8], [8])[0]
    first.eos_id = eos
    second = make_requests(cfg, [8], [6], seed=3)[0]
    sch.serve([first, second])
    assert first.out_tokens == probe.out_tokens[:3]
    assert first.done
    ref2 = lockstep_reference(cfg, params, [8], [6], seed=3)[0]
    assert second.out_tokens == ref2.out_tokens
    assert sch.stats["retired"] == 2 and sch.stats["retraces"] == 0
    sch.close()


def test_recurrent_arch_exact_length_admission():
    """Recurrent stacks (no pad-safe cache) admit at exact prompt length
    and still match their lock-step decode."""
    cfg = smoke_config("mamba2-130m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lens, mns = [8, 8, 11], [5, 3, 6]
    ref = lockstep_reference(cfg, params, lens, mns, seed=2)
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                      max_len=MAX_LEN)
    got = make_requests(cfg, lens, mns, seed=2)
    sch.serve(got)
    assert [r.out_tokens for r in ref] == [r.out_tokens for r in got]
    assert sch.stats["families"] == 1
    sch.close()


def test_streaming_callback_ordering(llama):
    """Per-token streaming callbacks: every token delivered exactly once,
    per-request indices strictly sequential, token values matching the
    request's final out_tokens — even though delivery is deferred past
    the next step's dispatch (the overlap window)."""
    cfg, params = llama
    events = []

    def stream(req, tok, idx):
        events.append((id(req), tok, idx))

    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                      max_len=MAX_LEN)
    reqs = make_requests(cfg, [8, 8, 5], [5, 3, 4], stream=stream)
    sch.serve(reqs)
    assert sch.stats["callbacks_delivered"] == \
        sum(len(r.out_tokens) for r in reqs)
    for r in reqs:
        mine = [(tok, idx) for rid, tok, idx in events if rid == id(r)]
        assert [idx for _, idx in mine] == list(range(len(r.out_tokens)))
        assert [tok for tok, _ in mine] == r.out_tokens
    sch.close()


def test_submit_validation(llama):
    cfg, params = llama
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=1,
                                      max_len=32)
    with pytest.raises(ValueError):
        sch.submit(Request(prompt=np.arange(20, dtype=np.int32),
                           max_new_tokens=20))
    with pytest.raises(ValueError):
        sch.submit(Request(prompt=np.zeros(0, np.int32)))
    sch.close()


def test_unsupported_family_raises():
    cfg = smoke_config("whisper-small")          # encoder/cross family
    with pytest.raises(NotImplementedError):
        ContinuousBatchingScheduler(cfg, params=None, max_len=32)


# ==========================================================================
# Lock-step run_batch satellites
# ==========================================================================

def test_run_batch_rejects_ragged_prompts(llama):
    cfg, params = llama
    eng = ServingEngine(cfg, params, max_len=MAX_LEN, use_terra=False)
    reqs = make_requests(cfg, [8, 5], [4, 4])
    with pytest.raises(ValueError, match="same-length"):
        eng.run_batch(reqs)


def test_run_batch_budget_tracks_live_rows_and_records_latency(llama):
    """A short request retiring early must not stretch the decode loop
    past the longest *live* request, pad rows never extend it, and the
    latency fields come back filled."""
    cfg, params = llama
    eng = ServingEngine(cfg, params, max_len=MAX_LEN, use_terra=False,
                        bucket_batches=True)
    reqs = make_requests(cfg, [8, 8, 8], [2, 6, 6])   # pads batch to 4
    eng.run_batch(reqs)
    assert [len(r.out_tokens) for r in reqs] == [2, 6, 6]
    # prefill (1 token) + 5 decode steps serve the longest request; the
    # retired row and the pad row add nothing
    assert eng.stats["decode_steps"] == 5
    assert eng.stats["prefill_tokens"] == 24          # real rows only
    for r in reqs:
        assert r.arrival_time <= r.first_token_time <= r.finish_time
    # finish stamped at the retiring step, not at batch drain: the
    # early-EOS row's latency excludes the steps it merely rode along
    assert reqs[0].finish_time < reqs[1].finish_time


def test_run_batch_streaming_callbacks(llama):
    cfg, params = llama
    got = []
    eng = ServingEngine(cfg, params, max_len=MAX_LEN, use_terra=False)
    reqs = make_requests(cfg, [8, 8], [3, 4],
                         stream=lambda r, t, i: got.append((id(r), t, i)))
    eng.run_batch(reqs)
    for r in reqs:
        mine = [(t, i) for rid, t, i in got if rid == id(r)]
        assert mine == list(zip(r.out_tokens, range(len(r.out_tokens))))


# ==========================================================================
# stable names of the step programs (the profiler's module lines)
# ==========================================================================

def test_step_programs_are_named_after_their_role(llama):
    """The co-executed decode segment and the prefill splice lower to XLA
    modules named for what they do."""
    cfg, params = llama
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                      max_len=MAX_LEN, steady_state=0)
    sch.serve(make_requests(cfg, [5, 7], [4, 3]))
    eng = sch._tf.engine
    assert sch.stats["phase"] == "co-execution"
    (sp,) = eng.gp.seg_progs
    plan = sp.plan
    decode = sp.fn.lower(
        tuple(eng.store.read(v) for v in plan.don_var_ids),
        tuple(eng.store.read(v) for v in plan.keep_var_ids),
        tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
              for _, _, a in plan.feed_keys),
        np.zeros(len(plan.sel_uids), np.int32),
        np.zeros(len(plan.trip_uids), np.int32), ()).as_text()
    state = [eng.variable_value(v)
             for v in sch._cache_vars + [sch._pos_var, sch._tokf_var]]
    prefill = sch._prefill_jit.lower(
        *sch._params_leaves, *state, np.zeros((1, 8), np.int32),
        np.zeros(1, np.int32), np.full(1, 5, np.int32),
        **sch._attrs).as_text()
    sch.close()
    assert "module @jit_serve.slot_decode" in decode
    assert "module @jit_serve.slot_prefill" in prefill


# ==========================================================================
# the steady state donates the pool (DESIGN.md §12)
# ==========================================================================

PROBE = 4                   # a forced walker probe every 4th steady call
LENS, MAX_NEWS = [5, 8, 13, 8, 5, 11], [20, 25, 17, 30, 22, 9]


def _pool_vars(sch):
    return sch._cache_vars + [sch._pos_var, sch._tokf_var]


def _pool_bytes(sch) -> int:
    return sum(int(np.prod(v.aval.shape)) * jnp.dtype(v.aval.dtype).itemsize
               for v in _pool_vars(sch))


def _steady_scheduler(cfg, params, **kw):
    return ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                       max_len=MAX_LEN, steady_probe=PROBE,
                                       **kw)


def test_steady_decode_donates_the_pool_every_step(llama):
    """Once steady, each decode step donates the whole pool: one more
    ``steady_donated_iters`` and the pool's bytes more ``donated_bytes``.
    Walker probes and prefill splices raise neither."""
    cfg, params = llama
    sch = _steady_scheduler(cfg, params)
    for r in make_requests(cfg, LENS, MAX_NEWS):
        sch.submit(r)
    pool, st = _pool_bytes(sch), sch._tf.stats
    steady = probes = 0
    while len(sch.queue) or sch.pool.active_count:
        before = dict(st)
        sch.run(max_steps=1)            # one plan, harvested and drained
        d = {k: st[k] - before[k] for k in
             ("steady_iters", "steady_donated_iters", "donated_bytes",
              "iterations")}
        assert d["steady_donated_iters"] == d["steady_iters"], d
        assert d["donated_bytes"] == d["steady_iters"] * pool, d
        steady += d["steady_iters"]
        if st["steady_entries"] and d["iterations"] > d["steady_iters"]:
            probes += 1                 # a walker decode after entry
    assert steady >= 3 * PROBE and probes >= 2, (steady, probes)
    assert st["steady_exits"] == 0 and st["replays"] == 0
    sch.close()


@pytest.mark.parametrize("arch,dtype", [("granite-3-2b", None),
                                        ("deepseek-v2-lite", "float32")],
                         ids=["dense", "mla-moe"])
def test_donated_steady_decode_serves_the_jitted_tokens(arch, dtype):
    """Greedy tokens of the co-executed scheduler, whose steady decode
    steps donate the pool, equal the hand-jitted scheduler's over a run
    that crosses several probes and prefill splices.  The MoE runs in
    float32: under bfloat16 a near tie between two experts may route
    differently in two compiled forms of the same step."""
    cfg = smoke_config(arch)
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype, param_dtype=dtype)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    a = make_requests(cfg, LENS, MAX_NEWS)
    b = make_requests(cfg, LENS, MAX_NEWS)
    s1 = _steady_scheduler(cfg, params)
    s2 = _steady_scheduler(cfg, params, use_terra=False)
    s1.serve(a)
    s2.serve(b)
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    st = s1.stats
    assert st["steady_donated_iters"] == st["steady_iters"] >= 3 * PROBE
    # walker iterations after the warm-up's 8: the probes
    assert st["iterations"] - st["steady_iters"] >= 8 + 2
    assert st["prefill_steps"] >= 3 and st["replays"] == 0
    s1.close()
    s2.close()


def test_reads_between_donated_steps_stay_readable(llama):
    """``Variable.read()`` and ``variable_value`` between steady steps
    hand out private copies: the next donated step consumes the store's
    buffer, not the reader's array."""
    cfg, params = llama
    sch = _steady_scheduler(cfg, params)
    for r in make_requests(cfg, LENS, MAX_NEWS):
        sch.submit(r)
    sch.run(max_steps=14)
    st, eng = sch._tf.stats, sch._tf.engine
    assert st["steady_donated_iters"] > 0
    checked = 0
    while len(sch.queue) or sch.pool.active_count:
        held = [v.read() for v in sch._cache_vars]
        held.append(eng.variable_value(sch._pos_var))
        saved = [np.asarray(x).copy() for x in held]
        live = [eng.store.buffers[v.var_id] for v in _pool_vars(sch)]
        before = dict(st)
        sch.run(max_steps=1)
        for x, want in zip(held, saved):
            assert not x.is_deleted()
            np.testing.assert_array_equal(np.asarray(x), want)
        if (st["steady_donated_iters"] > before["steady_donated_iters"]
                and st["prefill_steps"] == before["prefill_steps"]):
            # the step read the store's buffers and consumed them in place
            assert all(b.is_deleted() for b in live)
            checked += 1
    assert checked >= 3
    sch.close()


def test_checkpoint_after_donated_steps_serves_the_same_tokens(llama,
                                                               tmp_path):
    """A checkpoint taken after donated steps restores a scheduler that
    finishes every request with the tokens of an uninterrupted run, and
    the donor continues to the same tokens."""
    cfg, params = llama
    ref = make_requests(cfg, LENS, MAX_NEWS)
    s0 = _steady_scheduler(cfg, params)
    s0.serve(ref)
    s0.close()
    want = [r.out_tokens for r in ref]

    reqs = make_requests(cfg, LENS, MAX_NEWS)
    sch = _steady_scheduler(cfg, params)
    for r in reqs:
        sch.submit(r)
    sch.run(max_steps=20)
    assert sch._tf.stats["steady_donated_iters"] > 0
    assert sch.pool.active_count > 0
    path = str(tmp_path / "ckpt")
    sch.checkpoint(path)
    partial = {r.rid: list(r.out_tokens or []) for r in reqs}
    sch.run()
    assert [r.out_tokens for r in reqs] == want
    sch.close()

    rs = ContinuousBatchingScheduler.restore(path, cfg, params)
    tracked = {r.rid: r for _, r in rs.pool.active_items()}
    tracked.update({r.rid: r for r in rs.queue._queue})
    rs.run()
    assert rs._tf.stats["steady_donated_iters"] > 0
    full = dict(partial)
    full.update({rid: r.out_tokens for rid, r in tracked.items()})
    assert [full[r.rid] for r in reqs] == want
    rs.close()


def _aliases(text: str):
    """(output index, parameter) pairs of a compiled module's
    ``input_output_alias``."""
    head = text.splitlines()[0]
    return {(int(o), int(p)) for o, p in
            re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)}


def test_steady_form_aliases_every_pool_leaf(llama):
    """The steady plan's compiled segment donates every pool leaf into the
    output it rewrites; the walker's form of the same segment keeps its
    retained split and aliases nothing."""
    cfg, params = llama
    sch = _steady_scheduler(cfg, params)
    sch.serve(make_requests(cfg, [5, 7], [16, 14]))
    eng = sch._tf.engine
    gp, plan = eng.gp, eng.family.steady
    pool = sorted(v.var_id for v in _pool_vars(sch))
    weights = {v.var_id for v in sch._param_vars}
    steady, walker = plan.sp, gp.seg_progs[0]
    assert list(steady.don_var_ids) == pool == list(steady.var_writes)
    assert set(steady.keep_var_ids) == weights
    assert walker.don_var_ids == []
    assert set(walker.keep_var_ids) == weights | set(pool)
    assert steady.signature != walker.signature
    assert {steady.signature, walker.signature} <= gp.signatures()
    assert plan.donated_bytes == _pool_bytes(sch)

    def compiled(sp):
        return sp.fn.lower(*segment_specs(gp, sp)).compile().as_text()
    text = compiled(steady)
    assert "module jit_serve.slot_decode" in text.splitlines()[0].replace(
        "HloModule", "module")
    # var_out[i] is pool[i], and donated argument i is pool[i]
    assert _aliases(text) == {(i, i) for i in range(len(pool))}
    assert _aliases(compiled(walker)) == set()
    sch.close()
