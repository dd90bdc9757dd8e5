"""Event-stream tests (core/events/, DESIGN.md §13): counter
bit-compatibility with and without structured processors, causal
completeness of divergence → rollback → replay chains, per-request
serving traces (mid-decode admission and early-EOS retirement) with
monotone timestamps, steady-state lifecycle events, the strict JSONL
schema roundtrip, and the span tier: nothing built at a span site while
nobody listens, GC and compile spans from the process-wide sources,
labelled runner closures, and no deadlock when a processor collects
garbage on the runner thread."""

import contextlib
import gc
import json
import threading

import numpy as np
import pytest

import jax

from repro.configs import smoke_config
from repro.core import Variable, function, ops
from repro.core.events import (EventStream, JsonlSink, ListProcessor,
                               Processor, RequestTraceProcessor,
                               dict_to_event, event_to_dict, load_jsonl,
                               types, validate_jsonl)
from repro.core.events.runtime import RUNTIME
from repro.models import model as M
from repro.serve.engine import Request
from repro.serve.scheduler import ContinuousBatchingScheduler

MAX_LEN = 64


@pytest.fixture(scope="module")
def llama():
    cfg = smoke_config("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@contextlib.contextmanager
def no_gc():
    """No automatic collection, so no GC span joins a stream's events."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def make_requests(cfg, lens, max_news, seed=1, **kw):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                    max_new_tokens=mn, arrival_time=0.0, **kw)
            for L, mn in zip(lens, max_news)]


# ==========================================================================
# counter tier: bit-compatible with the pre-event-layer stats dicts
# ==========================================================================

def _counting_run(attach_list):
    v = Variable(np.ones(4, np.float32))

    @function
    def step(x):
        y = ops.mul(x, 2.0)
        v.assign(ops.add(v.read(), y))
        return float(ops.reduce_sum(y))

    lp = ListProcessor()
    if attach_list:
        step.engine.events.attach(lp)
    for i in range(6):
        step(np.full(4, i + 1.0, np.float32))
    step.wait()
    st = dict(step.stats)
    step.close()
    return st, lp


def test_counters_identical_with_and_without_processors():
    """Attaching a structured processor must not change a single counter:
    the counter tier and the event tier are independent by construction."""
    plain, _ = _counting_run(attach_list=False)
    traced, lp = _counting_run(attach_list=True)
    ints = {k for k, x in plain.items() if isinstance(x, (int, np.integer))}
    assert {k: plain[k] for k in ints} == {k: traced[k] for k in ints}
    # and the event tier saw the same lifecycle the counters recorded
    assert len(lp.of_type(types.IterationStart)) == plain["iterations"]
    assert plain["iterations"] == 6


def test_no_events_constructed_when_off():
    """Hot-path discipline: with no structured processor, ``on`` is False
    and emit sites never build an event object."""
    es = EventStream(counters={"n": 0})
    assert es.on is False
    es.inc("n")
    lp = es.attach(ListProcessor())
    assert es.on is True
    with no_gc():
        es.emit(types.Transition(0))
    es.detach(lp)
    assert es.on is False and len(lp.events) == 1
    assert es.counters["n"] == 1


# ==========================================================================
# causal completeness: divergence -> rollback -> replay, one iter_id
# ==========================================================================

def test_divergence_chain_causally_complete():
    """Every Divergence is followed by exactly one Rollback and exactly
    one Replay-or-Retrace carrying the same iteration id, in that order."""
    class Cfg:
        scale = 1.0
    cfg = Cfg()

    @function
    def step(x):
        y = ops.mul(x, 2.0)
        z = ops.mul(y, cfg.scale)      # baked const -> diverges on change
        return float(ops.reduce_sum(z))

    lp = step.engine.events.attach(ListProcessor())
    for i in range(4):                 # trace, cover, enter co-execution
        step(np.full(4, i + 1.0, np.float32))
    cfg.scale = 3.0                    # walker mismatch mid-iteration
    out = step(np.full(4, 9.0, np.float32))
    assert out == pytest.approx(4 * 9.0 * 2.0 * 3.0)
    step.wait()

    divs = lp.of_type(types.Divergence)
    assert len(divs) >= 1
    for d in divs:
        chain = [e for e in lp.of_type(types.Rollback, types.Replay,
                                       types.Retrace)
                 if e.iter_id == d.iter_id]
        rbs = [e for e in chain if isinstance(e, types.Rollback)]
        rps = [e for e in chain if isinstance(e, (types.Replay,
                                                  types.Retrace))]
        assert len(rbs) == 1, f"iter {d.iter_id}: {len(rbs)} rollbacks"
        assert len(rps) == 1, f"iter {d.iter_id}: {len(rps)} replays"
        order = lp.events.index
        assert order(d) < order(rbs[0]) < order(rps[0])
    # the chain is causally attributed: the divergence iteration retraced
    assert step.stats["retraces"] >= len(divs)
    step.close()


# ==========================================================================
# request traces: admit -> prefill -> token* -> retire, monotone ts
# ==========================================================================

def _assert_complete_trace(rec, req):
    kinds = [r["type"] for r in rec]
    assert kinds[0] == "RequestSubmit"
    assert kinds[1] == "RequestAdmit"
    assert kinds[2] == "RequestPrefill"
    assert kinds[-1] == "RequestRetire"
    toks = [r for r in rec if r["type"] == "RequestToken"]
    assert len(toks) == len(req.out_tokens)
    assert [t["token"] for t in toks] == list(req.out_tokens)
    assert [t["index"] for t in toks] == list(range(len(toks)))
    ts = [r["ts"] for r in rec]
    assert all(a <= b for a, b in zip(ts, ts[1:])), "timestamps regress"
    return rec[-1]


def test_request_traces_mid_decode_admission(llama):
    """Oversubscribed workload (6 requests, 3 slots): late requests are
    admitted mid-decode, and every admitted request's trace is complete
    — admit, prefill at its bucket, one token event per generated token,
    retire — with monotone timestamps."""
    cfg, params = llama
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                      max_len=MAX_LEN)
    tracer = sch.events.attach(RequestTraceProcessor())
    lens = [5, 8, 13, 8, 5, 16]
    mns = [4, 9, 3, 5, 7, 4]
    reqs = sch.serve(make_requests(cfg, lens, mns))
    assert sch.stats["retired"] == len(reqs)
    assert len(tracer.traces) == len(reqs)
    for req in reqs:
        retire = _assert_complete_trace(tracer.trace(req.rid), req)
        assert retire["reason"] == "budget"
        assert retire["tokens"] == len(req.out_tokens)
    sch.close()


def test_request_trace_eos_retirement(llama):
    """A request that hits EOS mid-budget retires with reason 'eos' and a
    trace that ends at the EOS token (no post-retirement token events)."""
    cfg, params = llama
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                      max_len=MAX_LEN)
    [probe] = sch.serve(make_requests(cfg, [6], [8]))
    # greedy decode is deterministic: the first token value NOT already
    # generated earlier marks a mid-budget EOS point when replayed
    idx, eos = next((i, t) for i, t in enumerate(probe.out_tokens)
                    if i > 0 and t not in probe.out_tokens[:i])
    tracer = sch.events.attach(RequestTraceProcessor())
    [req] = sch.serve(make_requests(cfg, [6], [8], eos_id=eos))
    assert req.done and len(req.out_tokens) == idx + 1
    retire = _assert_complete_trace(tracer.trace(req.rid), req)
    assert retire["reason"] == "eos" and retire["tokens"] == idx + 1
    sch.close()


# ==========================================================================
# steady-state lifecycle events
# ==========================================================================

def test_steady_state_events():
    """Zero-walker steady state announces itself: SteadyEnter on entry,
    'steady'-kind SegmentDispatch per plan dispatch, SteadyProbe on the
    forced walker iterations."""
    v = Variable(np.zeros(4, np.float32))

    @function(optimize="safe", steady_state=2, steady_probe=4)
    def step(x):
        y = ops.mul(x, 2.0)
        v.assign(ops.add(v.read(), y))
        return y

    lp = step.engine.events.attach(ListProcessor())
    for i in range(16):
        # materialized output: steady eligibility needs the fetch pattern
        np.asarray(step(np.full(4, float(i + 1), np.float32)))
    step.wait()
    assert step.stats["steady_iters"] > 0
    assert len(lp.of_type(types.SteadyEnter)) == step.stats["steady_entries"]
    steady_dispatch = [e for e in lp.of_type(types.SegmentDispatch)
                       if e.kind == "steady"]
    assert len(steady_dispatch) == step.stats["steady_iters"]
    # every steady_probe-th call is forced through the full walker path
    assert len(lp.of_type(types.SteadyProbe)) >= 1
    step.close()


# ==========================================================================
# strict JSONL schema
# ==========================================================================

def test_event_dict_roundtrip():
    for e in (types.IterationStart(3, "skeleton", "a1b2c3d4"),
              types.Divergence(7, "const mismatch"),
              types.RequestToken(2, 991, 0),
              types.PassPipelineRun(4, "f" * 8, ("cse", "dce"),
                                    {"cse": {"cse_hits": 2}})):
        d = json.loads(json.dumps(event_to_dict(e)))
        e2 = dict_to_event(d)
        assert type(e2) is type(e)
        assert event_to_dict(e2) == event_to_dict(e)


def test_schema_rejects_malformed():
    with pytest.raises(ValueError, match="unknown event type"):
        dict_to_event({"type": "NoSuchEvent"})
    with pytest.raises(ValueError):                      # extra field
        dict_to_event({"type": "Transition", "iter_id": 1, "bogus": 2})
    with pytest.raises(ValueError):                      # missing field
        dict_to_event({"type": "RequestToken", "rid": 1})


def test_jsonl_sink_and_validation(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    es = EventStream()
    sink = es.attach(JsonlSink(path))
    with no_gc():
        es.emit(types.IterationStart(0, "tracing", "00000000"))
        es.emit(types.RequestSubmit(1, 8, 4))
        es.emit(types.RequestRetire(1, "eos", 3))
    es.close()                          # close flushes the sink
    events = load_jsonl(path)
    assert [type(e).__name__ for e in events] == \
        ["IterationStart", "RequestSubmit", "RequestRetire"]
    assert events[0].ts is not None
    counts = validate_jsonl(path)
    assert counts == {"IterationStart": 1, "RequestSubmit": 1,
                      "RequestRetire": 1}
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "NoSuchEvent"}\n')
    with pytest.raises(ValueError, match="bad.jsonl:1"):
        validate_jsonl(str(bad))


# ==========================================================================
# the span tier (DESIGN.md §13)
# ==========================================================================

def test_span_sites_build_nothing_when_off(llama, monkeypatch):
    """No processor and no profiler session: no span site builds an event
    object or a TraceAnnotation — through warm-up, co-execution, steady
    state, prefill splices, harvests, idle waits and a GC pause."""
    cfg, params = llama
    built = []

    def refuse(name):
        def ctor(*a, **k):
            built.append(name)
            raise AssertionError(f"{name} built with nobody listening")
        return ctor
    for name in types.EVENT_TYPES:
        monkeypatch.setattr(types, name, refuse(name))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        refuse("TraceAnnotation"))
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=2,
                                      max_len=MAX_LEN, steady_state=2)
    reqs = make_requests(cfg, [5, 8, 6], [6, 4, 5])
    reqs[0].stream = lambda *a: gc.collect()
    sch.serve(reqs)
    st = sch.stats
    sch.close()
    assert not built
    assert st["steady_iters"] > 0 and st["prefill_steps"] > 0
    assert st["gc_pauses"] >= 6


def test_gc_collect_gives_one_pause_span_and_counters():
    es = EventStream()
    lp = es.attach(ListProcessor())
    with no_gc():
        c0 = es.runtime_counters()
        gc.collect()
        es.emit(types.Transition(0))
        c1 = es.runtime_counters()
    spans = lp.of_type(types.Span)
    assert [s.name for s in spans] == ["terra.gc.gen2"]
    assert spans[0].dur > 0.0 and spans[0].ts <= lp.events[-1].ts
    assert c1["gc_pauses"] == c0["gc_pauses"] + 1
    assert c1["gc_pause_s"] > c0["gc_pause_s"]
    assert sum(cb == RUNTIME._on_gc for cb in gc.callbacks) == 1


def test_fresh_jit_gives_compile_spans_and_counts():
    es = EventStream()
    lp = es.attach(ListProcessor())
    c0 = es.runtime_counters()
    f = jax.jit(lambda x: x * 3.0 - 1.0)
    np.asarray(f(np.arange(7, dtype=np.float32)))
    es.emit(types.Transition(0))
    c1 = es.runtime_counters()
    names = {s.name for s in lp.of_type(types.Span)}
    assert {"terra.compile.trace", "terra.compile.lower",
            "terra.compile.backend"} <= names
    assert c1["compiles"] >= c0["compiles"] + 1
    assert c1["compile_s"] > c0["compile_s"]


def _steady_step():
    v = Variable(np.zeros(4, np.float32))

    @function(optimize="safe", steady_state=2, steady_probe=4)
    def step(x):
        y = ops.mul(x, 2.0)
        v.assign(ops.add(v.read(), y))
        return y
    return step


def test_runner_closures_carry_their_label():
    step = _steady_step()
    lp = step.engine.events.attach(ListProcessor())
    n0 = RUNTIME.runner.n
    for i in range(12):
        np.asarray(step(np.full(4, float(i + 1), np.float32)))
    step.wait()
    done = lp.of_type(types.RunnerComplete)
    assert {"steady", "segment", "snapshot"} <= {e.label for e in done}
    assert all(e.queued >= 0.0 and e.wall >= 0.0 for e in done)
    # every closure also lands in the process's record, with its label
    held, _ = RUNTIME.runner.since(n0)
    assert len(held) >= len(done)
    assert {"steady", "segment", "snapshot"} <= {lab for _, _, lab in held}
    step.close()


def test_processor_collecting_garbage_on_the_runner_thread():
    """A GC on the runner thread inside ``emit`` (the stream's lock held)
    must not deadlock: the GC source takes no lock and calls no
    processor; its span arrives with a later event."""
    class Collecting(Processor):
        def __init__(self):
            self.seen = []

        def process(self, event):
            self.seen.append(event)
            if isinstance(event, types.RunnerComplete):
                gc.collect()

    step = _steady_step()
    proc = step.engine.events.attach(Collecting())

    def drive():
        for i in range(10):
            np.asarray(step(np.full(4, float(i + 1), np.float32)))
        step.wait()

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "deadlock: runner-thread GC inside emit"
    step.close()
    assert any(isinstance(e, types.Span) and e.name == "terra.gc.gen2"
               for e in proc.seen)
