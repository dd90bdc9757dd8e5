"""Observability-layer tests (repro.obs, DESIGN.md §15): timeline trace
validity (JSON, per-track monotone timestamps, request flow completeness)
for a mid-decode-admission serving run under a profiler session,
streaming-histogram accuracy against exact rank statistics, Prometheus
exposition wellformedness, token equality + steady entry with the
program's spans on, and fork-observation distributions."""

import gc
import glob
import json
import math
import os
import re

import numpy as np
import pytest

import jax

from repro.configs import smoke_config
from repro.core import function, ops
from repro.core.events import JsonlSink, load_jsonl, validate_jsonl
from repro.core.events import types as T
from repro.core.events.processors import ListProcessor
from repro.models import model as M
from repro.obs import (GROWTH, Histogram, MetricsProcessor, MetricsRegistry,
                       TraceViewerExporter, chrome_trace, counters_table)
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, SlotPool

MAX_LEN = 64


@pytest.fixture(scope="module")
def llama():
    cfg = smoke_config("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def make_requests(cfg, lens, max_news, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                    max_new_tokens=mn, arrival_time=0.0)
            for L, mn in zip(lens, max_news)]


def profiler_span_names(trace_dir) -> set:
    """Names of the program's ``terra.*`` spans in a profiler trace."""
    from jax.profiler import ProfileData
    names = set()
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("terra."))
    return names


@pytest.fixture(scope="module")
def served(llama, tmp_path_factory):
    """One mid-decode-admission serving run under a profiler session,
    with metrics and the trace buffer attached — shared by the timeline,
    metrics, and equality tests below."""
    cfg, params = llama
    lens = [5, 8, 13, 8, 5, 16]
    mns = [4, 9, 3, 5, 7, 4]
    eng = ServingEngine(cfg, params, max_len=MAX_LEN)
    ref = make_requests(cfg, lens, mns)
    for r in ref:
        eng.run_batch([r])
    eng.terra.close()

    sch = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                      max_len=MAX_LEN, steady_state=4)
    registry = sch.enable_metrics()
    lp = ListProcessor()
    sch.events.attach(lp)
    got = make_requests(cfg, lens, mns)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        sch.serve(got)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    stats = sch.stats
    sch.close()
    return dict(ref=ref, got=got, events=lp.events, registry=registry,
                stats=stats, span_names=profiler_span_names(trace_dir))


# ==========================================================================
# sampled profiling: correctness must be untouched
# ==========================================================================

def test_profiling_preserves_token_equality_and_steady_entry(served):
    """A profiler session and a structured processor both on: every
    request still matches its solo lock-step decode, the engine still
    reaches zero-walker steady state with no replay, and the program's
    spans are there — on the profiler's host line (scheduler phases,
    labelled runner closures, a GC pause) and in the event stream."""
    for i, (a, b) in enumerate(zip(served["ref"], served["got"])):
        assert a.out_tokens == b.out_tokens, f"request {i}"
    st = served["stats"]
    assert st["phase"] == "co-execution"
    assert st["retraces"] == 0 and st["replays"] == 0
    assert st["steady_iters"] > 0                  # steady entry happened
    names = served["span_names"]
    for name in ("terra.sched.plan", "terra.sched.decode_dispatch",
                 "terra.sched.prefill_splice", "terra.sched.harvest_wait",
                 "terra.sched.callback_flush", "terra.runner.steady",
                 "terra.runner.segment", "terra.runner.variable_update",
                 "terra.runner.snapshot", "terra.gc.gen2"):
        assert name in names, name
    done = [e for e in served["events"] if isinstance(e, T.RunnerComplete)]
    assert {"steady", "segment", "variable_update", "snapshot"} <= {
        e.label for e in done}
    assert all(e.queued >= 0.0 for e in done)
    assert st["gc_pauses"] >= 1 and st["gc_pause_s"] > 0.0


def test_dense_pool_counts_resident_tokens(served):
    """The dense layout reserves a full max_len row per active slot, so
    resident/peak accounting must be non-zero (satellite: the serving
    bench reported peak_resident_tokens: 0 on dense)."""
    st = served["stats"]
    assert st["peak_resident_tokens"] == 3 * MAX_LEN
    pool = SlotPool(2, row_tokens=16)
    pool.alloc("r0", 5)
    assert pool.resident_tokens == 16
    pool.alloc("r1", 7)
    assert (pool.resident_tokens, pool.peak_resident_tokens) == (32, 32)
    pool.release(0)
    assert (pool.resident_tokens, pool.peak_resident_tokens) == (16, 32)


# ==========================================================================
# timeline export
# ==========================================================================

def test_trace_json_valid_and_tracks_monotone(served, tmp_path):
    trace = chrome_trace(served["events"])
    # must round-trip as strict JSON
    blob = json.dumps(trace)
    doc = json.loads(blob)
    evs = doc["traceEvents"]
    assert len(evs) > 50
    by_track = {}
    for e in evs:
        assert {"ph", "pid", "tid"} <= set(e)
        if e["ph"] == "M":
            continue
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for track, tss in by_track.items():
        assert tss == sorted(tss), f"track {track} timestamps not monotone"
    # the exporter writes the same document
    exp = TraceViewerExporter(str(tmp_path / "t.trace.json"))
    for e in served["events"]:
        exp.process(e)
    exp.close()
    with open(exp.path) as f:
        assert json.load(f)["traceEvents"] == evs


def test_served_trace_validates_and_exports_through_the_report_cli(
        served, tmp_path):
    """The served run's event stream, written by ``JsonlSink``, passes
    the schema, and ``python -m repro.obs.report`` renders it to the same
    Perfetto document the exporter builds."""
    from repro.obs import report
    path = str(tmp_path / "trace.jsonl")
    sink = JsonlSink(path)
    for e in served["events"]:
        sink.process(e)
    sink.close()
    counts = validate_jsonl(path)
    assert sum(counts.values()) == len(served["events"])
    for kind in ("RequestSubmit", "StepDispatch", "RunnerComplete"):
        assert counts.get(kind, 0) > 0, kind
    out = str(tmp_path / "trace.perfetto.json")
    assert report.main([path, "--out", out]) == 0
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    assert len(evs) == len(chrome_trace(load_jsonl(path))["traceEvents"])
    assert len(evs) > 50


def test_trace_request_flows_complete(served):
    """Every retired request's lifecycle flow has a start (submit), at
    least one step (admit/prefill/token), and a finish (retire) — no
    dangling arrows even with mid-decode admissions."""
    evs = chrome_trace(served["events"])["traceEvents"]
    flows = {}
    for e in evs:
        if e.get("cat") == "flow" and str(e["id"]).startswith("req:"):
            flows.setdefault(e["id"], []).append(e["ph"])
    retired = {f"req:{e.rid}" for e in served["events"]
               if isinstance(e, T.RequestRetire)}
    assert retired and set(flows) == retired
    for fid, phs in flows.items():
        assert phs[0] == "s" and phs[-1] == "f", fid
        assert phs.count("s") == 1 and phs.count("f") == 1, fid
        assert "t" in phs, fid
    # finish arrows bind to the enclosing request span
    assert all(e.get("bp") == "e" for e in evs
               if e.get("cat") == "flow" and e["ph"] == "f")


# ==========================================================================
# streaming histograms + registry
# ==========================================================================

def test_histogram_matches_exact_rank_statistics():
    rng = np.random.RandomState(7)
    samples = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
    h = Histogram()
    for v in samples:
        h.observe(float(v))
    srt = np.sort(samples)
    tol = math.sqrt(GROWTH) - 1.0 + 1e-9           # bucket guarantee
    for q in (50.0, 90.0, 95.0, 99.0):
        exact = srt[max(1, math.ceil(q / 100.0 * len(srt))) - 1]
        got = h.percentile(q)
        assert abs(got - exact) / exact <= tol, (q, got, exact)
    assert h.mean == pytest.approx(samples.mean())  # mean is exact
    assert h.count == 5000
    assert h.percentile(0.0) == pytest.approx(srt[0], rel=tol)
    assert h.percentile(100.0) == pytest.approx(srt[-1], rel=tol)


def test_histogram_zeros_and_empty():
    h = Histogram()
    assert h.percentile(50.0) == 0.0 and h.mean == 0.0
    for v in (0.0, -1.0, 2.0, 4.0):
        h.observe(v)
    assert h.percentile(25.0) == 0.0               # underflow bucket
    assert h.count == 4 and h.mean == pytest.approx(1.25)


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]+=\"[^\"]*\"(,[a-zA-Z_]+="
    r"\"[^\"]*\")*\})? [-+0-9.eEnaif]+$")


def test_prometheus_exposition_parses(served):
    reg = served["registry"]
    assert reg.histograms["ttft_ms"].count == len(served["got"])
    text = reg.prometheus_text()
    names = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert line.startswith(("# HELP", "# TYPE"))
            continue
        assert _PROM_LINE.match(line), line
        names.add(line.split("{")[0].split(" ")[0])
    assert "terra_ttft_ms_count" in names
    assert "terra_ttft_ms_bucket" in names
    # cumulative buckets are monotone and +Inf equals the count
    for name, h in reg.histograms.items():
        if not h.count:
            continue
        pat = re.compile(rf'^terra_{name}_bucket{{le="([^"]+)"}} (\d+)$',
                         re.M)
        counts = [int(m.group(2)) for m in pat.finditer(text)]
        assert counts == sorted(counts)
        assert counts[-1] == h.count               # le="+Inf"


def test_metrics_processor_replay_and_counters_table(served):
    """Replaying the captured event list through a fresh processor gives
    the same histogram counts as the live run — the report CLI relies on
    this — and counters_table renders numeric entries only."""
    mp = MetricsProcessor()
    for e in served["events"]:
        mp.process(e)
    live = served["registry"]
    for name in ("ttft_ms", "token_latency_ms", "dispatch_us",
                 "fetch_us"):
        assert mp.registry.histograms[name].count == \
            live.histograms[name].count, name
    table = counters_table({"b_num": 3, "a_str": "x", "c_f": 1.5})
    assert "b_num" in table and "c_f" in table and "a_str" not in table


def test_metrics_registry_standalone():
    reg = MetricsRegistry()
    reg.observe("lat_ms", 3.0)
    reg.observe("lat_ms", 9.0)
    reg.set_gauge("depth", 4)
    reg.attach_counters({"steps": 12})
    snap = reg.snapshot()
    assert snap["histograms"]["lat_ms"]["count"] == 2
    assert snap["gauges"]["depth"] == 4
    assert snap["counters"]["steps"] == 12


# ==========================================================================
# fork observation (satellite: selector distributions)
# ==========================================================================

def test_fork_observation_distribution():
    @function
    def step(x):
        y = ops.mul(x, 2.0)
        if float(ops.reduce_sum(y)) > 10.0:        # gating fetch -> fork
            y = ops.mul(y, 10.0)
        else:
            y = ops.add(y, 1.0)
        return ops.reduce_sum(y)

    lp = ListProcessor()
    step.engine.events.attach(lp)
    vals = (0.5, 0.5, 3.0, 0.5, 3.0, 4.0, 0.1, 5.0)
    for v in vals:
        float(step(np.full(4, v, np.float32)))
    fam = step.engine.family
    step.close()
    obs = lp.of_type(T.ForkObserved)
    assert obs, "no ForkObserved events for a branchy program"
    assert len({e.case for e in obs}) == 2         # both arms observed
    assert len({e.family for e in obs}) == 1
    # the family accumulated the same distribution
    assert len(fam.sel_dist) >= 1
    dist = next(iter(fam.sel_dist.values()))
    assert sorted(dist) == [0, 1]
    assert sum(dist.values()) == len(obs)
