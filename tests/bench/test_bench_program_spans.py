"""The metrics read from the program's own spans (GC pauses, compiles,
runner closures), and idle gaps labelled from the ``terra.*`` spans of a
trace: on a synthetic record, on a hand-made trace, and through whole
runs at smoke widths on the CPU."""

import sys
import time

import pytest

import benchkit
import program_spans
from repro.core.events import runtime

MS = 1_000_000          # ns


@pytest.fixture
def record(monkeypatch):
    """A fresh process record, and a run's record whose window is
    [10, 21] s: t0 10, the trace ending at 21 after the window's 20."""
    rt = runtime.Runtime()
    monkeypatch.setattr(runtime, "RUNTIME", rt)
    rec = {"t0": 10.0, "t_end": 20.0, "trace_marks": (16.0, 21.0)}
    return rt, rec


def _fill(rt):
    for start, dur, gen in [(9.0, 0.5, 0),        # ends before t0
                            (12.0, 0.06, 2), (15.0, 0.002, 0),
                            (20.9, 0.2, 1)]:      # ends after the trace
        rt.gc.push(start, dur, gen)
    for start, dur, stage in [(11.0, 0.3, "trace"), (11.3, 0.1, "lower"),
                              (11.4, 0.5, "backend"),
                              (11.5, 0.2, "cache_load")]:
        rt.compile.push(start, dur, stage)
    for start, dur, label in [(12.0, 0.0002, "steady"),
                              (13.0, 0.0004, "segment"),
                              (14.0, 0.0006, "chain"),
                              (15.0, 0.1, "variable_update"),
                              (16.0, 0.2, "snapshot"),
                              (9.0, 0.1, "steady")]:
        rt.runner.push(start, dur, label)


def test_readers_on_a_synthetic_record(record):
    rt, rec = record
    _fill(rt)
    assert program_spans.gc_pause_ms(rec) == pytest.approx(62.0)
    # the cache load lies inside the backend compile: not counted twice
    assert program_spans.compile_ms(rec) == pytest.approx(900.0)
    assert program_spans.runner_us_per_decode(rec) == pytest.approx(400.0)
    bench = benchkit.benchmark()
    for cell in ("backlog", "rag"):
        read = {m: bench.reader(f"{m}.{cell}")(rec)
                for m in ("gc_pause_ms", "compile_ms",
                          "runner_us_per_decode")}
        assert read == {"gc_pause_ms": pytest.approx(62.0),
                        "compile_ms": pytest.approx(900.0),
                        "runner_us_per_decode": pytest.approx(400.0)}


def test_empty_window_reads_zero_compiles_and_no_runner(record):
    rt, rec = record
    assert program_spans.gc_pause_ms(rec) == 0.0
    assert program_spans.compile_ms(rec) == 0.0
    assert program_spans.runner_us_per_decode(rec) is None


def test_a_ring_that_lost_the_window_start_reads_none(record):
    rt, rec = record
    rt.gc = runtime.Ring(4)
    for k in range(6):
        rt.gc.push(11.0 + k, 0.01, 0)
    assert program_spans.gc_pause_ms(rec) is None


def test_a_program_without_the_record_reads_none(record, monkeypatch):
    _rt, rec = record
    import repro.core.events as events
    monkeypatch.delattr(events, "runtime")
    monkeypatch.setitem(sys.modules, "repro.core.events.runtime", None)
    assert program_spans.gc_pause_ms(rec) is None
    assert program_spans.compile_ms(rec) is None
    assert program_spans.runner_us_per_decode(rec) is None


def _hand_trace():
    """10 ms marked; the device idle in [0,1], [4,5], [6,7] and [9,10]."""
    ops = [["fusion.1", 1 * MS, 3 * MS], ["copy.2", 5 * MS, 1 * MS],
           ["fusion.1", 7 * MS, 2 * MS]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.clock.start", 0, 1000],
            ["bench.clock.stop", 10 * MS, 1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]}]}


def test_gaps_labelled_cause_over_bystander():
    spans = [
        # [0,1]: the harvest wait covers it, a GC pause inside it is why
        ["terra.sched.harvest_wait", 0, 1 * MS],
        ["terra.gc.gen2", int(0.5 * MS), int(0.2 * MS)],
        # [4,5]: a runner closure outranks the scheduler's plan
        ["terra.sched.plan", 4 * MS, 1 * MS],
        ["terra.runner.snapshot", int(4.8 * MS), int(0.1 * MS)],
        # [6,7]: two scheduler phases, the one overlapping most wins
        ["terra.sched.callback_flush", 6 * MS, int(0.3 * MS)],
        ["terra.sched.decode_dispatch", int(6.3 * MS), int(0.7 * MS)],
    ]                   # [9,10]: nothing
    out = program_spans.label_gaps(_hand_trace(), spans)
    assert sorted(out["idle_gaps"]) == sorted([
        ["gc.gen2", pytest.approx(0.001)],
        ["runner.snapshot", pytest.approx(0.001)],
        ["sched.decode_dispatch", pytest.approx(0.001)],
        ["other Python", pytest.approx(0.001)]])
    assert sum(out["idle_by_label_s"].values()) == pytest.approx(0.004)
    # a compile from the record lands on the trace through the marks
    on_trace = program_spans.to_trace_ns(
        _hand_trace(), 100.0, 100.010,
        [("compile", 100.009, 0.0005, "backend")])
    assert on_trace == [["terra.compile.backend", 9 * MS, MS // 2]]
    out = program_spans.label_gaps(_hand_trace(), spans + on_trace)
    assert ["compile.backend", pytest.approx(0.001)] in out["idle_gaps"]


def test_step_program_names_keep_their_classes():
    """The step programs' module names (``jit_serve.slot_decode``,
    ``jit_serve.slot_prefill``) fall in the classes the trace reduction
    has always used, as the old ones did."""
    import tracing
    for name, cls in [("jit_serve.slot_decode", "decode"),
                      ("jit_serve.slot_prefill", "prefill"),
                      ("jit_seg_fn", "decode"),
                      ("jit__slot_prefill_impl", "prefill")]:
        assert tracing.program_class(name) == cls, name


def test_longest_pause_says_what_the_program_recorded():
    import stalls

    class Req:
        def __init__(self, times):
            self.times = times
    rec = {"t0": 10.0, "t_end": 20.0,
           "requests": [Req([11.0, 11.1, 11.2]), Req([11.25, 13.0, 13.1])]}
    spans = [("gc", 11.5, 0.9, 2), ("gc", 12.5, 0.01, 0),
             ("compile", 11.3, 0.05, "backend"),
             ("runner", 11.26, 0.2, "steady"),
             ("runner", 14.0, 0.1, "steady")]
    host = [(11.25, 12.0, "harvest wait"), (12.9, 13.0, "decode dispatch"),
            (14.0, 14.1, "harvest wait")]
    p = stalls.longest_pause(rec, spans, host)
    assert p["seconds"] == pytest.approx(1.75)
    assert p["host_s"] == {"harvest wait": pytest.approx(0.75),
                           "decode dispatch": pytest.approx(0.1)}
    assert p["gc_s"] == {"gen2": pytest.approx(0.9), "gen0": 0.01}
    assert p["compile_s"] == pytest.approx(0.05)
    assert p["runner"] == {"steady": [1, pytest.approx(0.2)]}


def _cpu_run(mix_name, vocab, trace):
    import jax
    import run
    import serve
    t0 = time.perf_counter()
    bench = benchkit.benchmark()
    cfg, mix = benchkit.tiny_config(vocab), benchkit.tiny_mix(mix_name)
    fam = bench.family(cfg["family"])
    devs = jax.devices()[:1]
    rec = serve.measure(cfg, mix, fam, seed=2 ** 35 + vocab, seconds=2.0,
                        trace=trace, devs=devs, peaks=benchkit.CPU_PEAKS,
                        t_start=t0, say=lambda m: None)
    name = ("granite2b-decode-backlog" if mix["loop"] == "closed"
            else "granite2b-rag-steady")
    return run.result(bench, {"name": name}, rec, trace=trace, devs=devs)


@pytest.mark.parametrize("mix_name,cell,vocab", [
    ("decode-backlog", "backlog", 516), ("rag-steady", "rag", 524)])
def test_traced_run_reports_the_program_metrics(mix_name, cell, vocab):
    line = _cpu_run(mix_name, vocab, trace=True)
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    for m in ("gc_pause_ms", "compile_ms", "runner_us_per_decode"):
        assert f"{m}.{cell}" in got, (m, sorted(got))
    assert got[f"compile_ms.{cell}"]["value"] >= 0.0
    assert got[f"runner_us_per_decode.{cell}"]["value"] > 0.0


def test_stalls_study_on_the_cpu():
    import stalls
    bench = benchkit.benchmark()
    cfg, mix = benchkit.tiny_config(528), benchkit.tiny_mix("decode-backlog")
    fam = bench.family(cfg["family"])
    out = stalls.study(cfg, mix, fam, seed=7, seconds=2.0, trace=True,
                       log=lambda m: None)
    assert out["output_tokens_per_s"] > 0
    assert out["runner_us_per_decode"] > 0 and out["compile_ms"] >= 0.0
    assert out["record_spans"]["runner"] > 0
    assert out["longest_pause"]["seconds"] > 0
    labels = out["program_labels"]
    assert set(labels) == {"idle_gaps", "idle_by_label_s"}
