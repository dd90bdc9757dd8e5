"""The reduction from a device trace to numbers: interval arithmetic, a
hand-made trace, and a small trace recorded on a TPU v5e."""

import os

import pytest

import benchkit  # noqa: F401  (puts bench/ on the path)
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000          # ns


def test_interval_arithmetic():
    u = tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tracing.measure(u) == 6
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tracing.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tracing.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def _hand_trace():
    """10 ms marked; two decode programs, one prefill, a collective of
    which 1 ms runs alone; host spans at perf_counter 100.0 + t."""
    mod = [["jit_seg_fn", 1 * MS, 3 * MS], ["jit_seg_fn", 5 * MS, 2 * MS],
           ["jit__slot_prefill_impl", 8 * MS, 1 * MS]]
    ops = [["fusion.1", 1 * MS, 3 * MS], ["copy.2", 5 * MS, 2 * MS],
           ["all-reduce.3", 6 * MS, 2 * MS], ["fusion.1", 8 * MS, 1 * MS]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.clock.start", 0, 1000], ["bench.clock.stop", 10 * MS,
                                             1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mod},
            {"name": "XLA Ops", "events": ops}]}]}


def test_reduce_hand_trace():
    spans = [(100.0, 100.001, "decode dispatch"),
             (100.0041, 100.0049, "harvest wait"),
             (100.009, 100.0095, "prefill splice")]
    r = tracing.reduce(_hand_trace(), mark0=100.0, mark1=100.010,
                       host_spans=spans)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["devices"] == 1
    # busy: [1,4] + [5,8] + [8,9] ms = 7 ms
    assert r["busy_s"] == pytest.approx(0.007)
    assert r["programs"]["decode"] == {"count": 2,
                                       "device_s": pytest.approx(0.005)}
    assert r["programs"]["prefill"]["count"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert r["collective_s"] == pytest.approx(0.002)
    assert r["collective_exposed_s"] == pytest.approx(0.001)
    # gaps [0,1] [4,5] [9,10] ms: labelled by the span overlapping most
    labels = {lab for lab, _ in r["idle_gaps"]}
    assert labels == {"decode dispatch", "harvest wait", "prefill splice"}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(0.003)
    assert r["idle_by_host"]["harvest wait"] == pytest.approx(0.001)


def test_reduce_needs_the_clock_marks():
    t = _hand_trace()
    t["planes"] = t["planes"][1:]
    with pytest.raises(ValueError):
        tracing.reduce(t, mark0=0.0, mark1=1.0)


def test_excerpt_reduces_alone():
    """An excerpt of the marked stretch is a trace of its own: reduced
    with its own marks and host spans it gives the numbers of that part."""
    spans = [(100.0, 100.001, "decode dispatch"),
             (100.009, 100.0095, "prefill splice")]
    ex = tracing.excerpt(_hand_trace(), 100.0, 100.010, 0.0045, spans)
    assert ex["perf_marks"] == [100.0, pytest.approx(100.0045)]
    assert ex["host_spans"] == [list(spans[0])]
    r = tracing.reduce(ex, mark0=ex["perf_marks"][0],
                       mark1=ex["perf_marks"][1],
                       host_spans=ex["host_spans"])
    assert r["window_s"] == pytest.approx(0.0045)
    assert r["busy_s"] == pytest.approx(0.003)       # [1, 4] ms
    assert r["programs"] == {"decode": {"count": 1,
                                        "device_s": pytest.approx(0.003)}}
    assert r["idle_by_host"]["decode dispatch"] == pytest.approx(0.001)


def test_reduce_recorded_chip_trace():
    """0.13 s of a granite2b-decode-backlog window traced on one TPU v5e
    (``run.py --trace 1 --out``, trimmed by ``tracing.excerpt``): the
    decode step is the executor's segment ``jit_seg_fn``, one module a
    step (the host dispatches one decode step per module time), and the
    reduction gives its device time per step."""
    import gzip
    import json
    import statistics
    with gzip.open(os.path.join(DATA, "backlog_trace_excerpt.json.gz"),
                   "rt") as f:
        ex = json.load(f)
    r = tracing.reduce(ex, mark0=ex["perf_marks"][0],
                       mark1=ex["perf_marks"][1], host_spans=ex["host_spans"])
    assert r["devices"] == 1
    assert 0.98 * r["window_s"] <= r["busy_s"] <= r["window_s"]
    assert set(r["programs"]) == {"decode"}
    dec = r["programs"]["decode"]
    step = dec["device_s"] / dec["count"]
    assert step == pytest.approx(0.04189, rel=1e-3)
    mods = [e for p in ex["planes"] for ln in p["lines"]
            if ln["name"] == "XLA Modules" for e in ln["events"]]
    assert len(mods) == 4
    assert all(name.startswith("jit_seg_fn(") for name, _, _ in mods)
    ends = sorted(e for _, e, lab in ex["host_spans"]
                  if lab == "decode dispatch")
    intervals = [b - a for a, b in zip(ends, ends[1:]) if b - a > 0.005]
    assert statistics.median(intervals) == pytest.approx(step, rel=0.02)
    labels = {lab for lab, _ in r["idle_gaps"]}
    assert labels <= {"decode dispatch", "prefill splice", "harvest wait",
                      "scheduler idle", "callback flush", "other Python"}
