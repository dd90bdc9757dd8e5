"""Profiler capture and the reduction from a device trace to numbers.

``Capture`` takes a ``jax.profiler`` trace of a stretch of the window and
marks its ends with ``TraceAnnotation`` spans whose host-clock times it
also records, so that the trace's clock can be mapped onto
``time.perf_counter``.

``load`` turns the ``.xplane.pb`` file into a plain dict (planes, lines,
events as ``[name, start_ns, dur_ns]``), keeping the device planes and
the benchmark's own clock marks.  ``reduce`` works on that dict alone, so
that a small trace recorded on the chip can be committed and tested.

What ``reduce`` gives, for the marked stretch:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
* ``programs``: per class of program (by module name), the executions
  and their device seconds, per device; an execution that the stretch's
  ends cut counts by the share of it that lies inside;
* ``device_ops``: the operations that took the most device time;
* ``collective_s`` / ``collective_exposed_s``: time in collective
  operations, and the part of it in which no other operation ran on
  that device, per device;
* ``idle_gaps``: the longest stretches with no operation running,
  labelled by what the host was doing (from host spans on the
  ``perf_counter`` clock), and ``idle_by_host``, their sums per label.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CLOCK_MARK = "bench.clock"

# program classes by module name; the first pattern that matches wins
PROGRAM_CLASSES = {"prefill": "slot_prefill", "decode": "seg_fn|slot_decode"}
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"allreduce|allgather|reducescatter")


class Capture:
    """Start and stop one profiler trace; ``seconds`` is how long the
    caller means to trace."""

    def __init__(self, seconds: float, directory: str):
        self.seconds = float(seconds)
        self.dir = directory
        self.started = self.running = False
        self.mark0 = self.mark1 = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # a Python tracer slows the host
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = self.running = True
        with jax.profiler.TraceAnnotation(CLOCK_MARK + ".start"):
            self.mark0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(CLOCK_MARK + ".stop"):
            self.mark1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False

    def file(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def load(path: str) -> Dict:
    """The device planes of a trace file and the benchmark's clock marks,
    as a plain dict."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(CLOCK_MARK)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def excerpt(trace: Dict, mark0: float, mark1: float, seconds: float,
            host_spans: Sequence[Tuple[float, float, str]] = ()) -> Dict:
    """The first ``seconds`` of the marked stretch, as a trace of its own
    that ``reduce`` reads alone (a small recorded test): the stop mark
    moved to the excerpt's end, with its ``perf_counter`` ends
    (``perf_marks``) and the host spans inside it (``host_spans``)."""
    marks = _marks(trace)
    ns0, ns1 = marks["start"], marks["stop"]
    end_ns = min(ns1, ns0 + int(seconds * 1e9))
    end = mark0 + (mark1 - mark0) * (end_ns - ns0) / max(ns1 - ns0, 1)
    planes = []
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [[CLOCK_MARK + ".stop", end_ns, 0] if e[0] == CLOCK_MARK
                   + ".stop" else e for e in ln["events"]
                   if e[1] < end_ns and e[1] + e[2] > ns0
                   or e[0].startswith(CLOCK_MARK)]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes, "perf_marks": [mark0, end],
            "host_spans": [list(s) for s in host_spans
                           if s[1] > mark0 and s[0] < end]}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Union ``a`` minus union ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def _marks(trace: Dict) -> Dict[str, int]:
    marks = {}
    for p in trace["planes"]:
        for ln in p["lines"]:
            for name, start, dur in ln["events"]:
                if name.startswith(CLOCK_MARK):
                    marks[name[len(CLOCK_MARK) + 1:]] = start
    return marks


def program_class(name: str, classes=PROGRAM_CLASSES) -> str:
    for cls, pat in classes.items():
        if re.search(pat, name):
            return cls
    return "other"


def _device_lines(plane) -> Tuple[list, list]:
    ops, modules = [], []
    for ln in plane["lines"]:
        if ln["name"] == "XLA Ops":
            ops = ln["events"]
        elif ln["name"] == "XLA Modules":
            modules = ln["events"]
    return ops, modules


def reduce(trace: Dict, *, mark0: float, mark1: float,
           host_spans: Sequence[Tuple[float, float, str]] = (),
           classes=PROGRAM_CLASSES, top: int = 10) -> Dict:
    """Numbers of the stretch between the two clock marks; ``mark0`` and
    ``mark1`` are their ``perf_counter`` times, ``host_spans`` are
    ``(start, end, label)`` on the same clock."""
    marks = _marks(trace)
    if "start" not in marks or "stop" not in marks:
        raise ValueError("trace has no clock marks")
    ns0, ns1 = marks["start"], marks["stop"]
    # perf_counter seconds of a trace time (ns)
    scale = (mark1 - mark0) / max(ns1 - ns0, 1) if ns1 > ns0 else 1e-9

    def to_perf(ns):
        return mark0 + (ns - ns0) * scale
    window_s = (ns1 - ns0) * 1e-9
    lo, hi = float(ns0), float(ns1)

    per_dev, programs, op_time, gaps_all = [], {}, {}, []
    coll, coll_exposed = [], []
    for p in trace["planes"]:
        if not p["name"].startswith("/device:"):
            continue
        ops, modules = _device_lines(p)
        if not ops and not modules:
            continue
        busy = union(clip(((s, s + d) for _, s, d in (ops or modules)),
                          lo, hi))
        if not busy:
            continue
        per_dev.append(measure(busy) * 1e-9)
        for name, s, d in modules:
            iv = clip([(s, s + d)], lo, hi)
            if not iv:
                continue
            c = programs.setdefault(program_class(name, classes),
                                    {"count": 0, "device_s": 0.0})
            inside = measure(iv)
            c["count"] += inside / d if d > 0 else 1.0
            c["device_s"] += inside * 1e-9
        coll_iv, other_iv = [], []
        for name, s, d in ops:
            iv = clip([(s, s + d)], lo, hi)
            if not iv:
                continue
            op_time[name] = op_time.get(name, 0.0) + measure(iv) * 1e-9
            (coll_iv if COLLECTIVE.search(name) else other_iv).extend(iv)
        cu = union(coll_iv)
        coll.append(measure(cu) * 1e-9)
        coll_exposed.append(measure(subtract(cu, union(other_iv))) * 1e-9)
        gaps_all.extend(subtract([(lo, hi)], busy))
    n = max(len(per_dev), 1)
    for c in programs.values():
        c["count"] /= n
        c["device_s"] /= n

    spans = sorted((a, b, lab) for a, b, lab in host_spans)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)

    def label(g0, g1):
        """The host activity that overlaps the gap most."""
        a, b = to_perf(g0), to_perf(g1)
        best, best_t = "other Python", 0.0
        for i in range(bisect.bisect_left(starts, a - longest), len(spans)):
            s, e, lab = spans[i]
            if s >= b:
                break
            t = min(e, b) - max(s, a)
            if t > best_t:
                best, best_t = lab, t
        return best

    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])
    by_host: Dict[str, float] = {}
    for g0, g1 in gaps:
        lab = label(g0, g1)
        by_host[lab] = by_host.get(lab, 0.0) + (g1 - g0) * 1e-9 / n
    return {
        "window_s": window_s,
        "devices": len(per_dev),
        "busy_s": sum(per_dev) / n if per_dev else 0.0,
        "programs": programs,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "collective_s": max(coll) if coll else 0.0,
        "collective_exposed_s": max(coll_exposed) if coll_exposed else 0.0,
        "idle_gaps": [[label(g0, g1), (g1 - g0) * 1e-9]
                      for g0, g1 in gaps[:top]],
        "idle_by_host": by_host,
    }


def host_spans(spans, callbacks=()) -> List[Tuple[float, float, str]]:
    """(start, end, label) of what the scheduler's host thread was doing,
    from the HostSpans processor and the benchmark's token callbacks."""
    out = []
    for end, dur, kind, _rows in spans.dispatch:
        out.append((end - dur, end, "decode dispatch" if kind == "decode"
                    else "prefill splice"))
    for end, wait, _kind in spans.harvest:
        out.append((end - wait, end, "harvest wait"))
    for start, wait in spans.idle:
        out.append((start, start + min(max(wait, 0.0), 0.02),
                    "scheduler idle"))
    for start, dur in callbacks:
        out.append((start, start + max(dur, 1e-6), "callback flush"))
    return out
