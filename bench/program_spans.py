"""The program's own spans, read after a run.

The program keeps a process-wide record of its host stalls
(``repro.core.events.runtime``): every GC pause with its generation,
every compile stage, and every GraphRunner closure with its submitter's
label, on the ``perf_counter`` clock.  The per-layer metrics
``gc_pause_ms.*``, ``compile_ms.*`` and ``runner_us_per_decode.*`` read it
over the window ``[t0, end of the trace]`` (events count up to the end of
the trace where one was taken, as ``readers._span_end`` has it).  A
program without that record gives None for each, and nothing here
raises for it.

While a profiler session collects, the program also puts ``terra.*``
spans on the trace's host line (scheduler phases, runner closures, GC
pauses).  ``label_gaps`` puts each idle gap of the device down to them
on the trace's own clock: a cause (GC or compile) first, then a runner
closure, then the scheduler phase that overlaps the gap most, else
``other Python``.  Compile spans come from the record, mapped onto the
trace through the clock marks.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import readers
import tracing

PREFIX = "terra."
DECODE_CLOSURES = ("steady", "segment", "chain")     # a decode step's
OTHER = "other Python"


def _runtime():
    try:
        from repro.core.events import runtime
    except ImportError:
        return None
    return runtime


def record_spans(rec) -> Optional[List[Tuple[str, float, float, object]]]:
    """``(source, start, seconds, label)`` of the program's spans that end
    in the window, or None where the program keeps no record (or no
    longer holds the window's start)."""
    rt = _runtime()
    if rt is None:
        return None
    return rt.RUNTIME.between(rec["t0"], readers._span_end(rec))


def gc_pause_ms(rec) -> Optional[float]:
    """Milliseconds of GC pauses, all generations, ending in the window."""
    sp = record_spans(rec)
    if sp is None:
        return None
    return 1e3 * sum(d for src, _s, d, _lab in sp if src == "gc")


def compile_ms(rec) -> Optional[float]:
    """Milliseconds of compiles ending in the window: every stage but the
    persistent-cache load, which lies inside a backend compile."""
    sp = record_spans(rec)
    if sp is None:
        return None
    nested = _runtime().NESTED_STAGES
    return 1e3 * sum(d for src, _s, d, lab in sp
                     if src == "compile" and lab not in nested)


def runner_us_per_decode(rec) -> Optional[float]:
    """Mean runner-thread time of the decode step's closures (steady,
    segment, chain) ending in the window."""
    sp = record_spans(rec)
    if sp is None:
        return None
    walls = [d for src, _s, d, lab in sp
             if src == "runner" and lab in DECODE_CLOSURES]
    return 1e6 * float(np.mean(walls)) if walls else None


# --------------------------------------------------------------------------
# idle gaps labelled from the program's spans
# --------------------------------------------------------------------------

def host_spans(path: str) -> List[list]:
    """``[name, start_ns, dur_ns]`` of the ``terra.*`` spans on the host
    lines of a ``.xplane.pb`` trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def to_trace_ns(trace: Dict, mark0: float, mark1: float, spans):
    """``(source, start, seconds, label)`` record spans on the trace's
    clock, through its two clock marks: ``[name, start_ns, dur_ns]``."""
    marks = tracing._marks(trace)
    ns0, ns1 = marks["start"], marks["stop"]
    per_s = (ns1 - ns0) / (mark1 - mark0) if mark1 > mark0 else 1e9
    return [[f"{PREFIX}{src}.{'gen' if src == 'gc' else ''}{lab}",
             round(ns0 + (s - mark0) * per_s), round(d * per_s)]
            for src, s, d, lab in spans]


def _rank(name: str) -> int:
    if name.startswith((PREFIX + "gc.", PREFIX + "compile.")):
        return 0
    if name.startswith(PREFIX + "runner."):
        return 1
    return 2


def gaps(trace: Dict) -> List[Tuple[int, int]]:
    """The stretches of the marked span in which no operation ran on a
    device, longest first (ns; every device's, as ``tracing.reduce``)."""
    marks = tracing._marks(trace)
    lo, hi = marks["start"], marks["stop"]
    out = []
    for p in trace["planes"]:
        if not p["name"].startswith("/device:"):
            continue
        ops, modules = tracing._device_lines(p)
        busy = tracing.union(tracing.clip(
            ((s, s + d) for _, s, d in (ops or modules)), lo, hi))
        if busy:
            out.extend(tracing.subtract([(lo, hi)], busy))
    return sorted(out, key=lambda g: g[0] - g[1])


def label(g0: int, g1: int, spans: Sequence[list]) -> str:
    """What the host was doing in the gap ``[g0, g1]``: of the spans that
    overlap it, the highest-ranked cause, the one overlapping most."""
    best, key = OTHER, None
    for name, s, d in spans:
        t = min(s + d, g1) - max(s, g0)
        if t <= 0:
            continue
        k = (-_rank(name), t)
        if key is None or k > key:
            best, key = name[len(PREFIX):], k
    return best


def label_gaps(trace: Dict, spans: Sequence[list], top: int = 10,
               min_ns: int = 0) -> Dict:
    """Idle gaps labelled from the program's spans: the ``top`` longest
    (at least ``min_ns``) as ``[label, seconds]``, and the idle seconds
    of every gap by label."""
    found = gaps(trace)
    by_label: Dict[str, float] = {}
    listed = []
    for g0, g1 in found:
        lab = label(g0, g1, spans)
        by_label[lab] = by_label.get(lab, 0.0) + (g1 - g0) * 1e-9
        if len(listed) < top and g1 - g0 >= min_ns:
            listed.append([lab, (g1 - g0) * 1e-9])
    return {"idle_gaps": listed, "idle_by_label_s": by_label}


def over(rec_spans, a: float, b: float) -> Dict:
    """What the record holds over ``[a, b]`` (perf_counter): GC pauses by
    generation, compile seconds, and runner closures by label (count,
    seconds), for a stall's notes."""
    gcs: Dict[str, float] = {}
    runner: Dict[str, list] = {}
    compile_s = 0.0
    for src, s, d, lab in rec_spans:
        t = min(s + d, b) - max(s, a)
        if t <= 0:
            continue
        if src == "gc":
            gcs[f"gen{lab}"] = gcs.get(f"gen{lab}", 0.0) + d
        elif src == "compile":
            compile_s += d if lab not in _runtime().NESTED_STAGES else 0.0
        else:
            c = runner.setdefault(lab, [0, 0.0])
            c[0] += 1
            c[1] += d
    return {"gc_s": gcs, "compile_s": compile_s, "runner": runner}
