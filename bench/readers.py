"""Arithmetic the metric files share; each reads the run's record.

The record (built by ``run.py``) holds, for a serving cell:

* ``t0``, ``t_end``: the window on the ``perf_counter`` clock, and
  ``seconds``; ``setup_s``;
* ``requests``: every request submitted in the window (``due``,
  ``prompt``, ``tokens`` with their delivery ``times``);
* ``spans``: the event-stream processor's host spans (traced runs);
* ``trace``: the trace reduction (traced runs), and ``trace_marks``,
  its two ends on the ``perf_counter`` clock;
* ``family``, ``sizes``, ``peaks``, ``chips``.

Every function returns None where there is nothing to read.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def p50(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.median(v)) if v.size else None


def due_in_window(rec):
    return [r for r in rec["requests"] if rec["t0"] <= r.due <= rec["t_end"]]


def ttft_s(rec):
    """Due time to first token, for every request due in the window; one
    that never got a token counts up to the end of the run."""
    return [(r.times[0] if r.times else rec["t_stop"]) - r.due
            for r in due_in_window(rec)]


def itl_s(rec):
    out = []
    for r in due_in_window(rec):
        out.extend(np.diff(r.times).tolist())
    return out


def tokens_in_window(rec) -> int:
    t0, t1 = rec["t0"], rec["t_end"]
    return sum(sum(1 for t in r.times if t0 <= t <= t1)
               for r in rec["requests"])


def _span_end(rec) -> float:
    """Events count up to the end of the trace where one was taken (its
    stop stalls the host), else to the end of the window."""
    marks = rec.get("trace_marks")
    return marks[1] if marks else rec["t_end"]


def dispatches(rec, kind):
    sp = rec.get("spans")
    if sp is None:
        return []
    t0, t1 = rec["t0"], _span_end(rec)
    return [(dur, rows) for end, dur, k, rows in sp.dispatch
            if k == kind and t0 <= end <= t1]


def mean_dispatch_us(rec, kind) -> Optional[float]:
    d = dispatches(rec, kind)
    return 1e6 * float(np.mean([x for x, _ in d])) if d else None


def program(rec, cls):
    tr = rec.get("trace")
    if not tr:
        return None
    p = tr["programs"].get(cls)
    return p if p and p["count"] > 0 else None


def step_device_ms(rec, cls) -> Optional[float]:
    p = program(rec, cls)
    return 1e3 * p["device_s"] / p["count"] if p else None


def idle_share(rec) -> Optional[float]:
    tr = rec.get("trace")
    if not tr or tr["devices"] == 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def decode_keys(rec):
    """Keys attended by each token a decode step produced in the window:
    a request's token i >= 1 attends its prompt and i tokens."""
    t0, t1 = rec["t0"], _span_end(rec)
    out = []
    for r in rec["requests"]:
        n = len(r.prompt)
        out.extend(n + i for i, t in enumerate(r.times)
                   if i >= 1 and t0 <= t <= t1)
    return out


def decode_roofline(rec) -> Optional[float]:
    """Least time the chip could take for an average decode step of the
    window (needed FLOPs over peak, or needed bytes over bandwidth,
    whichever is larger) over the measured device time of one step."""
    p = program(rec, "decode")
    d = dispatches(rec, "decode")
    keys = decode_keys(rec)
    if not p or not d or not keys:
        return None
    fam, s, pk = rec["family"], rec["sizes"], rec["peaks"]
    rows = float(np.mean([r for _, r in d]))
    k = float(np.mean(keys))
    need_bytes = fam.decode_step_bytes(s, rows, k)
    need_flops = rows * fam.decode_token_flops(s, k)
    t_min = max(need_flops / pk["bf16_flops_per_s"],
                need_bytes / pk["hbm_bytes_per_s"])
    rec.setdefault("notes", {})["decode_step_bound"] = (
        "bytes" if need_bytes / pk["hbm_bytes_per_s"]
        >= need_flops / pk["bf16_flops_per_s"] else "flops")
    return 100.0 * t_min / (p["device_s"] / p["count"])


def serve_mfu(rec) -> Optional[float]:
    """Model FLOPs of the real prompt and generated tokens delivered in
    the window over the window times the chips' peak."""
    fam, s = rec["family"], rec["sizes"]
    t0, t1 = rec["t0"], rec["t_end"]
    flops = 0.0
    for r in rec["requests"]:
        n = len(r.prompt)
        for i, t in enumerate(r.times):
            if t0 <= t <= t1:
                flops += (fam.prompt_flops(s, n) if i == 0
                          else fam.decode_token_flops(s, n + i))
    if flops == 0:
        return None
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (rec["seconds"] * peak)


def prefill_mfu(rec) -> Optional[float]:
    """Model FLOPs of the real prompt tokens per prefill program (mean
    over the window's prefills) over its device time and the peak."""
    p = program(rec, "prefill")
    sp = rec.get("spans")
    steps = dispatches(rec, "prefill")
    if not p or sp is None or not steps:
        return None
    t0, t1 = rec["t0"], _span_end(rec)
    fam, s = rec["family"], rec["sizes"]
    flops = sum(fam.prompt_flops(s, n) for ts, _b, n in sp.prefill
                if t0 <= ts <= t1)
    per_step = flops / len(steps)
    return 100.0 * per_step * p["count"] / (
        p["device_s"] * rec["peaks"]["bf16_flops_per_s"])


def queue_wait_p95_ms(rec) -> Optional[float]:
    sp = rec.get("spans")
    if sp is None:
        return None
    t0, t1 = rec["t0"], _span_end(rec)
    v = p95(q for ts, q in sp.admit if t0 <= ts <= t1)
    return None if v is None else 1e3 * v
