"""A serving cell: set-up, warm-up, the measured window, and the record.

A configuration file names its kind of cell (``"cell": "serve"``), and
``run.py`` calls that module's ``measure``.

The window drives the program's ``ContinuousBatchingScheduler`` under
co-execution (``use_terra=True``), as a user of the system would: the
benchmark submits requests, takes each token through the request's
streaming callback, and calls ``run``.  It reads the scheduler's event
stream through a processor of its own, and nothing else of the program.

The loop runs in short ``run(max_steps=CHUNK)`` calls so that the window
can close, and the profiler start and stop, at a given time.
"""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

import check
import device
import tracing
from loadgen import Traffic

CHUNK = 32      # scheduler loop iterations per run() call
TRACE_S = 4.0   # traced stretch at the end of the window


class HostSpans:
    """Event-stream processor: host spans and request lifecycle, with the
    program's own timestamps (``time.perf_counter``)."""

    def __init__(self):
        self.dispatch: List[tuple] = []     # (end, dur, kind, rows)
        self.harvest: List[tuple] = []      # (end, wait, kind)
        self.idle: List[tuple] = []         # (start, wait)
        self.admit: List[tuple] = []        # (ts, queued_s)
        self.prefill: List[tuple] = []      # (ts, bucket, prompt_len)

    def process(self, e) -> None:
        name = type(e).__name__
        if name == "StepDispatch":
            self.dispatch.append((e.ts, e.dur, e.kind, e.rows))
        elif name == "StepHarvest":
            self.harvest.append((e.ts, e.wait, e.kind))
        elif name == "SchedulerIdle":
            self.idle.append((e.ts, e.wait))
        elif name == "RequestAdmit":
            self.admit.append((e.ts, e.queued_s))
        elif name == "RequestPrefill":
            self.prefill.append((e.ts, e.bucket, e.prompt_len))

    def close(self) -> None:
        pass


class CompileCounter:
    """Backend compile requests seen by JAX (jax.monitoring)."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Tracked:
    """One request of the window; holding ``req`` keeps its id unique."""
    __slots__ = ("prompt", "max_new", "due", "times", "tokens", "req")

    def __init__(self, prompt, max_new, due, req):
        self.prompt, self.max_new, self.due, self.req = (prompt, max_new,
                                                         due, req)
        self.times: List[float] = []
        self.tokens: List[int] = []


def pow2_buckets(lo: int, hi: int, max_len: int, floor: int = 8):
    """The power-of-two prompt buckets that lengths lo..hi fall into."""
    out, b = set(), max(1, floor)
    while b < lo:
        b <<= 1
    while True:
        out.add(min(b, max_len))
        if b >= hi:
            break
        b <<= 1
    return sorted(out)


class ServeCell:
    def __init__(self, cfg: Dict, mix: Dict, family, seed: int,
                 seconds: float, log,
                 compiles: Optional[CompileCounter] = None):
        self.cfg, self.mix, self.family = cfg, mix, family
        self.compiles = compiles
        self.seed, self.seconds, self.log = seed, float(seconds), log
        self.serving = cfg["serving"]
        self.traffic = Traffic(mix, seed, seconds, cfg["vocab_size"])
        self.tracked: Dict[int, Tracked] = {}
        self.requests: List[Tracked] = []     # every request of the window
        self.t_end = float("inf")
        self.spans: Optional[HostSpans] = None
        self.callback_s: List[tuple] = []

    # ------------------------------------------------------------------
    def _on_token(self, req, token, index) -> None:
        now = time.perf_counter()
        tr = self.tracked.get(id(req))
        if tr is None:
            return
        tr.times.append(now)
        tr.tokens.append(int(token))
        if (index == req.max_new_tokens - 1 and self.traffic.loop == "closed"
                and now < self.t_end):
            self._submit(now)
        self.callback_s.append((now, time.perf_counter() - now))

    def _submit(self, due: float, item=None) -> None:
        from repro.serve.engine import Request
        item = item or self.traffic.next_item()
        req = Request(prompt=item.prompt, max_new_tokens=item.max_new,
                      arrival_time=due, stream=self._on_token)
        tr = Tracked(item.prompt, item.max_new, due, req)
        self.tracked[id(req)] = tr
        self.requests.append(tr)
        self.sch.submit(req)

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.serve.engine import Request
        from repro.serve.scheduler import ContinuousBatchingScheduler
        self.model_cfg = self.family.program_config(self.cfg)
        t = time.perf_counter()
        params = self.family.init_weights(self.cfg, self.seed)
        jax.block_until_ready(params)
        self.log(f"weights made on the device in "
                 f"{time.perf_counter() - t:.2f} s")
        sv = self.serving
        self.sch = ContinuousBatchingScheduler(
            self.model_cfg, params, max_slots=sv["max_slots"],
            max_len=sv["max_len"], prefill_batch_cap=sv["prefill_batch_cap"],
            use_terra=True)
        del params
        rng = np.random.default_rng(0)
        vocab = self.cfg["vocab_size"]

        def reqs(n, length, max_new):
            return [Request(prompt=rng.integers(0, vocab, length,
                                                dtype=np.int32),
                            max_new_tokens=max_new, arrival_time=0.0)
                    for _ in range(n)]
        lo, hi = self.traffic.prompt_range()
        t = time.perf_counter()
        # into co-execution and its steady state: the decode step's shape
        # is fixed, so one request's decode steps warm it
        self.sch.serve(reqs(1, lo, 24))
        cap = sv["prefill_batch_cap"]
        rows = [r for r in (1, 2, 4, 8, 16, 32) if r <= cap]
        for b in pow2_buckets(lo, hi, sv["max_len"]):
            for r in rows:
                self.sch.serve(reqs(r, min(b, hi), 2))
        self.sch.serve(reqs(1, lo, 24))
        st = self.sch.stats
        self.log(f"warm-up {time.perf_counter() - t:.2f} s: phase="
                 f"{st.get('phase')} steady_entries="
                 f"{st.get('steady_entries')} retraces={st.get('retraces')}")

    # ------------------------------------------------------------------
    def window(self, tracer=None) -> None:
        sch = self.sch
        st0 = sch.stats
        self.retraces0 = st0.get("retraces", 0)
        c0 = self.compiles.requests if self.compiles else 0
        if tracer is not None:
            self.spans = HostSpans()
            sch.events.attach(self.spans)
        t0 = time.perf_counter()
        self.t0, self.t_end = t0, t0 + self.seconds
        if self.traffic.loop == "closed":
            for _ in range(self.traffic.clients):
                self._submit(t0)
        else:
            for item in self.traffic.schedule():
                self._submit(t0 + item.offset, item)
        deadline = self.t_end + 60.0
        while True:
            now = time.perf_counter()
            if tracer is not None:
                if not tracer.started and now >= self.t_end - tracer.seconds:
                    tracer.start()
                elif tracer.running and now >= self.t_end:
                    tracer.stop()
            if self.traffic.loop == "closed":
                if now >= self.t_end:
                    break
            elif not (len(sch.queue) or sch.pool.active_count):
                break
            if now >= deadline:
                self.log("open loop: requests still unserved a minute "
                         "after the window closed")
                break
            sch.run(max_steps=CHUNK)
        if tracer is not None and tracer.running:
            tracer.stop()
        self.t_stop = time.perf_counter()
        st = sch.stats
        self.stats = {"phase": st.get("phase"),
                      "retraces": st.get("retraces", 0) - self.retraces0,
                      "steady_entries": st.get("steady_entries", 0),
                      "compiles": (self.compiles.requests - c0
                                   if self.compiles else None)}

    def close(self) -> None:
        self.sch.close()
        del self.sch
        gc.collect()


# --------------------------------------------------------------------------
# one run: set-up, window, comparison
# --------------------------------------------------------------------------

def measure(cfg, mix, family, *, seed: int, seconds: float, trace: bool,
            devs, peaks, t_start: float, out_dir=None, say=print):
    """Set up, measure, compare; returns the run's record."""
    cell = ServeCell(cfg, mix, family, seed, seconds, say, CompileCounter())
    cell.setup()
    tracer, tdir = None, None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        tracer = tracing.Capture(min(TRACE_S, seconds / 2), tdir)
    cell.window(tracer)
    mem = device.memory_peak_bytes(devs)
    reqs, stats = cell.requests, cell.stats
    spans, callbacks = cell.spans, cell.callback_s
    t0, t_end, t_stop = cell.t0, cell.t_end, cell.t_stop
    cell.close()
    del cell

    loop = mix["loop"]
    finished = [r for r in reqs if len(r.tokens) == r.max_new]
    failed = 0 if loop == "closed" else sum(
        1 for r in reqs if t0 <= r.due <= t_end and len(r.tokens) < r.max_new)
    picked = check.sample(finished, seed, **mix["check_sample"])
    t = time.perf_counter()
    gaps = check.served_gaps(family, cfg, seed, picked,
                             cfg["serving"]["max_len"])
    say(f"reference over {gaps['requests']} requests, {gaps['tokens']} "
        f"served tokens: {time.perf_counter() - t:.2f} s")

    rec = {"setup_s": t0 - t_start, "t0": t0, "t_end": t_end,
           "t_stop": t_stop, "seconds": float(seconds), "loop": loop,
           "requests": reqs, "spans": spans, "trace": None,
           "trace_marks": None, "family": family,
           "sizes": family.sizes(cfg), "peaks": peaks, "chips": len(devs),
           "memory_peak_bytes": mem, "stats": stats, "gaps": gaps,
           "attempted": len(reqs) if loop == "closed" else sum(
               1 for r in reqs if t0 <= r.due <= t_end),
           "failed": failed, "notes": delivery(reqs, t0, t_end)}
    if tracer is not None:
        path = tracer.file()
        if path is not None:
            raw = tracing.load(path)
            hs = tracing.host_spans(spans, callbacks)
            rec["trace"] = tracing.reduce(
                raw, mark0=tracer.mark0, mark1=tracer.mark1, host_spans=hs)
            rec["trace_marks"] = (tracer.mark0, tracer.mark1)
            if out_dir:
                tracing.save(tracing.excerpt(raw, tracer.mark0, tracer.mark1,
                                             0.25, hs),
                             os.path.join(out_dir, "trace_excerpt.json.gz"))
        shutil.rmtree(tdir, ignore_errors=True)
    rec["checks"] = checks(cfg, rec)
    return rec


def delivery(reqs, t0: float, t_end: float, bin_s: float = 5.0):
    """How evenly the window delivered tokens: the longest pause between
    two deliveries, and the tokens of each ``bin_s`` stretch.  A stall of
    the host shows here as a long pause and a thin stretch."""
    times = np.sort([t for r in reqs for t in r.times if t0 <= t <= t_end])
    if times.size < 2:
        return {}
    bins = np.bincount(((times - t0) // bin_s).astype(int))
    return {"longest_delivery_pause_s": float(np.diff(times).max()),
            f"tokens_per_{bin_s:g}s": bins.tolist()}


def checks(cfg, rec):
    """(value, limit, relation) of each number compared."""
    st = rec["stats"]
    return {
        "max_logit_gap": (rec["gaps"]["max_logit_gap"],
                          cfg["check"]["max_logit_gap"], "<="),
        "checked_tokens": (rec["gaps"]["tokens"], 1, ">="),
        "unserved_requests": (rec["failed"], 0, "<="),
        "retraces_in_window": (st["retraces"], 0, "<="),
        "coexecution": (int(st["phase"] == "co-execution"), 1, ">="),
    }
