"""Process-wide host stalls: GC pauses, compiles and GraphRunner closures.

A stall of the Python host stops the chip: the scheduler keeps one step in
flight, so a garbage collection holding the GIL, a compile, or a slow
closure on the GraphRunner thread leaves the device idle until the host
moves again.  This module keeps what it takes to put such a stall down to
its cause, in one :class:`Runtime` per process (``RUNTIME``), installed
once by the first :class:`~repro.core.events.EventStream` and never per
engine:

* a ``gc.callbacks`` source: every collection's pause, with its
  generation, and a ``terra.gc.gen<N>`` profiler span around it while a
  profiler session collects;
* a ``jax.monitoring`` duration listener: each stage of a compile (trace,
  lowering, backend compile, persistent-cache load).  JAX reports a stage
  only once it has ended, so these spans are records only, start = end −
  duration, with no profiler span;
* the GraphRunner's closures (``GraphRunner._run_one``): label, start and
  wall time of each.

Each source writes its spans into a bounded :class:`Ring` on the
``time.perf_counter`` clock.  The GC callback can fire on any thread at
any allocation — inside ``EventStream.emit`` under the stream's lock, or
inside the runner's condition variable — so it takes no lock and calls no
processor; the JAX listener runs on the compiling thread under JAX's own
locks, so it only records too.  Streams deliver the GC and compile spans
to their processors on their next emit (``EventStream``), and a reader
that comes after the fact (a benchmark) takes them from the rings by
time (:meth:`Runtime.between`).
"""

from __future__ import annotations

import array
import gc
import threading
import time
from typing import List, Tuple

import jax

from repro.core.events import spans

# jax.monitoring duration events of one compile, by stage; a persistent
# cache load happens inside the backend-compile stage
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
NESTED_STAGES = ("cache_load",)

Record = Tuple[float, float, object]    # (start, seconds, label)


def span_name(source: str, label) -> str:
    """The span name of a record: ``terra.gc.gen2``,
    ``terra.compile.backend``."""
    return (f"{spans.PREFIX}gc.gen{label}" if source == "gc"
            else f"{spans.PREFIX}{source}.{label}")


class Ring:
    """The last ``size`` spans ``(start, seconds, label)`` of one source,
    with the count and seconds of every span ever written.  A ring has one
    writer at a time: the collector for GC, a lock for the others."""

    def __init__(self, size: int):
        self.size = size
        self._start = array.array("d", bytes(8 * size))
        self._dur = array.array("d", bytes(8 * size))
        self._label: List[object] = [None] * size
        self.n = 0              # spans written, ever
        self.seconds = 0.0      # their seconds, ever

    def push(self, start: float, seconds: float, label) -> None:
        i = self.n % self.size
        self._start[i] = start
        self._dur[i] = seconds
        self._label[i] = label
        self.seconds += seconds
        self.n += 1

    def since(self, n0: int) -> Tuple[List[Record], int]:
        """The spans written after the first ``n0`` that the ring still
        holds, oldest first, and the count it no longer holds."""
        n1 = self.n
        lo = max(n0, n1 - self.size)
        out = []
        for k in range(lo, n1):
            i = k % self.size
            out.append((self._start[i], self._dur[i], self._label[i]))
        return out, lo - n0


class Runtime:
    """The process's GC, compile and GraphRunner span sources."""

    def __init__(self):
        self.gc = Ring(1 << 15)
        self.compile = Ring(1 << 12)
        self.runner = Ring(1 << 15)
        self.compiles = 0               # executables built or loaded
        self.compile_s = 0.0            # seconds of the outer stages
        self._compile_lock = threading.Lock()
        self._runner_lock = threading.Lock()
        self._gc_t0 = None
        self._gc_span = None
        self._gc_names = [span_name("gc", g) for g in range(3)]
        self.installed = False
        self._install_lock = threading.Lock()

    def install(self) -> "Runtime":
        """Install the GC callback and the compile listener, once per
        process; later calls return at once."""
        if self.installed:
            return self
        with self._install_lock:
            if self.installed:
                return self
            gc.callbacks.append(self._on_gc)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_compile)
            self.installed = True
        return self

    # ------------------------------------------------------------------
    # sources
    # ------------------------------------------------------------------
    def _on_gc(self, phase: str, info) -> None:
        if phase == "start":
            if spans.profiling():
                sp = spans.span(self._gc_names[info["generation"]])
                sp.__enter__()
                self._gc_span = sp
            self._gc_t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        t0, self._gc_t0 = self._gc_t0, None
        sp, self._gc_span = self._gc_span, None
        if sp is not None:
            sp.__exit__(None, None, None)
        if t0 is not None:
            self.gc.push(t0, t1 - t0, info["generation"])

    def _on_compile(self, event: str, duration_secs: float, **_) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is None:
            return
        t1 = time.perf_counter()
        with self._compile_lock:
            self.compile.push(t1 - duration_secs, duration_secs, stage)
            if stage == "backend":
                self.compiles += 1
            if stage not in NESTED_STAGES:
                self.compile_s += duration_secs

    def runner_span(self, start: float, seconds: float, label: str) -> None:
        """One GraphRunner closure ran from ``start`` for ``seconds``."""
        with self._runner_lock:
            self.runner.push(start, seconds, label)

    # ------------------------------------------------------------------
    # readers
    # ------------------------------------------------------------------
    def counters(self) -> dict:
        """Process totals of the counters streams report."""
        return {"gc_pauses": self.gc.n, "gc_pause_s": self.gc.seconds,
                "compiles": self.compiles, "compile_s": self.compile_s}

    def between(self, t0: float, t1: float) -> List[Tuple[str, float, float,
                                                        object]]:
        """Every span the rings hold that ends within ``[t0, t1]``, as
        ``(source, start, seconds, label)``; ``source`` is ``"gc"``
        (label: generation), ``"compile"`` (label: stage) or ``"runner"``
        (label: the submitter's).  None if a ring no longer holds the
        start of the stretch, so a sum over it would come out short."""
        out = []
        for source in ("gc", "compile", "runner"):
            ring = getattr(self, source)
            held, lost = ring.since(0)
            if lost and held and held[0][0] + held[0][1] > t0:
                return None
            out.extend((source, s, d, lab) for s, d, lab in held
                       if t0 <= s + d <= t1)
        return out


RUNTIME = Runtime()
