"""EventStream: the one instrumentation substrate (DESIGN.md §13).

Every counter bump and every structured lifecycle event in the engine,
executor, scheduler and benchmarks flows through one of these.  The design
constraint is the decode hot path: with no structured processor attached
the stream must cost no more than the ad-hoc ``stats[...] +=`` dicts it
replaced, so the API splits into two tiers:

* **counters** — ``inc`` / ``add`` / ``put`` update the stream's counter
  dict directly (one method call, one dict op, no allocation).  The dict
  is owned by the always-attached :class:`CountersProcessor` and *is* the
  ``engine.stats`` object — bit-compatible with the pre-event-layer
  counters by construction.
* **structured events** — guarded by the ``on`` flag at every emit site
  (``if es.on: es.emit(Evt(...))`` or an ``emit.py`` helper that folds the
  predicate in).  When no structured processor is attached, ``on`` is
  False and **no event object is ever constructed**.

``emit`` stamps ``event.ts`` from the stream's injected clock — there is
exactly one clock per stream (the serving scheduler injects its virtual
clock here once instead of special-casing ``time.perf_counter`` at every
use), and :meth:`sleep` centralizes the only behavioural difference a
virtual clock implies (never sleep real time against a frozen clock).

Processors may be attached/detached at any time; emission is serialized
by a lock because the GraphRunner thread emits completion events
concurrently with the Python thread.

The process's GC pauses and compiles (``runtime.py``) are recorded by
process-wide sources that take no lock; a stream delivers the ones that
ended since its last emit, as ``Span`` events, at the head of its next
emit, and reports their counts and seconds since it was made in
:meth:`runtime_counters`.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from repro.core.events import types as T
from repro.core.events.processors import CountersProcessor, Processor
from repro.core.events.runtime import RUNTIME, span_name


class EventStream:
    """Counter fast path + pluggable structured processors, one clock."""

    def __init__(self, counters: Optional[Dict] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.counters_proc = CountersProcessor(counters)
        self.counters: Dict = self.counters_proc.data
        self.clock = clock
        self._procs: List[Processor] = []
        self.on = False                 # any structured processor attached
        self._lock = threading.Lock()
        rt = self._rt = RUNTIME.install()
        self._rt_base = rt.counters()
        self._gc_seen, self._cc_seen = rt.gc.n, rt.compile.n

    # ------------------------------------------------------------------
    # counter tier (always on; the hot path)
    # ------------------------------------------------------------------
    def inc(self, key: str, n: int = 1) -> None:
        c = self.counters
        c[key] = c.get(key, 0) + n

    def add(self, key: str, dt: float) -> None:
        c = self.counters
        c[key] = c.get(key, 0.0) + dt

    def put(self, key: str, value) -> None:
        self.counters[key] = value

    def seed(self, defaults: Dict) -> None:
        """Register counter keys without clobbering live values (the
        scheduler seeds its keys into its engine's existing stream)."""
        for k, v in defaults.items():
            self.counters.setdefault(k, v)

    # ------------------------------------------------------------------
    # structured tier (only when a processor is attached)
    # ------------------------------------------------------------------
    def attach(self, proc: Processor) -> Processor:
        with self._lock:
            if not self._procs:
                # GC / compile spans are delivered from attach on, not
                # everything the process recorded while nobody listened
                self._gc_seen = self._rt.gc.n
                self._cc_seen = self._rt.compile.n
            self._procs.append(proc)
            self.on = True
        return proc

    def detach(self, proc: Processor) -> None:
        with self._lock:
            self._procs = [p for p in self._procs if p is not proc]
            self.on = bool(self._procs)

    def emit(self, event) -> None:
        """Deliver one event to every structured processor, after the GC
        and compile spans that ended since the last emit.  Callers guard
        with ``es.on`` so the event object exists only when someone
        listens; emitting on a stream that raced to empty is harmless."""
        with self._lock:
            rt = self._rt
            if rt.gc.n != self._gc_seen or rt.compile.n != self._cc_seen:
                self._deliver_runtime()
            event.ts = self.clock()
            for p in self._procs:
                p.process(event)

    def _deliver_runtime(self) -> None:
        """GC and compile spans since the last delivery, as ``Span``
        events stamped at their end (under a virtual clock: now)."""
        rt, real = self._rt, self.clock_is_real
        gcs, lost = rt.gc.since(self._gc_seen)
        self._gc_seen += len(gcs) + lost
        ccs, lost = rt.compile.since(self._cc_seen)
        self._cc_seen += len(ccs) + lost
        found = [(s, d, span_name("gc", g)) for s, d, g in gcs]
        found += [(s, d, span_name("compile", st)) for s, d, st in ccs]
        for s, d, name in sorted(found):
            e = T.Span(name, d)
            e.ts = s + d if real else self.clock()
            for p in self._procs:
                p.process(e)

    def runtime_counters(self) -> Dict:
        """The process's GC pauses and compiles since this stream was
        made: ``gc_pauses``, ``gc_pause_s``, ``compiles``, ``compile_s``.
        Process-wide, so they stay out of the engine's own counter dict."""
        now = self._rt.counters()
        return {k: now[k] - self._rt_base[k] for k in now}

    # ------------------------------------------------------------------
    # the injected clock
    # ------------------------------------------------------------------
    def set_clock(self, clock: Callable[[], float]) -> None:
        self.clock = clock

    @property
    def clock_is_real(self) -> bool:
        return self.clock is time.perf_counter

    def sleep(self, seconds: float) -> None:
        """Wait for ``seconds`` of *this stream's* time.  Under the real
        clock that is a bounded real sleep; under an injected (virtual)
        clock real sleeping would hang the caller against frozen time, so
        yield and let the caller re-poll."""
        time.sleep(seconds if self.clock_is_real else 0)

    def close(self) -> None:
        with self._lock:
            procs, self._procs = self._procs, []
            self.on = False
        for p in procs:
            p.close()
