"""The span primitive: host spans on the profiler's own clock.

``with span("terra.sched.harvest_wait"):`` opens a
``jax.profiler.TraceAnnotation`` while a profiler session is collecting,
so the span lands on the trace's host line beside the device operations
and a device idle gap can be put down to what the host was doing in it.
Outside a session it returns a shared do-nothing context: the site costs
one check and builds nothing (DESIGN.md §13).  Every name starts with
``terra.``, so a reader of the trace keeps the program's spans by prefix.

The structured half of a span (an event with its duration, for attached
processors) is the existing event of the site — ``StepDispatch``,
``StepHarvest``, ``SchedulerIdle``, ``RunnerComplete`` — emitted through
the site's ``EventStream`` as before.
"""

from __future__ import annotations

import contextlib

import jax

PREFIX = "terra."

_NULL = contextlib.nullcontext()


def _is_enabled():
    """Whether a profiler session is collecting host spans.  The one use
    of jaxlib's private ``TraceMe.is_enabled`` (0.1 µs, against 0.5 µs
    for an unguarded annotation); a jaxlib without it counts as always
    collecting, which costs time and changes no result."""
    try:
        from jax._src.lib import _profiler
        return _profiler.TraceMe.is_enabled
    except (ImportError, AttributeError):     # pragma: no cover - jaxlib
        return lambda: True


profiling = _is_enabled()


def span(name: str):
    """A ``TraceAnnotation`` named ``name`` while the profiler collects,
    else a shared null context."""
    if profiling():
        return jax.profiler.TraceAnnotation(name)
    return _NULL
