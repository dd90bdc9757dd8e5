"""Observability layer: timeline export, live serving metrics
(DESIGN.md §15).

Built entirely on top of ``core/events/`` — nothing here touches the
executor hot path.  Device time comes from a ``jax.profiler`` trace, on
whose clock the program's ``terra.*`` host spans land (DESIGN.md §13);
this package consumes the structured event stream:

* :mod:`repro.obs.metrics` — streaming log-bucketed histograms and the
  :class:`MetricsRegistry` (Prometheus text exposition + JSON snapshot),
  updated online by :class:`MetricsProcessor` from serving events.
* :mod:`repro.obs.trace_viewer` — :class:`TraceViewerExporter`, a
  processor that renders the event stream as Chrome/Perfetto trace-event
  JSON: engine tracks (imperative Python, walker, GraphRunner, runtime,
  scheduler) plus per-request lanes with flow events linking each
  request's lifecycle and each divergence's recovery chain.
* :mod:`repro.obs.report` — the ``python -m repro.obs.report`` CLI:
  per-segment host tables, the divergence/replay audit, selector
  distributions, a metrics snapshot, and the ``.trace.json`` export.
* :mod:`repro.obs.http` — stdlib-only optional HTTP scrape endpoint
  serving ``/metrics`` (Prometheus text) and ``/metrics.json``.
"""

from repro.obs.metrics import (GROWTH, Histogram, MetricsProcessor,
                               MetricsRegistry, counters_table)
from repro.obs.trace_viewer import TraceViewerExporter, chrome_trace

__all__ = ["GROWTH", "Histogram", "MetricsRegistry", "MetricsProcessor",
           "counters_table", "TraceViewerExporter", "chrome_trace"]
