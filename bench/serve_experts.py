"""A serving cell of a model with dropless expert layers: ``serve.py``'s
cell, whose record also carries the scheduler's expert counters.

``serve.py``'s window keeps four of the scheduler's stats in the record
(phase, retraces, steady entries, compiles).  This kind of cell runs the
same set-up, window and comparison, and adds after the window what the
scheduler reports of its experts and its cache (``expert_tokens``,
``expert_dropped``, ``experts_held``, ``kv_append_leaves``) to
``rec["stats"]``, where a metric reads them.  A configuration names it
with ``"cell": "serve_experts"``.
"""

from __future__ import annotations

import serve

STATS = ("expert_tokens", "expert_dropped", "experts_held",
         "kv_append_leaves")


class ExpertServeCell(serve.ServeCell):
    def window(self, tracer=None) -> None:
        super().window(tracer)
        st = self.sch.stats
        self.stats.update({k: st[k] for k in STATS if k in st})


def measure(cfg, mix, family, **kw):
    """``serve.measure`` with :class:`ExpertServeCell` as its cell."""
    plain, serve.ServeCell = serve.ServeCell, ExpertServeCell
    try:
        return serve.measure(cfg, mix, family, **kw)
    finally:
        serve.ServeCell = plain
