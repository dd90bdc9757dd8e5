"""95th percentile of the gap between consecutive tokens of a request,
over every gap of the requests due in the window.  After a stall of
the host the queued prefills hold up decoding for a while, so a run
with one reads 10-40 % higher than one without (PERF.md section 2)."""

import readers


def read(rec):
    v = readers.p95(readers.itl_s(rec))
    return None if v is None else 1e3 * v
