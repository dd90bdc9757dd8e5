"""95th percentile of time to first token over every request due in
the window, timed from when it was due.  A stall of the host lands a
few requests in this tail, so it swings from run to run by more than a
bound can hold (PERF.md section 2); the cell's end-to-end TTFT is the
median."""

import readers


def read(rec):
    v = readers.p95(readers.ttft_s(rec))
    return None if v is None else 1e3 * v
