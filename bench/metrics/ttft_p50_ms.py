"""Median time to first token over every request due in the window,
timed from when it was due."""

import readers


def read(rec):
    v = readers.p50(readers.ttft_s(rec))
    return None if v is None else 1e3 * v
