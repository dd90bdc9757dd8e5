"""Model FLOPs of the real prompt tokens of a prefill over its device
time times the peak."""

import readers


def read(rec):
    return readers.prefill_mfu(rec)
