"""Model FLOPs of the real prompt and generated tokens of the window
over the window times the peak."""

import readers


def read(rec):
    return readers.serve_mfu(rec)
