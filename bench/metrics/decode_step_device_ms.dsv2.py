"""Device time of one decode program, from the trace."""

import readers


def read(rec):
    return readers.step_device_ms(rec, "decode")
