"""Mean GraphRunner-thread time of the decode step's closures (steady,
segment, chain) that end in the window [t0, end of the trace]."""

import program_spans


def read(rec):
    return program_spans.runner_us_per_decode(rec)
