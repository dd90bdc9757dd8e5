"""Milliseconds of GC pauses, all generations, that end in the window
[t0, end of the trace], from the program's record of its host stalls."""

import program_spans


def read(rec):
    return program_spans.gc_pause_ms(rec)
