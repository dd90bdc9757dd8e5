"""Milliseconds of compiles that end in the window [t0, end of the
trace], from the program's record; 0.0 where there were none."""

import program_spans


def read(rec):
    return program_spans.compile_ms(rec)
