"""Host time of one decode dispatch (StepDispatch events), mean."""

import readers


def read(rec):
    return readers.mean_dispatch_us(rec, "decode")
