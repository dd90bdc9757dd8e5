"""Model FLOPs of the real prompt tokens of a prefill (routed pairs
of the held experts only: top_k x held / experts expert FFNs a token)
over its device time times the peak."""

import readers


def read(rec):
    return readers.prefill_mfu(rec)
