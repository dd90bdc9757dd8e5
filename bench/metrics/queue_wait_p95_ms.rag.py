"""95th percentile of due time to admission (RequestAdmit events)."""

import readers


def read(rec):
    return readers.queue_wait_p95_ms(rec)
