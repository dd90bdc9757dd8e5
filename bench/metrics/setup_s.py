"""Set-up: process start to the first request of the window (weights,
the scheduler, warm-up of every shape the traffic uses, compiles)."""

def read(rec):
    return rec["setup_s"]
