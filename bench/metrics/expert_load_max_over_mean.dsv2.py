"""Routed pairs of the busiest held expert over the mean of the held
experts, from the scheduler's device counter (``expert_tokens`` in the
record's stats, per held expert, decode and prefill).  The counter runs
from the scheduler's start, so this is over the whole run, warm-up
included.  1.0 is an even load; None where the record has no counter."""


def read(rec):
    tokens = rec["stats"].get("expert_tokens")
    if not tokens or sum(tokens) == 0:
        return None
    return max(tokens) / (sum(tokens) / len(tokens))
