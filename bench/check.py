"""The comparison that decides ``correct`` for a served model.

After the window, a sample of the requests the program finished, drawn
from the seed and holding the one with the most served tokens, goes
through the plain reference once: each prompt followed by the tokens
the program served.  For every served token the reference gives the gap
by which that token's logit lies below its own best logit.  A sound
bf16 program serves the reference's best token or one within rounding
of it; the widest gap over the sample is compared with the limit that
the configuration file states.

The tokens compared are those the timed path delivered through the
streaming callback: prefill's first token and then decoding through the
pool's KV cache, at the window's batch, lengths and slot occupancy.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(finished: Sequence, seed: int, min_served_tokens: int,
           max_requests: int) -> List:
    """The finished request with the most served tokens, then others in
    seed order until ``min_served_tokens`` tokens or ``max_requests``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -len(finished[i].tokens))
    rest = list(np.random.default_rng(int(seed) + 1).permutation(order[1:]))
    picked = [order[0]]
    total = len(finished[order[0]].tokens)
    for i in rest:
        if total >= min_served_tokens or len(picked) >= max_requests:
            break
        picked.append(int(i))
        total += len(finished[i].tokens)
    return [finished[i] for i in picked]


def rows(picked: Sequence, length: int):
    """tokens/targets [n, length]: each prompt followed by the served
    tokens but the last; ``targets[t]`` is the token served after
    position t (-1 elsewhere)."""
    n = len(picked)
    tokens = np.zeros((n, length), np.int32)
    targets = np.full((n, length), -1, np.int32)
    for r, tr in enumerate(picked):
        p, out = np.asarray(tr.prompt, np.int32), tr.tokens
        seq = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        tokens[r, :len(seq)] = seq
        targets[r, len(p) - 1:len(p) - 1 + len(out)] = out
    return tokens, targets


def served_gaps(family, cfg: Dict, seed: int, picked: Sequence,
                length: int, control: bool = False) -> Dict:
    tokens, targets = rows(picked, length)
    gaps, ctl = family.reference_gaps(cfg, seed, tokens, targets,
                                      control=control)
    out = {"requests": len(picked), "tokens": int(np.sum(targets >= 0))}
    out.update(_summary(gaps, ""))
    if control:
        out.update(_summary(ctl, "control_"))
    return out


def _summary(gaps, prefix: str) -> Dict:
    """Widest and mean gap, and the share of served tokens that are not
    the reference's first choice."""
    g = gaps[~np.isnan(gaps)] if gaps.size else gaps
    if not g.size:
        return {prefix + "max_logit_gap": 0.0}
    return {prefix + "max_logit_gap": float(g.max()),
            prefix + "mean_logit_gap": float(g.mean()),
            prefix + "not_first_share": float(np.mean(g > 0))}
