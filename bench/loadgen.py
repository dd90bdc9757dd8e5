"""The one traffic generator: it reads a mix's data file and the seed.

A mix (``bench/traffic/<name>.json``) states its loop and the shapes of
its requests; the generator turns those into sizes and gaps, and puts
them in one order that is the same for every seed.  The seed fills the
prompts, so seeds change what the tokens are, never how much work there
is or when it comes: the order of long and short requests sets the
tails of an open loop, and a seed that moved them would change the work.

Keys of a mix file:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one finished) or ``"open"`` (``rate_per_s``
  Poisson arrivals over the window, whatever the system does).
* ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max"}``
  of a lognormal, clipped.  Outputs are generated to their length.
* ``pool``: how many distinct (prompt, output) sizes a closed loop
  cycles through.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Item:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    offset: float = 0.0         # open loop: due time after the window start


def lognormal_sizes(spec: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the clipped lognormal."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the exponential inter-arrival
    time at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


ORDER_SEED = 0      # the one order of sizes and gaps, for every seed


class Traffic:
    """A mix, bound to a seed and a window length."""

    def __init__(self, mix: Dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.loop = mix["loop"]
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(int(seed))
        order = np.random.default_rng(ORDER_SEED)
        if self.loop == "closed":
            n = int(mix["pool"])
        elif self.loop == "open":
            n = max(1, int(round(float(mix["rate_per_s"]) * self.seconds)))
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        self.n = n
        self._prompts = order.permutation(
            lognormal_sizes(mix["prompt_len"], n))
        self._outputs = order.permutation(
            lognormal_sizes(mix["output_len"], n))
        self._gaps = order.permutation(exponential_gaps(
            float(mix["rate_per_s"]), n)) if self.loop == "open" else None
        self._next = 0

    def _item(self, i: int, offset: float = 0.0) -> Item:
        j = i % self.n
        prompt = self.rng.integers(0, self.vocab, int(self._prompts[j]),
                                   dtype=np.int32)
        return Item(prompt, int(self._outputs[j]), offset)

    # closed loop ----------------------------------------------------------
    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def next_item(self) -> Item:
        """The next request of a closed loop, in the fixed order
        (cycling)."""
        item = self._item(self._next)
        self._next += 1
        return item

    # open loop -------------------------------------------------------------
    def schedule(self) -> List[Item]:
        """Every request of an open loop with its due offset: the gaps
        are the exponential's quantiles in the fixed order, scaled so
        that the last request is due as the window closes."""
        offsets = np.cumsum(self._gaps)
        offsets *= self.seconds / offsets[-1]
        return [self._item(i, float(offsets[i])) for i in range(self.n)]

    # shapes ------------------------------------------------------------
    def prompt_range(self):
        p = self.mix["prompt_len"]
        return int(p["min"]), int(p["max"])
