"""The device a run measures, and its peaks.

A run needs TPU chips: with anything else, or fewer chips than the cell
asks for, it stops before printing a result.  The peaks come from
``peaks.json``, keyed by ``device_kind``; a kind the table lacks is an
error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


class NoDevice(SystemExit):
    def __init__(self, what: str):
        super().__init__(f"bench: {what}")


def peaks(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> Dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in "
                       f"{os.path.basename(path)} (known: {sorted(table)})")
    return table[kind]


def chips(need: int):
    """The first ``need`` TPU devices, or NoDevice."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees {len(devs)} {d.platform} "
                       f"device(s) ({d.device_kind!r})")
    if len(devs) < need:
        raise NoDevice(f"{need} TPU chips needed, JAX sees {len(devs)}")
    return devs[:need]


def describe(devs) -> Dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no such count)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
