"""Shared set-up of the benchmark's tests: paths, and a cell cut down to
a size the CPU runs in seconds."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def benchmark():
    return spec.Benchmark(ROOT)


def tiny_config(vocab: int = 512):
    """granite-3-2b-serve's file at smoke widths (CPU only)."""
    b = benchmark()
    cfg = b.config("granite-3-2b-serve")
    cfg.update(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=vocab)
    cfg["serving"] = {"pool": "dense", "max_slots": 4, "max_len": 128,
                      "prefill_batch_cap": 2}
    cfg["check"] = {"max_logit_gap": 0.1}
    return cfg


def tiny_mix(name: str):
    mix = benchmark().traffic(name)
    mix["prompt_len"] = {"median": 12, "sigma": 0.5, "min": 6, "max": 24}
    mix["output_len"] = {"median": 16, "sigma": 0.5, "min": 6, "max": 32}
    if mix["loop"] == "closed":
        mix["clients"] = 8
    else:
        mix["rate_per_s"] = 6.0
    mix["check_sample"] = {"min_served_tokens": 64, "max_requests": 6}
    return mix


def load_json(path):
    with open(path) as f:
        return json.load(f)
