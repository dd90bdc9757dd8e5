#!/usr/bin/env python3
"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 bench/control.py --workload granite2b-decode-backlog \
        --seed 11 --seconds 50

It runs the cell as a run does (set-up, the window at the cell's own
load) and then reads, over the same sample of served requests, the
program's widest logit gap and the control's: the plain reference
computed in float8, read at every position of the same prompts and
served tokens.  The program's readings over a dozen seeds give the
limit's lower end, the control's its upper end.  Prints one JSON line.
One seed to a process: the program keeps device memory after its
scheduler is closed, so a third cell in one process runs out of it.
The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import sys                                                  # noqa: E402

import run                                                  # noqa: E402
import check                                                # noqa: E402
import device                                               # noqa: E402
import spec                                                 # noqa: E402
from serve import ServeCell                                 # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    bench = spec.Benchmark(run.ROOT)
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    family = bench.family(cfg["family"])
    devs = device.chips(int(cell["chips"]))
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seed = args.seed
    c = ServeCell(cfg, mix, family, seed, args.seconds, run.log)
    c.setup()
    c.window(None)
    reqs = c.requests
    c.close()
    picked = check.sample([r for r in reqs if len(r.tokens) == r.max_new],
                          seed, **mix["check_sample"])
    g = check.served_gaps(family, cfg, seed, picked,
                          cfg["serving"]["max_len"], control=True)
    g.update(seed=seed, seconds=round(time.perf_counter() - T_START, 1),
             limit=cfg["check"]["max_logit_gap"])
    print(json.dumps(g), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
