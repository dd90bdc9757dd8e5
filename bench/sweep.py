#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the tree
sustains, by a sweep on the chip.

    python3 bench/sweep.py --workload granite2b-rag-steady --rates 3,4,5,6,7 \
        --seconds 20 --seed 1

One process sets the cell up once and then runs its mix at each rate in
turn, one window per rate, each drained before the next (at most a
minute).  A rate is sustained when at least 0.98 of the requests due in
the window finished, and the backlog (requests due and still without a
first token) does not grow over the window: its mean over the last third
at most one request above the first third's.  The knee is the highest
rate sustained with every lower rate sustained too, so the sweep stops
at the first rate that is not; the cell's mix file carries 0.8 x the
knee as its ``rate_per_s``.  Prints one JSON line per rate and a last
line with the knee.
"""

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import sys                                                  # noqa: E402

import numpy as np                                          # noqa: E402

import run                                                  # noqa: E402
import device                                               # noqa: E402
import readers                                              # noqa: E402
import spec                                                 # noqa: E402
from loadgen import Traffic                                 # noqa: E402
from serve import ServeCell                                 # noqa: E402


def backlog(reqs, t):
    """Requests due by ``t`` that had no first token yet at ``t``."""
    return sum(1 for r in reqs
               if r.due <= t and (not r.times or r.times[0] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = spec.Benchmark(run.ROOT)
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    family = bench.family(cfg["family"])
    devs = device.chips(int(cell["chips"]))
    device.peaks(devs[0].device_kind)
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    c = ServeCell(cfg, mix, family, args.seed, args.seconds, run.log)
    c.setup()
    knee, broken = None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        c.traffic = Traffic(dict(mix, rate_per_s=rate), args.seed,
                            args.seconds, cfg["vocab_size"])
        c.tracked, c.requests, c.t_end = {}, [], float("inf")
        c.window(None)
        reqs, t0, t1 = c.requests, c.t0, c.t_end
        due = [r for r in reqs if t0 <= r.due <= t1]
        done = sum(1 for r in due if len(r.tokens) == r.max_new)
        depth = [backlog(reqs, t) for t in np.linspace(t0, t1, 60)]
        first = float(np.mean(depth[:20]))
        last = float(np.mean(depth[-20:]))
        rec = {"t0": t0, "t_end": t1, "t_stop": c.t_stop, "requests": reqs}
        ttft = readers.p95(readers.ttft_s(rec))
        ok = done >= 0.98 * len(due) and last <= first + 1.0
        broken = broken or not ok
        if ok and not broken:
            knee = rate
        print(json.dumps({"rate_per_s": rate, "due": len(due),
                          "finished": done,
                          "backlog_first_third": first,
                          "backlog_last_third": last,
                          "ttft_p95_ms": None if ttft is None
                          else 1e3 * ttft,
                          "drain_s": c.t_stop - t1,
                          "sustained": ok}), flush=True)
        if broken:
            break
    c.close()
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
