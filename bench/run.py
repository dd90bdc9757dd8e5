#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell
asks for (BENCHMARK.json).  Everything about the cell is found by name:
its configuration file, its traffic mix (``bench/traffic/<mix>.json``),
its model family (``bench/families/<family>.py``), its kind of cell
(the module the configuration's ``"cell"`` names, e.g. ``serve.py``,
whose ``measure`` makes the run's record) and its metrics
(``bench/metrics/<metric>.py``).

A run builds the weights on the device from the seed, warms up every
shape the traffic uses (set-up), measures for ``--seconds``, then checks
what the timed path served against the plain reference.  With
``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it also traces the last seconds of the window with the
profiler and reports the cell's per-layer metrics and a breakdown.  The
numbers compared, each with its limit, come last on standard error and
last in the result line.  Without a TPU it exits non-zero and prints no
result.  ``--out DIR`` also writes the run's record and the first
quarter second of the trace there.
"""

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import math                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import device                                               # noqa: E402
import spec                                                 # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def passed(value, limit, rel) -> bool:
    return value <= limit if rel == "<=" else value >= limit


def _num(x):
    return x if isinstance(x, int) else float(x)


def result(bench, cell, rec, *, trace: bool, devs):
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(cell["name"], kind):
        v = bench.reader(m["name"])(rec)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = int(rec["memory_peak_bytes"])
    line = {"correct": all(passed(*c) for c in rec["checks"].values()),
            "attempted": int(rec["attempted"]), "failed": int(rec["failed"]),
            "metrics": metrics, "device": dev}
    tr = rec["trace"]
    if trace and tr is not None:
        dev["busy_s"] = float(tr["busy_s"])
        dev["window_s"] = float(tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        line["idle_by_host_s"] = tr["idle_by_host"]
    line["checks"] = {k: {"value": _num(v), "limit": _num(lim)}
                      for k, (v, lim, _rel) in rec["checks"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for the record and a trace excerpt")
    args = ap.parse_args(argv)

    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    family = bench.family(cfg["family"])

    devs = device.chips(int(cell["chips"]))
    pk = device.peaks(devs[0].device_kind)
    import jax
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{cell['name']}: {device.describe(devs)}, compile cache {cache}")

    if args.out:
        os.makedirs(args.out, exist_ok=True)
    kind = importlib.import_module(cfg["cell"])
    rec = kind.measure(cfg, mix, family, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       devs=devs, peaks=pk, t_start=T_START,
                       out_dir=args.out, say=log)
    line = result(bench, cell, rec, trace=bool(args.trace), devs=devs)
    notes = rec.get("notes", {})
    log(f"setup_s {rec['setup_s']:.3f}, requests {line['attempted']}, "
        f"stats {rec['stats']}, gaps {rec['gaps']}, notes {notes}")
    if rec["trace"] is not None:
        tr = rec["trace"]
        log(f"trace: window {tr['window_s']:.3f} s, busy {tr['busy_s']:.3f}"
            f" s, programs {tr['programs']}, idle by host "
            f"{tr['idle_by_host']}")
    if args.out:
        with open(os.path.join(args.out, "record.json"), "w") as f:
            json.dump({"line": line, "notes": notes,
                       "trace": rec["trace"], "stats": rec["stats"],
                       "gaps": rec["gaps"]}, f, indent=1, default=str)
    for name, (v, lim, rel) in rec["checks"].items():
        print(f"check {name}: {v} {rel} {lim}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
