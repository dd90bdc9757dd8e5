"""Hot-path benchmark: steady-state per-iteration Python overhead.

Measures, per benchmark program, what the skeleton phase costs the Python
thread each iteration once the engine is in steady-state co-execution:

* ``py_stall_us``    — time blocked at Output Fetching / per-value fences
                       (``engine.stats["py_stall_time"]``),
* ``dispatch_us``    — Python-thread time spent in segment dispatch
                       (``engine.stats["dispatch_time"]``),
* ``py_overhead_us`` — their sum: the interpreter-overhead class the paper's
                       speedup claim depends on keeping off the critical
                       path (ISSUE 2; JANUS / TF-Eager interpreter gap),
* GraphRunner occupancy (``runner_exec_time`` / ``runner_stall_time``) and
  the hot-path counters (``walker_fast_hits``, ``feeds_defaulted``).

Per-iteration samples are collected individually; the headline statistic is
the **median** (steady-state cost — the mean is dominated by GC pauses and
OS scheduling tails on a shared machine, which hit pre- and post-change
code alike).  Each cell runs ``--rounds`` times in-process and keeps the
round with the lowest median overhead.

The ``shape_flip`` section (ISSUE 3) drives serving decode with
alternating batch sizes through the shape-keyed TraceGraph families and
asserts zero ``retraces`` / ``segments_recompiled`` across the flips after
one trace+compile per shape class.

Writes ``BENCH_hotpath.json``.  If a baseline file exists
(``benchmarks/baseline_hotpath.json`` — measured at the pre-change commit
with this same methodology), a per-program and mean reduction is reported;
the ISSUE 2 gate is ``mean_reduction_pct >= 25`` over the fig5 programs.

Usage:
    python -m benchmarks.bench_hotpath [--smoke] [--out BENCH_hotpath.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.programs import NON_CONVERTIBLE, REGISTRY
from repro.core import function as terra_function
from repro.launch import compile_cache

DEFAULT_PROGRAMS = ["resnet", "gpt2", "bert_qa", "fasterrcnn"]
BASELINE_PATH = os.path.join(os.path.dirname(__file__),
                             "baseline_hotpath.json")


def measure_once(name: str, warmup: int, iters: int) -> dict:
    step, _ = REGISTRY[name]("terra")
    tf = terra_function(step)
    for i in range(warmup):
        tf(i)
    tf.wait()
    eng = tf.engine
    stats = eng.stats
    base_counters = {k: stats[k] for k in
                     ("walker_fast_hits", "feeds_defaulted",
                      "segments_dispatched", "replays")}
    base_runner = (stats["runner_exec_time"], stats["runner_stall_time"])
    samples = []
    prev = (stats["py_stall_time"], stats["dispatch_time"])
    for i in range(warmup, warmup + iters):
        t0 = time.perf_counter()
        tf(i)
        wall = time.perf_counter() - t0
        cur = (stats["py_stall_time"], stats["dispatch_time"])
        samples.append((wall, cur[0] - prev[0], cur[1] - prev[1]))
        prev = cur
    tf.wait()
    a = np.asarray(samples) * 1e6
    overhead = a[:, 1] + a[:, 2]
    out = {
        "iters": iters,
        "phase": tf.phase,
        "wall_us_median": float(np.median(a[:, 0])),
        "wall_us_mean": float(a[:, 0].mean()),
        "py_stall_us_median": float(np.median(a[:, 1])),
        "dispatch_us_median": float(np.median(a[:, 2])),
        "py_overhead_us_median": float(np.median(overhead)),
        "py_overhead_us_mean": float(overhead.mean()),
        "runner_exec_us_per_iter":
            (stats["runner_exec_time"] - base_runner[0]) / iters * 1e6,
        "runner_stall_us_per_iter":
            (stats["runner_stall_time"] - base_runner[1]) / iters * 1e6,
    }
    for k, v in base_counters.items():
        out[k] = stats[k] - v
    tf.close()
    return out


def measure(name: str, warmup: int, iters: int, rounds: int) -> dict:
    best = None
    for _ in range(rounds):
        r = measure_once(name, warmup, iters)
        if best is None or (r["py_overhead_us_median"]
                            < best["py_overhead_us_median"]):
            best = r
    return best


def measure_shape_flip(flips: int = 50, sizes=(4, 8)) -> dict:
    """Serving decode with alternating batch sizes (ISSUE 3 acceptance):
    after one trace + compile per shape class, every batch-size flip must
    be a TraceGraph-family lookup — zero retraces, zero segment
    recompiles, zero divergences across ``flips`` flips."""
    import jax
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=48)
    rng = np.random.RandomState(0)

    def run_batch(B):
        reqs = [Request(prompt=rng.randint(0, cfg.vocab, 8).astype(np.int32),
                        max_new_tokens=4) for _ in range(B)]
        t0 = time.perf_counter()
        engine.run_batch(reqs)
        return time.perf_counter() - t0

    for B in sizes:                     # warmup: trace+compile each class
        for _ in range(2):
            run_batch(B)
    st = engine.terra.stats
    eng = engine.terra._tf.engine
    base = (st["retraces"], eng.seg_cache.misses, st["replays"])
    times = [run_batch(sizes[i % len(sizes)]) for i in range(flips)]
    out = {
        "sizes": list(sizes), "flips": flips,
        "retraces": st["retraces"] - base[0],
        "segments_recompiled": eng.seg_cache.misses - base[1],
        "replays": st["replays"] - base[2],
        "families": st["families"],
        "family_switches": st["family_switches"],
        "batch_wall_ms_median": float(np.median(times) * 1e3),
        "phase": engine.terra.phase,
    }
    engine.terra.close()
    assert out["phase"] == "co-execution", "shape-flip never reached skeleton"
    assert out["retraces"] == 0, \
        f"shape flips caused {out['retraces']} retraces (want 0)"
    assert out["segments_recompiled"] == 0, \
        f"shape flips recompiled {out['segments_recompiled']} segments"
    print(f"shape_flip: {flips} flips over batch sizes {list(sizes)}: "
          f"retraces={out['retraces']} segments_recompiled="
          f"{out['segments_recompiled']} replays={out['replays']} "
          f"median batch wall {out['batch_wall_ms_median']:.1f}ms",
          flush=True)
    return out


# ==========================================================================
# --passes ablation (ISSUE 4): symbolic pass pipeline on vs off
# ==========================================================================

def _ablation_workloads():
    """REGISTRY programs plus pass-targeted workloads.  The synthetic ones
    model the async-logging / discarded-metrics patterns the pipeline
    exists for: scalar probes read late (coalescible boundaries), probe
    chains nobody reads (dead ops), repeated subexpressions over variable
    state (CSE) and iteration-constant numpy inputs (feed folding)."""
    import numpy as np
    from repro.core import Variable, ops

    def async_logging(_variant):
        rng = np.random.RandomState(11)
        w1 = Variable((rng.randn(64, 64) * 0.1).astype(np.float32), "al_w1")
        w2 = Variable((rng.randn(64, 64) * 0.1).astype(np.float32), "al_w2")
        norm = np.full((), 1.0 / 64.0, np.float32)   # constant -> folds

        def step(i):
            r = np.random.RandomState(1000 + i)
            x = r.randn(16, 64).astype(np.float32)
            h1 = ops.relu(ops.matmul(x, w1.read()))
            s1 = ops.reduce_sum(ops.mul(ops.reduce_mean(h1), norm))
            h2 = ops.relu(ops.matmul(h1, w2.read()))
            s2 = ops.reduce_sum(ops.mul(ops.reduce_mean(h2), norm))
            out = ops.reduce_sum(h2)
            # telemetry probes read AFTER all graph work is recorded: the
            # boundaries they cut are pure dispatch overhead
            logs = (float(s1), float(s2))
            return float(out) + 0.0 * sum(logs)
        return step, None

    def dead_metrics(_variant):
        rng = np.random.RandomState(12)
        w = Variable((rng.randn(64, 64) * 0.1).astype(np.float32), "dm_w")

        def step(i):
            r = np.random.RandomState(2000 + i)
            x = r.randn(16, 64).astype(np.float32)
            h = ops.relu(ops.matmul(x, w.read()))
            # discarded diagnostics: never fetched, never assigned
            _ = ops.reduce_max(ops.abs_op(ops.mul(h, 3.0)))
            _ = ops.reduce_mean(ops.square(h))
            # duplicate subexpression over variable state (CSE)
            a = ops.mul(w.read(), 2.0)
            b = ops.mul(w.read(), 2.0)
            probe = ops.reduce_sum(ops.sub(a, b))
            out = ops.reduce_sum(h)
            p = float(probe)                   # late read -> coalescible
            return float(out) + 0.0 * p
        return step, None

    wl = {name: REGISTRY[name] for name in DEFAULT_PROGRAMS + ["bert_cls"]}
    wl["async_logging"] = async_logging
    wl["dead_metrics"] = dead_metrics
    return wl


def _measure_passes_mode(make, mode: str, warmup: int, iters: int) -> dict:
    step, _ = make("terra")
    tf = terra_function(step, optimize=mode)
    values = [float(np.asarray(tf(i))) for i in range(warmup)]
    tf.wait()
    stats = tf.engine.stats
    base_seg = stats["segments_dispatched"]
    walls = []
    for i in range(warmup, warmup + iters):
        t0 = time.perf_counter()
        values.append(float(np.asarray(tf(i))))
        walls.append(time.perf_counter() - t0)
    tf.wait()
    assert tf.phase == "co-execution", f"{mode} run never converted"
    result = {
        "segments_per_iter":
            (stats["segments_dispatched"] - base_seg) / iters,
        "iter_wall_us_median": float(np.median(walls) * 1e6),
        "values": values,
        "counters": {k: stats[k] for k in
                     ("nodes_eliminated", "cse_hits", "feeds_folded",
                      "segments_coalesced", "kernels_substituted",
                      "fold_divergences", "replays")},
    }
    tf.close()
    return result


def measure_passes(warmup: int, iters: int, rounds: int = 3) -> dict:
    """Run every ablation workload with the pass pipeline on ("all") and
    off ("none"); emit per-workload segments/iter, pass counters and
    median iteration wall time, and FAIL if any workload's fetched values
    differ between the modes (the pipeline is semantics-preserving by
    contract).  Wall medians keep the best of ``rounds`` alternating
    in-process rounds — the same tail-suppression methodology as the
    headline benchmark (module docstring)."""
    out = {}
    fewer_segments = []
    for name, make in _ablation_workloads().items():
        modes = {}
        for r in range(rounds):
            order = ("all", "none") if r % 2 == 0 else ("none", "all")
            for mode in order:
                m = _measure_passes_mode(make, mode, warmup, iters)
                best = modes.get(mode)
                if best is not None:
                    m["values"] = best["values"]    # deterministic per seed
                    if m["iter_wall_us_median"] > best["iter_wall_us_median"]:
                        m = best
                modes[mode] = m
        va, vn = modes["all"].pop("values"), modes["none"].pop("values")
        if not np.allclose(va, vn, rtol=1e-4, atol=1e-5):
            bad = int(np.argmax(~np.isclose(va, vn, rtol=1e-4, atol=1e-5)))
            raise AssertionError(
                f"--passes ablation: {name} fetched values differ between "
                f"optimize=all and optimize=none at iteration {bad}: "
                f"{va[bad]} vs {vn[bad]}")
        delta = (modes["none"]["segments_per_iter"]
                 - modes["all"]["segments_per_iter"])
        if delta > 0:
            fewer_segments.append(name)
        out[name] = {
            "all": modes["all"], "none": modes["none"],
            "segments_per_iter_delta": delta,
            "wall_reduction_pct": 100.0 * (
                1.0 - modes["all"]["iter_wall_us_median"]
                / max(modes["none"]["iter_wall_us_median"], 1e-9)),
        }
        print(f"passes[{name}]: segments/iter "
              f"{modes['none']['segments_per_iter']:.1f} -> "
              f"{modes['all']['segments_per_iter']:.1f}, "
              f"eliminated={modes['all']['counters']['nodes_eliminated']} "
              f"cse={modes['all']['counters']['cse_hits']} "
              f"folded={modes['all']['counters']['feeds_folded']} "
              f"coalesced={modes['all']['counters']['segments_coalesced']} "
              f"wall {modes['none']['iter_wall_us_median']:.0f} -> "
              f"{modes['all']['iter_wall_us_median']:.0f}us", flush=True)
    assert len(fewer_segments) >= 2, (
        f"expected >=2 workloads with fewer dispatched segments under the "
        f"pass pipeline, got {fewer_segments}")
    # iteration-time gate: no workload may regress beyond scheduler noise.
    # A workload with zero pass activity compiles a bit-identical program
    # — its wall delta is noise by construction (observed swinging ±23%
    # on this shared box even with best-of-rounds medians), so the gate
    # only covers workloads the pipeline actually rewrote, with an
    # allowance wide enough for scheduler jitter but far below the
    # pathological class it exists to catch (interpret-mode kernels, a
    # probe on the hot path, per-iteration replays: 2-100x)
    active_keys = ("nodes_eliminated", "cse_hits", "feeds_folded",
                   "segments_coalesced", "kernels_substituted")
    regressed = {
        n: round(v["wall_reduction_pct"], 1) for n, v in out.items()
        if v["wall_reduction_pct"] < -25.0
        and any(v["all"]["counters"][k] for k in active_keys)}
    assert not regressed, (
        f"pass pipeline regressed median iteration time beyond the noise "
        f"allowance on: {regressed}")
    out["_fewer_segment_workloads"] = fewer_segments
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", nargs="*", default=DEFAULT_PROGRAMS)
    ap.add_argument("--warmup", type=int, default=12)
    ap.add_argument("--iters", type=int, default=80)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: 2 programs, short runs, 1 round")
    ap.add_argument("--flips", type=int, default=50,
                    help="shape-flip scenario: alternating-batch flips "
                         "after warmup (0 disables)")
    ap.add_argument("--passes", action="store_true",
                    help="ISSUE 4 ablation: run every workload with the "
                         "symbolic pass pipeline on vs off; fails on any "
                         "fetched-value mismatch")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.smoke:
        args.programs = args.programs[:2]
        args.warmup, args.iters, args.rounds = 6, 20, 1

    results = {}
    for name in args.programs:
        r = measure(name, args.warmup, args.iters, args.rounds)
        results[name] = r
        print(f"{name}: py_overhead={r['py_overhead_us_median']:.1f}us/iter "
              f"(stall {r['py_stall_us_median']:.1f} + dispatch "
              f"{r['dispatch_us_median']:.1f}), wall "
              f"{r['wall_us_median']:.0f}us, fast_hits/iter "
              f"{r['walker_fast_hits'] / r['iters']:.1f}", flush=True)
        assert r["phase"] == "co-execution", f"{name} never reached skeleton"
        if name not in NON_CONVERTIBLE and r["feeds_defaulted"]:
            # zeros substitution is only legitimate for untaken regions of
            # branchy programs — a linear covered program defaulting a feed
            # means the Walker failed to collect a value it validated
            raise AssertionError(
                f"{name}: {r['feeds_defaulted']} Input Feeding values "
                f"silently defaulted to zeros on a covered linear program")

    report = {
        "meta": {
            "metric": "py_stall_time + dispatch_time, median us/iter at "
                      "steady state (see module docstring)",
            "warmup": args.warmup, "iters": args.iters,
            "rounds": args.rounds, "smoke": bool(args.smoke),
        },
        "programs": results,
    }
    if args.flips:
        # ISSUE 3 gate: alternating batch sizes decode through shape-keyed
        # TraceGraph families with zero retraces / recompiles after warmup
        report["shape_flip"] = measure_shape_flip(flips=args.flips)
    if args.passes:
        # ISSUE 4 gate: the pass pipeline preserves every fetched value
        # and at least two workloads dispatch fewer segments per iteration
        report["passes_ablation"] = measure_passes(
            warmup=max(6, args.warmup // 2), iters=args.iters,
            rounds=args.rounds)

    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)
        comparison, reductions = {}, []
        for name, r in results.items():
            b = baseline.get("programs", {}).get(name)
            if not b:
                continue
            red = 100.0 * (1.0 - r["py_overhead_us_median"]
                           / b["py_overhead_us_median"])
            comparison[name] = {
                "baseline_py_overhead_us": b["py_overhead_us_median"],
                "current_py_overhead_us": r["py_overhead_us_median"],
                "reduction_pct": red,
            }
            reductions.append(red)
        report["baseline"] = {"source": baseline.get("meta", {}),
                              "path": args.baseline}
        report["comparison"] = comparison
        if reductions:
            report["mean_reduction_pct"] = float(np.mean(reductions))
            print(f"mean steady-state Python-overhead reduction vs "
                  f"pre-change baseline: {report['mean_reduction_pct']:.1f}%"
                  f" (gate: >= 25%)")

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
