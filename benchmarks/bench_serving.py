"""Serving benchmark: continuous batching vs lock-step vs Terra-off.

A mixed-length, Poisson-arrival workload is served three ways:

* ``scheduler_terra``   — serve/scheduler/ continuous batching, decode
                          loop under Terra co-execution (the system);
* ``scheduler_noterra`` — the same scheduler with ``use_terra=False``
                          (plain donated jax.jit steps): what co-execution
                          itself is worth at equal scheduling policy;
* ``lockstep``          — ServingEngine.run_batch, greedy same-length
                          batch formation in arrival order, each batch
                          drained to its slowest request (the pre-ISSUE-5
                          serving shape).

A fourth arm, ``paged_highconc`` (ISSUE 7), serves a burst of short
requests through the paged KV cache with a block arena HALF the size of
the dense pool's memory — concurrency the dense layout cannot reach at
equal memory — and checks exact token equality against a dense run.

Reported per arm: tokens/s, TTFT (time to first token) and per-request
latency p50/p95, the co-execution counters, and a per-step overhead
breakdown (dispatch time, fetch-wait time, runner occupancy, residual
Python) derived from a :class:`TimingProcessor` attached to the
scheduler's EventStream (DESIGN.md §13) during a traced re-run — the
measured trials themselves stay counters-only, the deployment
configuration.  The terra arm's traced re-run also exports the full
event stream as ``trace.jsonl`` (schema-validated, uploaded by CI).
Gates:

* token equality — for an identical fixed request set the scheduler's
  output tokens match lock-step decode exactly (equal quality);
* ``tokens_per_s(scheduler_terra) >= tokens_per_s(scheduler_noterra)``
  — co-execution costs nothing at serving steady state (ISSUE 7; hard
  gate in smoke and full runs);
* the full event stream (timing + request traces + JSONL export)
  costs at most 2 % tokens/s vs counters-only on the terra arm
  (hard gate in smoke and full runs);
* ``tokens_per_s(scheduler_terra) >= 1.5 * tokens_per_s(lockstep)``
  (full-run only);
* after warmup, slot churn causes zero ``retraces`` and the family map
  holds at most 2 shape classes;
* the paged arm's peak concurrency exceeds the dense-equivalent slot
  count for the same memory, with zero post-warmup retraces and tokens
  identical to the dense pool.

Writes ``BENCH_serving.json`` (CI uploads it as an artifact alongside
the hot-path ablation and the event trace).

Usage:
    python -m benchmarks.bench_serving [--smoke] [--out BENCH_serving.json]
                                       [--trace-out trace.jsonl]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.core.events import (JsonlSink, RequestTraceProcessor,
                               TimingProcessor)
from repro.core.events.schema import validate_jsonl
from repro.launch import compile_cache
from repro.models import model as M
from repro.obs import Histogram, MetricsProcessor, TraceViewerExporter
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import ContinuousBatchingScheduler

def build_workload(cfg, seed, n, mean_gap_s, lens, max_new_lo, max_new_hi):
    """(arrival_offset, prompt, max_new) triples; Poisson arrivals."""
    rng = np.random.RandomState(seed)
    offsets = np.cumsum(rng.exponential(mean_gap_s, size=n))
    out = []
    for i in range(n):
        L = int(rng.choice(lens))
        out.append((float(offsets[i]),
                    rng.randint(0, cfg.vocab, L).astype(np.int32),
                    int(rng.randint(max_new_lo, max_new_hi + 1))))
    return out


def make_requests(workload, t0):
    return [Request(prompt=p, max_new_tokens=mn, arrival_time=t0 + off)
            for off, p, mn in workload]


def summarize(requests, wall):
    """Latency summary through the same streaming log-bucketed histograms
    a live serving process exposes (repro.obs.metrics; one percentile
    path for benches and production, ±2.5 % bucket error by contract).
    The mean stays exact — histograms track the true sum/count."""
    ttft, lat = Histogram(), Histogram()
    for r in requests:
        ttft.observe((r.first_token_time - r.arrival_time) * 1e3)
        lat.observe((r.finish_time - r.arrival_time) * 1e3)
    toks = sum(len(r.out_tokens) for r in requests)
    return {
        "requests": len(requests),
        "generated_tokens": toks,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(toks / wall, 2),
        "ttft_ms": {"mean": round(ttft.mean, 2),
                    "p50": round(ttft.percentile(50), 2),
                    "p95": round(ttft.percentile(95), 2)},
        "latency_ms": {"p50": round(lat.percentile(50), 2),
                       "p95": round(lat.percentile(95), 2)},
    }


# --------------------------------------------------------------------------
# Arms
# --------------------------------------------------------------------------

def _pow2_sizes(n):
    k, out = 1, []
    while k <= n:
        out.append(k)
        k <<= 1
    return out


def _warm_requests(cfg, bucket, k):
    # max_new=4 gives every warmed shape class >= 3 decode iterations:
    # enough to trace twice, compile, and reach co-execution, so no
    # segment compile can land inside the timed run
    rng = np.random.RandomState(bucket * 131 + k)
    return [Request(prompt=rng.randint(0, cfg.vocab, bucket)
                    .astype(np.int32), max_new_tokens=4, arrival_time=0.0)
            for _ in range(k)]


def make_scheduler(cfg, params, workload, *, max_slots, max_len, use_terra,
                   **sched_kw):
    """Build a scheduler and warm every (group size, length bucket) shape
    the workload can produce — compile caches are engine-lifetime state
    in a real serving deployment, so warmup is not part of the measured
    steady-state cost (same treatment as bench_hotpath)."""
    sch = ContinuousBatchingScheduler(cfg, params, max_slots=max_slots,
                                      max_len=max_len, use_terra=use_terra,
                                      **sched_kw)
    for bucket in sorted({len(p) for _, p, _ in workload}):
        for k in _pow2_sizes(max_slots):
            sch.serve(_warm_requests(cfg, bucket, k))
    return sch


def _one_trial(sch, workload):
    stats0 = dict(sch.stats)
    t0 = time.perf_counter()
    reqs = make_requests(workload, t0)
    sch.serve(reqs)
    return reqs, time.perf_counter() - t0, stats0, dict(sch.stats)


def run_scheduler(sch, workload, trials=5, trace_path=None):
    """Serve the workload both counters-only (the deployment
    configuration) and with the full observability stack attached —
    structured events, request traces, JSONL export, live metrics
    registry and Chrome/Perfetto timeline export — interleaved per
    round, alternating
    which goes first, so machine drift and any within-round warmth hit
    both configurations equally; report the best-throughput trial of
    each — the steady-state estimator.  The TimingProcessor supplies the
    host-overhead breakdown, and the best-vs-best throughput ratio is
    the ≤2 % tracing-cost gate (DESIGN.md §15)."""
    timing = TimingProcessor()
    metrics = MetricsProcessor()
    extras = [metrics]
    viewer = None
    if trace_path:
        open(trace_path, "w").close()       # truncate any stale artifact
        viewer = TraceViewerExporter(trace_path + ".trace.json")
        extras += [RequestTraceProcessor(), JsonlSink(trace_path), viewer]
    best = tbest = None
    for i in range(max(1, trials)):
        for with_events in ((False, True) if i % 2 == 0 else (True, False)):
            if not with_events:
                trial = _one_trial(sch, workload)
                if best is None or trial[1] < best[1]:
                    best = trial
                continue
            timing.reset()                  # breakdown = winning window
            procs = [sch.events.attach(p) for p in [timing] + extras]
            try:
                traced = _one_trial(sch, workload)
            finally:
                for p in procs:
                    sch.events.detach(p)
            if tbest is None or traced[1] < tbest[1]:
                tbest = (traced[0], traced[1], timing.summary())
    for p in extras:
        p.close()                   # flushes the JSONL sink + trace export
    reqs, wall, stats0, st = best
    out = summarize(reqs, wall)
    if sch.use_terra:
        out["coexec"] = {
            "phase": st["phase"],
            "retraces_post_warmup": st["retraces"] - stats0["retraces"],
            "families": st["families"],
            "replays": st["replays"],
            "walker_fast_hits": st["walker_fast_hits"],
            "steady_iters": st["steady_iters"] - stats0["steady_iters"],
            "steady_exits": st["steady_exits"] - stats0["steady_exits"],
        }
    out["sched"] = {k: st[k] for k in ("admitted", "retired", "decode_steps",
                                       "prefill_steps", "prefill_tokens",
                                       "peak_resident_tokens")}
    treqs, twall, ov = tbest
    traced = summarize(treqs, twall)
    ov["other_py_ms"] = round(
        (twall - ov.pop("dispatch_s") - ov.pop("fetch_wait_s")) * 1e3, 3)
    out["overhead"] = ov
    snap = metrics.registry.snapshot()
    out["tracing"] = {
        "tokens_per_s": traced["tokens_per_s"],
        "ratio_vs_counters_only": round(
            traced["tokens_per_s"] / out["tokens_per_s"], 4),
        "trace": trace_path,
        "perfetto": viewer.path if viewer is not None else None,
        "metrics": {k: {kk: round(vv, 3) for kk, vv in h.items()}
                    for k, h in snap["histograms"].items()},
    }
    return out


def run_paged_arm(cfg, params, *, smoke, seed=7):
    """High-concurrency burst through the paged pool: the block arena is
    HALF the dense pool's memory (``capacity_tokens = max_slots*max_len/2``)
    yet the burst runs more requests concurrently than a dense pool of
    that same memory could hold rows for.  Token equality is checked
    against a dense-pool run of the identical request set."""
    max_slots, max_len, page = (8, 64, 16) if smoke else (32, 64, 16)
    num_blocks = (max_slots * max_len // 2) // page + 1
    rng = np.random.RandomState(seed)
    n = max_slots + 4 if smoke else 200     # oversubscribe: most must queue
    lens = ([8] * n if smoke else
            [int(rng.choice((8, 16))) for _ in range(n)])
    mns = ([8] * n if smoke else
            [int(rng.randint(4, 13)) for _ in range(n)])
    workload = [(0.0, p.prompt, mns[i]) for i, p in
                enumerate(make_fixed(cfg, lens, mns, seed))]
    paged = make_scheduler(cfg, params, workload, max_slots=max_slots,
                           max_len=max_len, use_terra=True,
                           page_size=page, num_blocks=num_blocks)
    stats0 = dict(paged.stats)
    peaks = [0]
    reqs = make_fixed(cfg, lens, mns, seed,
                      stream=lambda r, t, i: peaks.append(
                          paged.pool.active_count))
    t0 = time.perf_counter()
    paged.serve(reqs)
    wall = time.perf_counter() - t0
    out = summarize(reqs, wall)
    st = paged.stats
    out["coexec"] = {
        "phase": st["phase"],
        "retraces_post_warmup": st["retraces"] - stats0["retraces"],
        "families": st["families"],
        "steady_iters": st["steady_iters"] - stats0["steady_iters"],
    }
    paged.close()
    dense = ContinuousBatchingScheduler(cfg, params, max_slots=max_slots,
                                        max_len=max_len)
    dref = make_fixed(cfg, lens, mns, seed)
    dense.serve(dref)
    dense.close()
    mism = [i for i, (x, y) in enumerate(zip(reqs, dref))
            if x.out_tokens != y.out_tokens]
    cap_tokens = (num_blocks - 1) * page
    out["paged"] = {
        "page_size": page, "num_blocks": num_blocks,
        "capacity_tokens": cap_tokens,
        "dense_equiv_slots": cap_tokens // max_len,
        "peak_concurrent": int(max(peaks)),
        "peak_resident_tokens": st["peak_resident_tokens"],
        "equal_vs_dense": not mism, "mismatches": mism,
    }
    return out


def make_fixed(cfg, lens, mns, seed, **kw):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, cfg.vocab, L).astype(np.int32),
                    max_new_tokens=mn, arrival_time=0.0, **kw)
            for L, mn in zip(lens, mns)]


def make_lockstep(cfg, params, workload, *, max_slots, max_len):
    """Lock-step baseline engine, batch shapes pre-warmed.  Batches are
    padded to power-of-two sizes (bucket_batches) so the greedy batch
    former's shape space is as small as the scheduler's."""
    eng = ServingEngine(cfg, params, max_len=max_len, bucket_batches=True)
    for L in sorted({len(p) for _, p, _ in workload}):
        for k in _pow2_sizes(max_slots):
            eng.run_batch(_warm_requests(cfg, L, k))
    return eng


def run_lockstep(eng, workload, *, max_slots):
    t0 = time.perf_counter()
    reqs = make_requests(workload, t0)
    pending = sorted(reqs, key=lambda r: r.arrival_time)
    while pending:
        now = time.perf_counter()
        ready = [r for r in pending if r.arrival_time <= now]
        if not ready:
            time.sleep(max(0.0, pending[0].arrival_time - now))
            continue
        # greedy same-length batch in arrival order (run_batch rejects
        # ragged prompts); the batch then drains to its slowest member
        L = len(ready[0].prompt)
        batch = [r for r in ready if len(r.prompt) == L][:max_slots]
        taken = {id(r) for r in batch}
        pending = [r for r in pending if id(r) not in taken]
        eng.run_batch(batch)
    wall = time.perf_counter() - t0
    out = summarize(reqs, wall)
    out["engine_stats"] = {k: round(v, 4) if isinstance(v, float) else v
                           for k, v in eng.stats.items()}
    return out


def check_equality(sch, eng, workload, *, max_slots):
    """Equal quality: identical fixed request set (all arrived at t=0),
    scheduler tokens == lock-step tokens, request by request."""
    fixed = [(0.0, p, mn) for _, p, mn in workload]
    a = make_requests(fixed, 0.0)
    sch.serve(a)
    b = make_requests(fixed, 0.0)
    by_len = {}
    for r in b:
        by_len.setdefault(len(r.prompt), []).append(r)
    for group in by_len.values():
        for i in range(0, len(group), max_slots):
            eng.run_batch(group[i:i + max_slots])
    mism = [i for i, (x, y) in enumerate(zip(a, b))
            if x.out_tokens != y.out_tokens]
    return {"checked": len(a), "mismatches": mism, "equal": not mism}


# --------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for CI; the equality and "
                         "shape-stability gates still hard-fail, only "
                         "the 1.5x speedup gate is full-run-only")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--trace-out", default="trace.jsonl",
                    help="JSONL event-trace artifact from the terra arm's "
                         "traced re-run (schema-validated; '' disables)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = smoke_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.smoke:
        # decode-heavy even in smoke: the terra-vs-noterra gate measures
        # steady-state decode overhead, which a prefill-dominated burst
        # would bury in compile-adjacent noise
        knobs = dict(max_slots=4, max_len=64)
        mean_gap = 0.005
        workload = build_workload(cfg, args.seed, n=12, mean_gap_s=mean_gap,
                                  lens=(8, 16), max_new_lo=8, max_new_hi=24)
    else:
        # heavy mixed traffic: high decode-budget variance is exactly what
        # lock-step batching is worst at (every batch drains to its
        # slowest member while finished rows burn decode steps)
        knobs = dict(max_slots=8, max_len=128)
        mean_gap = 0.003
        workload = build_workload(cfg, args.seed, n=40, mean_gap_s=mean_gap,
                                  lens=(8, 16, 32), max_new_lo=4,
                                  max_new_hi=80)

    arms = {}
    sch = make_scheduler(cfg, params, workload, use_terra=True, **knobs)
    arms["scheduler_terra"] = run_scheduler(sch, workload,
                                            trace_path=args.trace_out or None)
    sch2 = make_scheduler(cfg, params, workload, use_terra=False, **knobs)
    arms["scheduler_noterra"] = run_scheduler(sch2, workload)
    sch2.close()
    eng = make_lockstep(cfg, params, workload, **knobs)
    arms["lockstep"] = run_lockstep(eng, workload,
                                    max_slots=knobs["max_slots"])
    equality = check_equality(sch, eng, workload,
                              max_slots=knobs["max_slots"])
    sch.close()
    if eng.terra is not None:
        eng.terra.close()
    arms["paged_highconc"] = run_paged_arm(cfg, params, smoke=args.smoke)

    speedup = (arms["scheduler_terra"]["tokens_per_s"]
               / arms["lockstep"]["tokens_per_s"])
    vs_noterra = (arms["scheduler_terra"]["tokens_per_s"]
                  / arms["scheduler_noterra"]["tokens_per_s"])
    coexec = arms["scheduler_terra"]["coexec"]
    paged = arms["paged_highconc"]["paged"]
    tracing = arms["scheduler_terra"]["tracing"]
    trace_counts = (validate_jsonl(args.trace_out) if args.trace_out
                    else {})
    gates = {
        "token_equality": equality["equal"],
        "speedup_vs_lockstep": round(speedup, 3),
        "speedup_gate_1.5x": speedup >= 1.5,
        "terra_vs_noterra": round(vs_noterra, 3),
        "terra_ge_noterra": vs_noterra >= 1.0,
        "tracing_ratio": tracing["ratio_vs_counters_only"],
        "tracing_cost_le_2pct": tracing["ratio_vs_counters_only"] >= 0.98,
        "retraces_post_warmup": coexec["retraces_post_warmup"],
        "families": coexec["families"],
        "shape_stable": (coexec["retraces_post_warmup"] == 0
                         and coexec["families"] <= 2),
        "paged_equal_vs_dense": paged["equal_vs_dense"],
        "paged_beyond_dense_capacity": (
            paged["peak_concurrent"] > paged["dense_equiv_slots"]),
        "paged_retraces_post_warmup":
            arms["paged_highconc"]["coexec"]["retraces_post_warmup"],
    }
    report = {
        "arch": cfg.name, "smoke": args.smoke, "knobs": knobs,
        "workload": {"requests": len(workload),
                     "mean_gap_s": mean_gap,
                     "prompt_lens": sorted({len(p) for _, p, _ in workload}),
                     "total_budget_tokens": sum(mn for _, _, mn in workload)},
        "arms": arms, "equality": equality, "gates": gates,
        "trace": {"path": args.trace_out or None,
                  "events": sum(trace_counts.values()),
                  "by_type": trace_counts},
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))

    failures = []
    if not equality["equal"]:
        failures.append(f"token mismatch at requests {equality['mismatches']}")
    if not gates["shape_stable"]:
        failures.append(f"slot churn not shape-stable: {coexec}")
    if not gates["terra_ge_noterra"]:
        failures.append(f"co-execution overhead visible: terra is "
                        f"{vs_noterra:.3f}x of noterra (< 1.0)")
    if not gates["tracing_cost_le_2pct"]:
        failures.append(
            f"full event stream costs more than 2% tokens/s: traced run "
            f"is {tracing['ratio_vs_counters_only']:.4f}x of counters-only")
    if not gates["paged_equal_vs_dense"]:
        failures.append(f"paged tokens diverge from dense at requests "
                        f"{paged['mismatches']}")
    if not gates["paged_beyond_dense_capacity"]:
        failures.append(
            f"paged peak concurrency {paged['peak_concurrent']} did not "
            f"exceed dense-equivalent {paged['dense_equiv_slots']} slots")
    if gates["paged_retraces_post_warmup"] != 0:
        failures.append("paged arm retraced after warmup")
    if not args.smoke and not gates["speedup_gate_1.5x"]:
        failures.append(f"speedup {speedup:.2f}x < 1.5x")
    if failures:
        raise SystemExit("bench_serving FAILED: " + "; ".join(failures))
    print(f"bench_serving OK: {speedup:.2f}x vs lockstep, "
          f"{vs_noterra:.2f}x vs noterra, tracing "
          f"{tracing['ratio_vs_counters_only']:.3f}x, "
          f"retraces={coexec['retraces_post_warmup']}, "
          f"families={coexec['families']}, paged peak "
          f"{paged['peak_concurrent']}/{paged['dense_equiv_slots']} "
          f"dense-equiv slots")


if __name__ == "__main__":
    main()
