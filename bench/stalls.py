#!/usr/bin/env python3
"""What held the host in one window, from the program's own spans.

    python3 bench/stalls.py --workload granite2b-decode-backlog \
        --seed 11 --seconds 50 --trace 1

It runs the cell as a run does (set-up, the window at the cell's own
load), with the benchmark's processor on the scheduler's event stream,
and reports from the program's spans (``program_spans.py``):

* tokens/s and TTFT median over the window, as the end-to-end metrics
  read them: with ``--trace 0`` this is a window with a structured
  processor attached and the program's spans active, but no profiler;
* ``gc_pause_ms``, ``compile_ms``, ``runner_us_per_decode`` and
  ``dispatch_us_per_decode`` over the window;
* the longest pause between token deliveries, and what the program
  recorded over it: GC pauses by generation, compile seconds, runner
  closures by label, and the scheduler's dispatches, harvest waits and
  callbacks (its events) that cover it;
* with ``--trace 1``, the traced stretch's idle gaps labelled from the
  ``terra.*`` spans on the profiler's host line (``label_gaps``), beside
  the labels ``tracing.reduce`` gives from the benchmark's own spans.

Prints one JSON line, and writes it to ``--out`` as well.  It checks no
served token against the reference; the benchmark's own runs never run
this.
"""

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402

import numpy as np                                          # noqa: E402

import program_spans                                        # noqa: E402
import readers                                              # noqa: E402
import run                                                  # noqa: E402
import serve                                                # noqa: E402
import tracing                                              # noqa: E402


class Listening:
    """Stands in for a profiler capture that never starts, so that the
    window attaches its processor and runs untraced."""
    started, running, seconds = True, False, 0.0


def study(cfg, mix, family, *, seed: int, seconds: float, trace: bool,
          log=run.log):
    """One window of the cell, and what the program's spans say of it."""
    cell = serve.ServeCell(cfg, mix, family, seed, seconds, log)
    cell.setup()
    tdir = tempfile.mkdtemp(prefix="stalls_trace_") if trace else None
    tracer = (tracing.Capture(min(serve.TRACE_S, seconds / 2), tdir)
              if trace else Listening())
    cell.window(tracer)
    rec = {"t0": cell.t0, "t_end": cell.t_end, "t_stop": cell.t_stop,
           "seconds": float(seconds), "requests": cell.requests,
           "spans": cell.spans,
           "trace_marks": (tracer.mark0, tracer.mark1) if trace else None}
    cell.close()
    out = {"seed": seed, "trace": int(trace),
           "output_tokens_per_s": readers.tokens_in_window(rec) / seconds,
           "ttft_p50_ms": _ms(readers.p50(readers.ttft_s(rec))),
           "dispatch_us_per_decode": readers.mean_dispatch_us(rec, "decode"),
           "gc_pause_ms": program_spans.gc_pause_ms(rec),
           "compile_ms": program_spans.compile_ms(rec),
           "runner_us_per_decode": program_spans.runner_us_per_decode(rec)}
    spans = program_spans.record_spans(rec) or []
    out["record_spans"] = {src: sum(1 for s in spans if s[0] == src)
                           for src in ("gc", "compile", "runner")}
    out["gc_longest_ms"] = 1e3 * max(
        (d for src, _s, d, _l in spans if src == "gc"), default=0.0)
    out["longest_pause"] = longest_pause(
        rec, spans, tracing.host_spans(cell.spans, cell.callback_s))
    if trace:
        path = tracer.file()
        if path is not None:
            raw = tracing.load(path)
            host = program_spans.host_spans(path)
            host += program_spans.to_trace_ns(
                raw, tracer.mark0, tracer.mark1,
                [s for s in spans if s[0] == "compile"])
            out["program_labels"] = program_spans.label_gaps(
                raw, host, min_ns=1_000_000)
            red = tracing.reduce(raw, mark0=tracer.mark0, mark1=tracer.mark1,
                                 host_spans=tracing.host_spans(
                                     rec["spans"], cell.callback_s))
            out["bench_labels"] = {"idle_gaps": red["idle_gaps"],
                                   "idle_by_host": red["idle_by_host"]}
            out["busy_s"], out["window_s"] = red["busy_s"], red["window_s"]
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def _ms(v):
    return None if v is None else 1e3 * v


def longest_pause(rec, spans, host=()):
    """The longest pause between two token deliveries in the window, what
    the program recorded over it, and the seconds of it that each of the
    scheduler's own spans (``host``: ``(start, end, label)``) covers."""
    t0, t1 = rec["t0"], rec["t_end"]
    times = np.sort([t for r in rec["requests"] for t in r.times
                     if t0 <= t <= t1])
    if times.size < 2:
        return None
    i = int(np.argmax(np.diff(times)))
    a, b = float(times[i]), float(times[i + 1])
    covered = {}
    for s, e, lab in host:
        t = min(e, b) - max(s, a)
        if t > 0:
            covered[lab] = covered.get(lab, 0.0) + t
    return {"seconds": b - a, "at_s": a - t0, "host_s": covered,
            **program_spans.over(spans, a, b)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None, help="file for the JSON line")
    args = ap.parse_args(argv)
    import device
    import spec
    bench = spec.Benchmark(run.ROOT)
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    family = bench.family(cfg["family"])
    device.chips(int(cell["chips"]))
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = study(cfg, mix, family, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace))
    out.update(workload=args.workload,
               seconds_total=round(time.perf_counter() - T_START, 1))
    line = json.dumps(out, default=float)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
