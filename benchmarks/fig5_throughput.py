"""Figure 5: training speed-up of Terra co-execution (and the full-jit
AutoGraph analogue, where it works) relative to imperative execution, plus
the Appendix-F phase-transition counters."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.programs import NON_CONVERTIBLE, REGISTRY
from repro.core import function as terra_function, imperative
from repro.launch import compile_cache


def time_variant(name: str, variant: str, warmup: int = 12,
                 measure: int = 40):
    step, _ = REGISTRY[name](variant)
    stats = {}
    if variant == "terra":
        tf = terra_function(step)
        for i in range(warmup):
            tf(i)
        tf.wait()
        t0 = time.perf_counter()
        for i in range(warmup, warmup + measure):
            tf(i)
        tf.wait()
        dt = time.perf_counter() - t0
        stats = dict(tf.stats)
        stats["phase"] = tf.phase
        tf.close()
    elif variant == "imperative":
        with imperative() as imp:
            for i in range(warmup):
                step(i)
                imp.step()
            t0 = time.perf_counter()
            for i in range(warmup, warmup + measure):
                step(i)
                imp.step()
            dt = time.perf_counter() - t0
    else:  # fulljit
        for i in range(warmup):
            step(i)
        t0 = time.perf_counter()
        for i in range(warmup, warmup + measure):
            step(i)
        dt = time.perf_counter() - t0
    return dt / measure, stats


def main():
    compile_cache.enable()
    print("program,imperative_us,terra_us,fulljit_us,"
          "terra_speedup,fulljit_speedup,traced_iters,transitions,replays")
    rows = []
    for name in sorted(REGISTRY):
        imp_t, _ = time_variant(name, "imperative")
        terra_t, st = time_variant(name, "terra")
        if name in NON_CONVERTIBLE:
            fj_t = float("nan")
        else:
            try:
                fj_t, _ = time_variant(name, "fulljit")
            except Exception:  # noqa: BLE001
                fj_t = float("nan")
        row = (name, imp_t * 1e6, terra_t * 1e6, fj_t * 1e6,
               imp_t / terra_t,
               imp_t / fj_t if np.isfinite(fj_t) else float("nan"),
               st.get("traced_iterations", 0), st.get("transitions", 0),
               st.get("replays", 0))
        rows.append(row)
        print(f"{name},{row[1]:.0f},{row[2]:.0f},{row[3]:.0f},"
              f"{row[4]:.2f},{row[5]:.2f},{row[6]},{row[7]},{row[8]}")
    sp = [r[4] for r in rows]
    print(f"# terra speedup over imperative: min {min(sp):.2f}x, "
          f"max {max(sp):.2f}x, mean {np.mean(sp):.2f}x "
          f"(paper: up to 1.73x with XLA)")
    return rows


if __name__ == "__main__":
    main()
