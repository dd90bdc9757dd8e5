"""The harness decides ``correct`` from what the timed path served.

Each test drives a whole run at smoke widths on the CPU (the look for a
chip skipped): set-up, the window, the reference.  A sound program comes
out correct; with the timed path broken underneath, ``correct`` comes
out false, once for each fault a served cell can have: a token altered
where it is produced, and a decode step that returns its cache
unchanged.  Each case uses its own vocabulary size, so no compiled
program of another case is reused.
"""

import time


import benchkit


def _run(mix_name, vocab, seconds=2.0):
    import jax
    import run
    import serve
    t0 = time.perf_counter()
    bench = benchkit.benchmark()
    cfg, mix = benchkit.tiny_config(vocab), benchkit.tiny_mix(mix_name)
    fam = bench.family(cfg["family"])
    devs = jax.devices()[:1]
    rec = serve.measure(cfg, mix, fam, seed=2 ** 35 + vocab, seconds=seconds,
                      trace=False, devs=devs, peaks=benchkit.CPU_PEAKS,
                      t_start=t0, say=lambda m: None)
    cell = {"name": ("granite2b-decode-backlog" if mix["loop"] == "closed"
                     else "granite2b-rag-steady")}
    return rec, run.result(bench, cell, rec, trace=False, devs=devs)


def test_sound_run_is_correct():
    rec, line = _run("rag-steady", 520)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 12
    assert set(line["metrics"]) == {"ttft_p50_ms", "setup_s"}
    assert rec["gaps"]["tokens"] >= 64
    assert list(line)[-1] == "checks"


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.scheduler import pool_ops
    sample = pool_ops._sample

    def altered(logits, temperature, rng):
        tok = sample(logits, temperature, rng)
        return (tok.at[0].add(1)) % logits.shape[-1]
    monkeypatch.setattr(pool_ops, "_sample", altered)
    rec, line = _run("decode-backlog", 528)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]


def test_unchanged_cache_is_not_correct(monkeypatch):
    from repro.serve.scheduler import pool_ops
    decode = pool_ops._pool_decode

    def stale(meta, params, cache_leaves, *rest):
        out = decode(meta, params, cache_leaves, *rest)
        return (out[0],) + tuple(cache_leaves) + tuple(out[-2:])
    monkeypatch.setattr(pool_ops, "_pool_decode", stale)
    rec, line = _run("decode-backlog", 536)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]
