"""The mla_moe family (deepseek-v2-lite-serve): counts from shapes checked
by hand, and, at test widths on the CPU, the float8 control failing the
limit that the sound program keeps, and a traced run of the cell's kind
reporting the expert counters."""

import time

import numpy as np
import pytest

import benchkit

GIB = 2 ** 30


@pytest.fixture(scope="module")
def fam():
    return benchkit.benchmark().family("mla_moe")


@pytest.fixture(scope="module")
def cfg():
    return benchkit.benchmark().config("deepseek-v2-lite-serve")


def tiny_config(vocab: int = 512):
    """deepseek-v2-lite-serve's file at test widths (CPU only): 16 routed
    experts, 4 held, top-3, one dense and two expert layers."""
    cfg = benchkit.benchmark().config("deepseek-v2-lite-serve")
    cfg.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=32,
               v_head_dim=32, intermediate_size=128, moe_intermediate_size=32,
               n_routed_experts=4, experts_first=4, num_experts_per_tok=3,
               vocab_size=vocab)
    cfg["published"] = {"n_routed_experts": 16}
    cfg["serving"] = {"pool": "dense", "max_slots": 4, "max_len": 128,
                      "prefill_batch_cap": 2}
    # at these widths (CPU, 30 windows of 1.5-4 s over 5 seeds) the bf16
    # program's widest gap read 0-0.14 and the float8 control's 0.78-3.6
    cfg["check"] = {"max_logit_gap": 0.3}
    return cfg


def tiny_mix():
    mix = benchkit.benchmark().traffic("mla-moe-backlog")
    mix["prompt_len"] = {"median": 12, "sigma": 0.5, "min": 6, "max": 24}
    mix["output_len"] = {"median": 16, "sigma": 0.5, "min": 6, "max": 32}
    mix["clients"] = 8
    mix["check_sample"] = {"min_served_tokens": 64, "max_requests": 6}
    return mix


def test_param_count_of_the_chip_share(fam, cfg):
    s = fam.sizes(cfg)
    assert (s.experts, s.first, s.held, s.top_k) == (64, 0, 8, 6)
    # per layer: q 2048x3072, kv_a 2048x576, kv_a norm 512, kv_b
    # 512x4096, o 2048x2048, two norms; layer 0's SwiGLU of 10944; each
    # expert layer's router 2048x64, shared SwiGLU of 2816 and 8 held
    # experts of 1408; untied embedding and head
    attn = (2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
            + 2 * 2048)
    expert = 3 * 2048 * 1408
    layer0 = attn + 3 * 2048 * 10944
    moe = attn + 2048 * 64 + 3 * 2048 * 2816 + 8 * expert
    assert fam.expert_params(s) == expert
    assert fam.held_expert_params(s) == 26 * 8 * expert
    assert fam.param_count(s) == (layer0 + 26 * moe + 2 * 102400 * 2048
                                  + 2048) == 3_110_989_312
    assert fam.param_bytes(s) / GIB == pytest.approx(5.795, abs=0.001)


def test_latent_and_decode_bytes(fam, cfg):
    s = fam.sizes(cfg)
    assert fam.latent_bytes_per_token(s) == 27 * 576 * 2 == 31_104
    experts = 2 * 26 * 8 * 3 * 2048 * 1408
    rest = fam.param_bytes(s) - experts
    # 32 rows at 700 valid keys: every weight but the routed experts once,
    # the held experts where some row routes to them, the valid latent
    # rows read, one new row written per active row
    touched = 1 - (58 / 64) ** 32
    want = rest + experts * touched + 32 * 700 * 31104 + 32 * 31104
    assert fam.decode_step_bytes(s, 32, 700) == pytest.approx(want)
    assert touched == pytest.approx(0.957, abs=0.001)
    # one row touches top_k / experts of the held experts' bytes
    one = fam.decode_step_bytes(s, 1, 0) - rest - 31104
    assert one == pytest.approx(experts * 6 / 64)


def test_flops_count_the_routed_pairs_held_here(fam, cfg):
    s = fam.sizes(cfg)
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    layer0 = attn + 3 * 2048 * 10944
    # a token's routed pairs here: top_k x held / experts = 0.75 experts
    moe = attn + 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    mm = layer0 + 26 * moe
    assert fam.matmul_params(s) == pytest.approx(mm)
    head = 2 * 2048 * 102400
    # expanded prefill: per head and (query, key) pair, scores over
    # 128 + 64 and values over 128
    assert fam.prompt_flops(s, 3) == pytest.approx(
        2 * mm * 3 + 2 * 27 * 16 * 320 * 6 + head)
    # absorbed decode: per head and key, scores over 512 + 64 and the
    # weighted latent over 512
    assert fam.decode_token_flops(s, 10) == pytest.approx(
        2 * mm + 2 * 27 * 16 * (2 * 512 + 64) * 10 + head)


def test_program_takes_the_weights_layout(fam):
    """The weights tree has the program's params structure and shapes,
    and each held expert is the one the reference draws by its global
    index."""
    import jax
    from repro.models import model as M
    cfg = tiny_config()
    mine = jax.eval_shape(lambda: fam.init_weights(cfg, 3))
    theirs = M.abstract_params(fam.program_config(cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.shape == b.shape
    params = fam.init_weights(cfg, 2 ** 33 + 3)
    s = fam.sizes(cfg)
    other = fam.sizes(dict(cfg, experts_first=6, n_routed_experts=2))
    key = fam.seed_key(2 ** 33 + 3)
    w = fam.moe_weights(other, key, 2)
    got = params["blocks"][0]["moe"]["w_up"][1]          # layer 2
    np.testing.assert_array_equal(np.asarray(got[2:]),
                                  np.asarray(w["expert_w_up"]))
    assert s.first == 4 and got.shape[0] == 4


def test_control_in_lower_precision_fails_the_limit(fam):
    """The float8 control, read at every position of the same prompts and
    served tokens, lies beyond the limit that the sound program keeps."""
    import check
    import jax
    from serve import ServeCell
    cfg, mix = tiny_config(544), tiny_mix()
    limit = cfg["check"]["max_logit_gap"]
    for seed in (11, 2 ** 33 + 13):
        cell = ServeCell(cfg, mix, fam, seed, 1.5, lambda m: None)
        cell.setup()
        cell.window(None)
        reqs = cell.requests
        cell.close()
        picked = check.sample([r for r in reqs if len(r.tokens) == r.max_new],
                              seed, **mix["check_sample"])
        g = check.served_gaps(fam, cfg, seed, picked,
                              cfg["serving"]["max_len"], control=True)
        assert g["max_logit_gap"] <= limit < g["control_max_logit_gap"], g
    assert jax.devices()[0].platform == "cpu"


def test_traced_run_reports_the_expert_counters(fam):
    """A traced run of the cell's kind (``serve_experts``) is correct and
    its record carries the scheduler's expert counters, which the load
    metric reads."""
    import jax
    import run
    import serve_experts
    bench = benchkit.benchmark()
    cfg, mix = tiny_config(532), tiny_mix()
    assert cfg["cell"] == "serve_experts"
    devs = jax.devices()[:1]
    rec = serve_experts.measure(cfg, mix, fam, seed=2 ** 35 + 1, seconds=2.0,
                                trace=True, devs=devs,
                                peaks=benchkit.CPU_PEAKS,
                                t_start=time.perf_counter(),
                                say=lambda m: None)
    st = rec["stats"]
    assert st["experts_held"] == 4 and st["expert_dropped"] == 0
    assert st["kv_append_leaves"] == 2 and sum(st["expert_tokens"]) > 0
    line = run.result(bench, {"name": "dsv2lite-decode-backlog"}, rec,
                      trace=True, devs=devs)
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    load = got["expert_load_max_over_mean.dsv2"]["value"]
    tokens = st["expert_tokens"]
    assert load == pytest.approx(max(tokens) * len(tokens) / sum(tokens))
    assert "dispatch_us_per_decode.dsv2" in got
