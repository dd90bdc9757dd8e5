"""Counts from shapes, the seeded weights, the traffic generator and the
peaks table, checked by hand on the CPU."""

import math
import statistics

import numpy as np
import pytest

import benchkit

GIB = 2 ** 30


@pytest.fixture(scope="module")
def fam():
    return benchkit.benchmark().family("dense_gqa")


@pytest.fixture(scope="module")
def cfg():
    return benchkit.benchmark().config("granite-3-2b-serve")


def test_granite_param_count(fam, cfg):
    s = fam.sizes(cfg)
    # per layer: q 2048x2048, k and v 2048x512, o 2048x2048, three
    # 2048x8192 MLP matrices, two norms; tied 49155x2048 embedding
    layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048
    assert fam.layer_params(s) == layer == 60_821_504
    assert fam.param_count(s) == 40 * layer + 49155 * 2048 + 2048
    assert round(fam.param_count(s) / 1e9, 2) == 2.53
    assert fam.param_bytes(s) / GIB == pytest.approx(4.72, abs=0.005)


def test_train_cut_counts(fam, cfg):
    s = fam.sizes(dict(cfg, num_hidden_layers=20))
    assert round(fam.param_count(s) / 1e9, 3) == 1.317
    # bf16 params + f32 master + two f32 moments = 14 bytes a parameter
    assert fam.train_state_bytes(s) / GIB == pytest.approx(17.17, abs=0.005)
    tokens = 8 * 128
    attn = 3 * 8 * 4 * 20 * 32 * 64 * (128 * 129 // 2)
    assert fam.train_flops(s, 8, 128) == 6 * fam.param_count(s) * tokens \
        + attn


def test_kv_and_decode_bytes(fam, cfg):
    s = fam.sizes(cfg)
    assert fam.kv_bytes_per_token(s) == 40 * 2 * 8 * 64 * 2 == 80 * 1024
    # 16 rows at 500 valid keys: params once, the valid KV read, one row
    # written per active row
    want = fam.param_bytes(s) + 16 * 500 * 81920 + 16 * 81920
    assert fam.decode_step_bytes(s, 16, 500) == want


def test_flops_count_real_tokens_only(fam, cfg):
    s = fam.sizes(cfg)
    mm = 40 * (fam.layer_params(s) - 2 * 2048)
    assert fam.matmul_params(s) == mm
    # one prompt of 3 tokens: 3 token matmuls, 1+2+3 attended keys, the
    # last position's logits
    assert fam.prompt_flops(s, 3) == (2 * mm * 3
                                      + 4 * 40 * 32 * 64 * 6
                                      + 2 * 2048 * 49155)
    assert fam.decode_token_flops(s, 10) == (2 * mm + 4 * 40 * 32 * 64 * 10
                                             + 2 * 2048 * 49155)


def test_weights_match_layer_by_layer(fam):
    """The stacked weights handed to the program are, layer by layer, the
    weights the reference regenerates, for a seed wider than 32 bits."""
    cfg = benchkit.tiny_config()
    seed = 2 ** 33 + 17
    params = fam.init_weights(cfg, seed)
    s = fam.sizes(cfg)
    key = fam.seed_key(seed)
    blk = params["blocks"][0]
    for layer in (0, s.layers - 1):
        w = fam.layer_weights(s, key, layer)
        np.testing.assert_array_equal(np.asarray(blk["attn"]["wq"][layer]),
                                      np.asarray(w["wq"]))
        np.testing.assert_array_equal(np.asarray(blk["mlp"]["w_down"][layer]),
                                      np.asarray(w["w_down"]))
        np.testing.assert_array_equal(np.asarray(blk["norm2"]["scale"][layer]),
                                      np.asarray(w["norm2"]))
    other = fam.init_weights(cfg, seed + 1)
    assert not np.array_equal(np.asarray(params["embed"]),
                              np.asarray(other["embed"]))


def test_program_takes_the_weights_layout(fam):
    """The weights tree has the program's params structure and shapes."""
    import jax
    from repro.models import model as M
    cfg = benchkit.tiny_config()
    mine = jax.eval_shape(lambda: fam.init_weights(cfg, 3))
    theirs = M.abstract_params(fam.program_config(cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", ["decode-backlog", "rag-steady"])
def test_every_seed_gets_the_same_work(name):
    """Seeds fill the prompts; the prompt and output lengths and (open
    loop) the due times are the same, in the same order, for every
    seed."""
    import loadgen
    mix = benchkit.benchmark().traffic(name)

    def sizes(seed):
        t = loadgen.Traffic(mix, seed, 40.0, 49155)
        if t.loop == "closed":
            items = [t.next_item() for _ in range(t.n)]
        else:
            items = t.schedule()
            assert items[-1].offset == pytest.approx(40.0)
        return (sorted(len(i.prompt) for i in items),
                sorted(i.max_new for i in items), items)

    a, b = sizes(1), sizes(2 ** 40 + 3)
    assert a[0] == b[0] and a[1] == b[1]
    assert [(len(i.prompt), i.max_new, i.offset) for i in a[2]] == [
        (len(i.prompt), i.max_new, i.offset) for i in b[2]]
    assert a[2][0].prompt.tolist() != b[2][0].prompt.tolist()
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert lo <= min(a[0]) and max(a[0]) <= hi
    med = statistics.median(a[0])
    assert abs(med - mix["prompt_len"]["median"]) <= 0.01 * med
    assert sizes(5)[2][0].prompt.tolist() == sizes(5)[2][0].prompt.tolist()


def test_peaks_table_is_sourced():
    import device
    pk = device.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16 * GIB
    assert "TPU v5e" in pk["source"]
    assert math.isfinite(pk["int8_ops_per_s"])
