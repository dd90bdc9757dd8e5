"""The control: the plain reference computed in float8, the precision
below the bfloat16 the configuration states, read at every position of
the prompts and tokens a sound program served.  At smoke widths on the
CPU, on three seeds, the program keeps the limit and the control fails
it (the same readings at the cell's own size come from
``bench/control.py`` on the chip)."""

import benchkit


def test_control_in_lower_precision_fails_the_limit():
    """The float8 control, read at every position of the same prompts and
    served tokens, lies beyond the limit that the sound program keeps."""
    import check
    import jax
    from serve import ServeCell
    bench = benchkit.benchmark()
    cfg, mix = benchkit.tiny_config(544), benchkit.tiny_mix("decode-backlog")
    fam = bench.family(cfg["family"])
    limit = cfg["check"]["max_logit_gap"]
    for seed in (11, 12, 2 ** 33 + 13):
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
