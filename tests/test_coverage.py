"""Table 1 of the paper as a test: every imperative program of
``benchmarks/programs.py`` runs under ``terra.function``, the
whole-program jit (the AutoGraph analogue) fails exactly the programs the
paper names, and the symbolic pass pipeline changes no fetched loss."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.programs import NON_CONVERTIBLE, REGISTRY  # noqa: E402
from benchmarks.table1_coverage import (classify_fulljit,  # noqa: E402
                                        run_terra)

PROGRAMS = sorted(REGISTRY)


def test_registry_holds_the_ten_programs_and_five_failures():
    assert len(PROGRAMS) == 10
    assert len(NON_CONVERTIBLE) == 5 and set(NON_CONVERTIBLE) < set(PROGRAMS)


@pytest.mark.parametrize("name", PROGRAMS)
def test_terra_runs_every_program_and_fulljit_fails_the_named_five(name):
    losses, phase, stats = run_terra(name)
    assert np.all(np.isfinite(losses)), losses
    assert phase == "co-execution"
    status, detail = classify_fulljit(name)
    assert (status != "ok") == (name in NON_CONVERTIBLE), (status, detail)
    if name not in NON_CONVERTIBLE:
        # a program with no Python-side state converts after its traced
        # iterations, then dispatches one segment a call with no replay,
        # no retrace and no zero-defaulted feed
        assert stats["replays"] == stats["retraces"] == 0
        assert stats["feeds_defaulted"] == 0
        assert stats["segments_dispatched"] == (
            stats["iterations"] - stats["traced_iterations"])
        assert stats["walker_fast_hits"] > 0


@pytest.mark.parametrize("name", PROGRAMS)
def test_pass_pipeline_keeps_every_fetched_loss(name):
    """The pipeline is semantics-preserving: each program's losses are
    the same with every pass on and with none."""
    on, _, _ = run_terra(name, optimize="all")
    off, _, _ = run_terra(name, optimize="none")
    np.testing.assert_allclose(on, off, rtol=1e-4, atol=1e-5)
