"""Mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state: a caller may still set ``XLA_FLAGS`` (for
example a forced host device count) before jax initializes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis in ``Auto`` mode.

    Since jax 0.7 ``make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` may only name Auto axes and gathers and
    ``shard_map`` bodies type-check shardings eagerly.  This code base
    annotates activations with ``parallel.sharding.logical`` constraints
    and lets XLA propagate the rest, which is the Auto-mode contract."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)

