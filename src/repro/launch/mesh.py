"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization, and smoke tests must keep seeing a single device.
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


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Hardware constants for the roofline analysis (TPU v5e, per brief):
PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_LINK_BW = 50e9                # bytes/s per link
ICI_LINKS_PER_RING = 2            # 2D torus: one ring per mesh axis, 1 link
                                  # each direction => 100 GB/s ring bandwidth
ICI_BW = ICI_LINK_BW * ICI_LINKS_PER_RING
HBM_PER_CHIP = 16 * 2 ** 30       # 16 GiB
