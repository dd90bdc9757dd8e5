"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Model code annotates arrays with *logical* axis names; this module maps them
to mesh axes via a rule table, MaxText-style.  Meshes are built with
``launch/mesh.make_mesh`` on axes ("data", "model"), optionally with a
leading "pod" axis.

Default rules:
    batch       -> ("pod", "data")      # DP across pods and data axis
    fsdp        -> ("data",)            # ZeRO-3 weight shard (+pod optional)
    tp          -> ("model",)           # tensor parallel: heads / ffn hidden
    expert      -> ("model",)           # EP: MoE expert dim
    seq         -> ()                   # sequence kept unsharded by default
    sp          -> ("model",)           # sequence parallel for long-context
    vocab       -> ("model",)

Rules are plain data: a caller swaps rule tables to change the layout.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_TLS = threading.local()


DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "fsdp_pod": ("pod", "data"),
    "tp": ("model",),
    "expert": ("model",),
    "capacity": ("data",),     # MoE per-expert token slots shard over data
    "seq": (),
    "sp": ("model",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "d_model": (),
    "d_ff": ("model",),
    "unsharded": (),
}


class ShardingPolicy:
    """Resolves logical axis names to mesh axes for a given mesh."""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[dict] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def spec(self, *logical: Optional[str]) -> P:
        if self.mesh is None:
            return P()
        parts = []
        for name in logical:
            if name is None:
                parts.append(None)
                continue
            axes = tuple(a for a in self.rules.get(name, ())
                         if a in self.mesh.axis_names)
            parts.append(axes if axes else None)
        return P(*parts)

    def sharding(self, *logical: Optional[str]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*logical))


def current_policy() -> Optional[ShardingPolicy]:
    return getattr(_TLS, "policy", None)


@contextlib.contextmanager
def use_policy(policy: Optional[ShardingPolicy]):
    prev = current_policy()
    _TLS.policy = policy
    try:
        yield policy
    finally:
        _TLS.policy = prev


def logical(x, *names: Optional[str]):
    """Annotate activation sharding with logical axis names.  A no-op when
    no policy/mesh is active (single-device smoke tests)."""
    pol = current_policy()
    if pol is None or pol.mesh is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(f"rank mismatch: {names} for shape {x.shape}")
    spec = pol.spec(*names)
    # never request a partition that does not divide the dim, and never use
    # one mesh axis for two tensor dims (first occurrence wins)
    fixed = []
    used: set = set()
    for dim, part in zip(x.shape, spec):
        if part is None:
            fixed.append(None)
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        axes = tuple(a for a in axes if a not in used)
        size = 1
        for a in axes:
            size *= pol.mesh.shape[a]
        if not axes or dim % size != 0:
            fixed.append(None)
            continue
        used.update(axes)
        fixed.append(axes if len(axes) > 1 else axes[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pol.mesh, P(*fixed)))


def param_spec(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
               pol: ShardingPolicy) -> P:
    """PartitionSpec for a parameter, dropping non-divisible partitions."""
    spec = pol.spec(*logical_axes)
    fixed = []
    for dim, part in zip(shape, spec):
        if part is None:
            fixed.append(None)
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        size = 1
        for a in axes:
            size *= pol.mesh.shape[a]
        fixed.append(part if dim % size == 0 else None)
    return P(*fixed)
