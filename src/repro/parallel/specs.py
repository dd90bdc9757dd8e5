"""Parameter / batch / cache PartitionSpecs for (data, model) meshes.

Name-pattern rules (Megatron/MaxText-style):
  column-parallel weights  [d, X]      -> (fsdp, tp)       X = heads*hd | d_ff
  row-parallel weights     [X, d]      -> (tp, fsdp)
  MoE expert weights       [E, d, f]   -> (expert=tp, -, -)   (fine-grained)
                                          fallback (-, fsdp, tp) when E does
                                          not divide the model axis (Mixtral)
  embeddings / lm head     [V, d]      -> (tp=vocab, fsdp)
  vectors / scalars                    -> replicated
Stacked super-block leaves get a leading None.  Every rule drops
non-divisible partitions (parallel.sharding.param_spec semantics).

KV caches shard batch over (pod, data) and the *sequence* dim over the
model axis (sequence parallelism) — kv-head counts (8) do not divide the
16-way model axis, and SP is what keeps a 32k x 128 cache at ~1 GiB/chip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

COLUMN = {"wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_in_x", "w_in_y",
          "w_a", "w_x", "w_router"}
ROW = {"wo", "w_down", "w_out"}
EMBED = {"embed", "lm_head", "enc_pos"}


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh, dim: int, axes) -> Optional[Any]:
    """Return axes if they divide dim, else None (replicate)."""
    if not axes:
        return None
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    if dim % _axes_size(mesh, axes):
        return None
    return axes if len(axes) > 1 else axes[0]


def param_spec_for(mesh, path: str, shape: Tuple[int, ...],
                   fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf, identified by its tree path.

    ``fsdp=False`` (serve mode): parameters shard over the model axis only
    — no per-layer all-gather of weight shards at inference."""
    name = path.split("|")[-1]
    data_axes = ("data",) if fsdp else ()
    nd = len(shape)
    lead = ()                       # stacked super-block axis
    core = shape
    if name in COLUMN | ROW and nd == 3:
        lead, core = (None,), shape[1:]
    if name in COLUMN | ROW and nd == 4:     # stacked MoE expert weights
        lead, core = (None,), shape[1:]

    if name in EMBED and nd == 2:
        return P(_fit(mesh, shape[0], ("model",)),
                 _fit(mesh, shape[1], data_axes))
    if len(core) == 3 and name in COLUMN | ROW:
        # expert weights [E, d, f] / [E, f, d]
        e = _fit(mesh, core[0], ("model",))
        if e is not None:
            return P(*lead, e, None, None)
        if name in ROW:
            return P(*lead, None, _fit(mesh, core[1], ("model",)),
                     _fit(mesh, core[2], data_axes))
        return P(*lead, None, _fit(mesh, core[1], data_axes),
                 _fit(mesh, core[2], ("model",)))
    if len(core) == 2 and name in COLUMN:
        return P(*lead, _fit(mesh, core[0], data_axes),
                 _fit(mesh, core[1], ("model",)))
    if len(core) == 2 and name in ROW:
        return P(*lead, _fit(mesh, core[0], ("model",)),
                 _fit(mesh, core[1], data_axes))
    # conv kernels, norm scales, biases, gates, router scalars: replicate
    return P(*([None] * nd))


def _flat_paths(tree):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "|".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        yield key, leaf


def tree_param_specs(mesh, params, fsdp: bool = True):
    leaves = []
    for key, leaf in _flat_paths(params):
        leaves.append(param_spec_for(mesh, key, leaf.shape, fsdp=fsdp))
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def tree_shardings(mesh, params):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_param_specs(mesh, params))


def opt_state_specs(mesh, opt_state, param_specs):
    """m / v / master mirror the parameter sharding; step is replicated."""
    return {
        "step": P(),
        "m": param_specs, "v": param_specs, "master": param_specs,
    }


def batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh, shape: Tuple[int, ...]) -> P:
    b = _fit(mesh, shape[0], batch_axes(mesh))
    return P(b, *([None] * (len(shape) - 1)))


def cache_spec_for(mesh, path: str, shape: Tuple[int, ...]) -> P:
    """KV/recurrent cache leaves.  k/v: [nb, B, S, Hkv, D] -> batch over
    (pod,data), seq over model (SP).  Recurrent states: batch only."""
    name = path.split("|")[-1]
    if name in ("k", "v") and len(shape) >= 5:
        return P(None, _fit(mesh, shape[1], batch_axes(mesh)),
                 _fit(mesh, shape[2], ("model",)), None, None)
    if name in ("k", "v") and len(shape) == 4:     # unstacked (extra blocks)
        return P(_fit(mesh, shape[0], batch_axes(mesh)),
                 _fit(mesh, shape[1], ("model",)), None, None)
    if name == "len":
        return P()
    # conv/ssm/h states: shard batch; distribute width over model if it fits
    if len(shape) >= 2:
        lead = None if len(shape) < 3 else None
        bdim = 1 if len(shape) >= 3 else 0
        spec = [None] * len(shape)
        spec[bdim] = _fit(mesh, shape[bdim], batch_axes(mesh))
        spec[-1] = _fit(mesh, shape[-1], ("model",))
        return P(*spec)
    return P(*([None] * len(shape)))


def tree_cache_specs(mesh, cache):
    leaves = []
    for key, leaf in _flat_paths(cache):
        leaves.append(cache_spec_for(mesh, key, leaf.shape))
    treedef = jax.tree_util.tree_structure(cache)
    return jax.tree_util.tree_unflatten(treedef, leaves)
