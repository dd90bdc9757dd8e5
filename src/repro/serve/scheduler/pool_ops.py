"""Slot-pool DL operations: ``serve.slot_prefill`` / ``serve.slot_decode``.

The continuous-batching scheduler keeps one fixed KV/recurrent cache for
the whole engine lifetime; requests borrow slots and return them at
retirement.  Both pool mutations are registered DL ops (core op registry,
DESIGN.md §2 granularity), so under Terra co-execution they land in the
TraceGraph as single nodes whose input/output leaves are the pool cache
Variables:

* ``serve.slot_prefill`` — run the model over a length-bucketed prompt
  batch against a *fresh* batch-local cache, sample the first token at
  each row's true last position, then scatter the batch rows into the
  pool at the assigned slot indices and set the per-slot position
  counters to the prompt lengths.
* ``serve.slot_decode`` — one masked decode step over *all* slots: each
  row attends at its own position (vector ``cache["len"]``, see
  models/attention.py), the new K/V lands at that row's position, and
  only *active* rows advance their counter / produce a real token.
  Inactive rows compute garbage that stays beyond their valid length —
  masked at every future read and overwritten by the next prefill into
  that slot — so slot churn never changes the op's shape.

The sampled-token frame ``tokf`` [max_slots, 1] is threaded *on device*:
decode embeds it directly and writes the frame for the next step
(``where(mask, tok, tokf)``); prefill scatters each admitted row's first
token into it.  The host therefore never needs step N's token to
dispatch step N+1 — the scheduler fetches the token frame one step late,
purely for delivery (DESIGN.md §12).

Paged mode (``page_size > 0``): attention K/V leaves become flat block
arenas ``[num_blocks, page_size, Hkv, D]`` addressed through a per-slot
block table ``bt`` [max_slots, nbps] fed each step; recurrent leaves
(O(1) state per slot) stay dense.  Prefill scatters whole bucket rows
block-wise through the admitted rows' tables (``bt_rows`` [b, nbps]).

Pytrees are flattened at the op boundary; a meta registry keeps the
(static) treedefs and per-leaf scatter axes out of band.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro.core.ops import def_op, op_impl
from repro.models import layers as L
from repro.models import model as M
from repro.models import transformer as T
from repro.serve.meta import MetaRegistry

# kinds whose cache reads tolerate right-padding (garbage entries beyond
# the valid length are masked out by the attention valid-length mask);
# recurrent kinds fold every position into their state, so their prompts
# must be admitted at exact length (no padding)
PAD_SAFE_KINDS = ("attn", "attn_swa", "attn_local", "moe")
RECURRENT_KINDS = ("ssd", "rglru")


def check_supported(cfg) -> None:
    """The slot pool supports self-attention and recurrent decoder stacks;
    encoder/cross-attention families need per-request side inputs that the
    pooled step has no lane for yet — the lock-step engine serves those."""
    kinds = tuple(cfg.block_pattern) + tuple(cfg.extra_blocks)
    bad = [k for k in kinds if k not in PAD_SAFE_KINDS + RECURRENT_KINDS]
    if bad or cfg.enc_layers:
        raise NotImplementedError(
            f"slot-pooled scheduling does not support {cfg.name}: block "
            f"kinds {bad or ['encoder']} need per-request cross/frontend "
            "state; use ServingEngine.run_batch for this family")


def pads_allowed(cfg) -> bool:
    """True when prompts may be right-padded to their length bucket."""
    kinds = tuple(cfg.block_pattern) + tuple(cfg.extra_blocks)
    return all(k in PAD_SAFE_KINDS for k in kinds)


def build_pool_cache(cfg, max_slots: int, max_len: int, page_size: int = 0,
                     num_blocks: int = 0):
    """Zero-initialised pool cache: ``init_cache`` minus the scalar
    ``len`` (replaced by the per-slot position vector).  Returns
    (leaves, treedef, batch_axes, paged): ``batch_axes[i]`` is the slot
    axis of leaf i — scanned layer caches carry a leading
    n_pattern_blocks axis, extra-block caches do not — and ``paged[i]``
    marks leaves laid out as block arenas instead of slot rows."""
    dt = jnp.dtype(cfg.dtype)

    def slot(kind, nb):
        if page_size and kind in PAD_SAFE_KINDS:
            Hkv, D = cfg.n_kv_heads, cfg.head_dim
            shp = (num_blocks, page_size, Hkv, D)
            shp = (nb,) + shp if nb is not None else shp
            return {"kp": jnp.zeros(shp, dt), "vp": jnp.zeros(shp, dt)}
        return M._slot_cache(cfg, kind, nb, max_slots, max_len)

    nb = cfg.n_pattern_blocks
    tmpl = {"layers": [slot(k, nb) for k in cfg.block_pattern],
            "extra": [slot(k, None) for k in cfg.extra_blocks]}
    axes_tree = {"layers": jax.tree.map(lambda _: 1, tmpl["layers"]),
                 "extra": jax.tree.map(lambda _: 0, tmpl["extra"])}

    def pg_tree(kind, sub):
        flag = bool(page_size) and kind in PAD_SAFE_KINDS
        return jax.tree.map(lambda _: flag, sub)

    pg = {"layers": [pg_tree(k, s)
                     for k, s in zip(cfg.block_pattern, tmpl["layers"])],
          "extra": [pg_tree(k, s)
                    for k, s in zip(cfg.extra_blocks, tmpl["extra"])]}
    leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    axes = jax.tree_util.tree_leaves(axes_tree)
    paged = jax.tree_util.tree_leaves(pg)
    return leaves, treedef, tuple(axes), tuple(paged)


def _flatten_cache(cache) -> List[Any]:
    """Flatten a run_stack cache pytree in pool-leaf order (minus len)."""
    return jax.tree_util.tree_leaves({"layers": cache["layers"],
                                      "extra": cache["extra"]})


# --------------------------------------------------------------------------
# Meta registry: static treedefs/axes keyed by an attribute-sized id
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PoolMeta:
    cfg: Any
    params_def: Any
    cache_def: Any
    batch_axes: Tuple[int, ...]
    temperature: float
    max_len: int
    page_size: int = 0
    num_blocks: int = 0
    paged: Tuple[bool, ...] = ()


_META = MetaRegistry()


def register_pool_meta(cfg, params_def, cache_def, batch_axes,
                       temperature: float, max_len: int, page_size: int = 0,
                       num_blocks: int = 0, paged=()) -> int:
    return _META.register(PoolMeta(cfg, params_def, cache_def,
                                   tuple(batch_axes), float(temperature),
                                   int(max_len), int(page_size),
                                   int(num_blocks), tuple(paged)))


def pool_meta(mid: int) -> PoolMeta:
    return _META.get(mid)


# --------------------------------------------------------------------------
# Pure step bodies
# --------------------------------------------------------------------------

def _sample(logits, temperature: float, rng):
    if temperature > 0.0 and rng is not None:
        tok = jax.random.categorical(
            rng, logits.astype(jnp.float32) / temperature, axis=-1)
    else:
        tok = jnp.argmax(logits, axis=-1)
    return tok.astype(jnp.int32)


def _head_logits(cfg, params, x2d):
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return L.unembed(x2d, head)


def _pool_prefill(meta: PoolMeta, params, cache_leaves, pos, tokf, tokens,
                  slots, lengths, bt_rows, rng):
    """tokens [b, S] (padded to the bucket), slots/lengths [b] int32 ->
    (first token [b, 1], scattered pool leaves, updated pos, tokf)."""
    cfg = meta.cfg
    B, S = tokens.shape
    # batch-local cache at the pool's max_len: bit-identical math to the
    # lock-step prefill (same shapes through run_stack), scattered whole-row
    fresh = M.init_cache(cfg, B, meta.max_len)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x, fresh = T.run_stack(cfg, params, x, positions=jnp.arange(S)[None],
                           caches=fresh)
    x = T._norm(cfg, params["final_norm"], x)                  # [b, S, d]
    last = jnp.take_along_axis(
        x, (lengths - 1).astype(jnp.int32)[:, None, None], axis=1)[:, 0]
    tok = _sample(_head_logits(cfg, params, last), meta.temperature, rng)

    bs = meta.page_size
    new_leaves = []
    for pool_leaf, b_leaf, ax, pg in zip(cache_leaves, _flatten_cache(fresh),
                                         meta.batch_axes, meta.paged):
        b_leaf = b_leaf.astype(pool_leaf.dtype)
        if pg:
            # block-wise scatter of the dense bucket rows through the
            # admitted rows' block tables; unassigned table tail entries
            # are 0 -> the trash block (never read)
            if ax == 0:
                r = b_leaf.reshape((B, b_leaf.shape[1] // bs, bs)
                                   + b_leaf.shape[2:])
                new_leaves.append(pool_leaf.at[bt_rows].set(r))
            else:
                nb_ = b_leaf.shape[0]
                r = b_leaf.reshape((nb_, B, b_leaf.shape[2] // bs, bs)
                                   + b_leaf.shape[3:])
                new_leaves.append(pool_leaf.at[:, bt_rows].set(r))
        elif ax == 0:
            new_leaves.append(pool_leaf.at[slots].set(b_leaf))
        else:
            new_leaves.append(pool_leaf.at[:, slots].set(b_leaf))
    new_pos = pos.at[slots].set(lengths.astype(pos.dtype))
    new_tokf = tokf.at[slots].set(tok[:, None])
    return (tok[:, None],) + tuple(new_leaves) + (new_pos, new_tokf)


def _pool_decode(meta: PoolMeta, params, cache_leaves, pos, tokf,
                 mask, bt, rng):
    """tokf [max_slots, 1], pos/mask [max_slots] -> (this step's token,
    updated pool leaves, advanced pos, next-step token frame).  One fixed
    shape class forever."""
    cfg = meta.cfg
    cache = jax.tree_util.tree_unflatten(meta.cache_def, cache_leaves)
    caches = {"layers": cache["layers"], "extra": cache["extra"],
              "len": pos}
    if bt is not None:
        caches["bt"] = bt
    x = L.embed(params["embed"], tokf).astype(jnp.dtype(cfg.dtype))
    x, new_caches = T.run_stack(cfg, params, x, positions=pos[:, None],
                                caches=caches)
    x = T._norm(cfg, params["final_norm"], x)
    tok = _sample(_head_logits(cfg, params, x[:, 0]), meta.temperature, rng)
    tok = jnp.where(mask, tok, 0)[:, None]
    new_pos = pos + mask.astype(pos.dtype)
    new_tokf = jnp.where(mask[:, None], tok, tokf)
    return (tok,) + tuple(_flatten_cache(new_caches)) + (new_pos, new_tokf)


# --------------------------------------------------------------------------
# Registered DL ops (flat-leaf boundary)
# --------------------------------------------------------------------------

def _split(leaves, n_params: int, n_cache: int, meta_id: int):
    meta = _META.get(meta_id)
    params = jax.tree_util.tree_unflatten(meta.params_def,
                                          leaves[:n_params])
    cache_leaves = list(leaves[n_params:n_params + n_cache])
    rest = list(leaves[n_params + n_cache:])
    return meta, params, cache_leaves, rest


def _slot_prefill_impl(*leaves, _meta: int, _n_params: int, _n_cache: int,
                       _has_rng: bool):
    meta, params, cache_leaves, rest = _split(leaves, _n_params, _n_cache,
                                              _meta)
    pos, tokf, tokens, slots, lengths = rest[:5]
    rest = rest[5:]
    bt_rows = rest.pop(0) if meta.page_size else None
    rng = rest[0] if _has_rng else None
    return _pool_prefill(meta, params, cache_leaves, pos, tokf, tokens,
                         slots, lengths, bt_rows, rng)


def _slot_decode_impl(*leaves, _meta: int, _n_params: int, _n_cache: int,
                      _has_rng: bool):
    meta, params, cache_leaves, rest = _split(leaves, _n_params, _n_cache,
                                              _meta)
    pos, tokf, mask = rest[:3]
    rest = rest[3:]
    bt = rest.pop(0) if meta.page_size else None
    rng = rest[0] if _has_rng else None
    return _pool_decode(meta, params, cache_leaves, pos, tokf, mask, bt, rng)


def _slot_decode_kernel_impl(*leaves, **attrs):
    """Paged decode with the Pallas paged-attention kernel enabled; the
    flag is read at trace time, so the substituted node compiles the
    kernel path while the math (and the op signature) stays identical."""
    from repro.models import attention as A
    prev, A.PAGED_KERNEL = A.PAGED_KERNEL, True
    try:
        return _slot_decode_impl(*leaves, **attrs)
    finally:
        A.PAGED_KERNEL = prev


def program(op: str):
    """The op's implementation under the op's name, for a jitted step
    program whose XLA module then says its role
    (``jit_serve.slot_prefill``) on the profiler's lines."""
    impl = op_impl(op)

    @functools.wraps(impl)
    def step(*leaves, **attrs):
        return impl(*leaves, **attrs)
    step.__name__ = step.__qualname__ = op
    return step


slot_prefill = def_op("serve.slot_prefill", _slot_prefill_impl)
slot_decode = def_op("serve.slot_decode", _slot_decode_impl)
