"""Pallas TPU kernel that appends each row's new K/V (or latent) row to a
dense cache leaf, in the leaf's stored layout (DESIGN.md §11).

A TPU stores a cache leaf ``[..., B, Smax, *F]`` whose minor feature width
is not a multiple of 128 (granite's head_dim 64, DeepSeek-V2's 576-wide
latent row) with positions minor: one position is one lane of every
(8, 128) tile of its row.  Viewed through the transpose that puts
positions last, ``[Lr, B, F, Smax]``, the leaf is that byte order, so the
view is a bitcast and the kernel works on the stored bytes, aliased in to
out.

Grid = (B, Lr / lc).  Step (b, c) reads the aligned 128-position tile that
holds row b's position for lc layers (a BlockSpec whose index map reads
the scalar-prefetched positions), sets the one lane, and writes the tile
back; Pallas double-buffers the blocks, so the next step's read overlaps
this step's write.  Rows are distinct, so no two steps share a tile.

The new rows come in as column vectors without lane padding: the
``[Lr * B, F]`` rows, transposed to ``[groups, F, 128]``, so that lane
``j % 128`` of group ``j // 128`` holds column ``j = l * B + b``.  The
kernel rotates that group's lanes until the column sits at the row's lane
and selects it there, on 32-bit words (a bf16 pair shares a lane), so
every other byte of the tile is written back unchanged.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128                  # positions per tile (one lane each)
BLOCK_BYTES = 2 * 2 ** 20   # a block's tile bytes over its lc layers, at most


def engages(shape, dtype, lead: int) -> bool:
    """True for a leaf ``[*lead, B, Smax, *F]`` that the TPU stores with
    positions minor and this kernel can write: ``Smax`` a multiple of 128,
    a minor feature width that is not, and one that fills whole sublane
    tiles of 32-bit words.  Other leaves (head_dim 128, ``Smax`` < 128)
    keep the portable loop (``transformer.append_rows``)."""
    size = jnp.dtype(dtype).itemsize
    feat = shape[lead + 2:]
    if not feat or size not in (1, 2, 4):
        return False
    return (shape[lead + 1] % TILE == 0 and feat[-1] % TILE != 0
            and feat[-1] % (8 * 4 // size) == 0)


def _layers_per_block(n_layers: int, layer_bytes: int) -> int:
    """Largest divisor of ``n_layers`` whose block fits ``BLOCK_BYTES``."""
    return max(d for d in range(1, n_layers + 1)
               if n_layers % d == 0
               and (d == 1 or d * layer_bytes <= BLOCK_BYTES))


def _append_kernel(p_ref, rows_ref, cache_ref, out_ref, *, lc: int, B: int):
    b, c = pl.program_id(0), pl.program_id(1)
    lane = p_ref[b] % TILE
    words = functools.partial(pltpu.bitcast, ty=jnp.uint32)
    n_words = out_ref.shape[1] * jnp.dtype(out_ref.dtype).itemsize // 4
    hit = jax.lax.broadcasted_iota(jnp.int32, (n_words, TILE), 1) == lane
    for i in range(lc):
        j = (c * lc + i) * B + b
        shift = (lane + TILE - j % TILE) % TILE
        col = pltpu.roll(words(rows_ref[j // TILE]), shift, 1)
        out_ref[i] = pltpu.bitcast(jnp.where(hit, col, words(cache_ref[i])),
                                   out_ref.dtype)


def kv_append(cache, rows, pos, *, lead: int, interpret: bool = False):
    """cache ``[*lead, B, Smax, *F]`` (``engages``), rows ``[*lead, B, *F]``,
    pos ``[B]`` or scalar, at least 0.  Returns the cache with each row
    written at its position; one at or beyond ``Smax`` lands at
    ``Smax - 1``.  The result is the cache's buffer (aliased): donate it
    to write in place."""
    Lr = math.prod(cache.shape[:lead])
    B, Smax = cache.shape[lead], cache.shape[lead + 1]
    feat = cache.shape[lead + 2:]
    F, nf = math.prod(feat), len(feat)
    view = jnp.transpose(cache.reshape((Lr, B, Smax) + feat),
                         (0, 1) + tuple(range(3, 3 + nf)) + (2,))
    view = view.reshape(Lr, B, F, Smax)
    # clipped below too: a tile index out of the leaf would be a DMA out
    # of bounds, where the loop's dynamic_slice clamps
    p = jnp.clip(jnp.broadcast_to(pos, (B,)), 0, Smax - 1).astype(jnp.int32)
    groups = -(-Lr * B // TILE)
    cols = jnp.pad(rows.astype(cache.dtype).reshape(Lr * B, F),
                   ((0, groups * TILE - Lr * B), (0, 0)))
    cols = cols.reshape(groups, TILE, F).transpose(0, 2, 1)

    layer_bytes = F * TILE * cache.dtype.itemsize
    lc = _layers_per_block(Lr, layer_bytes)
    tile = pl.BlockSpec((lc, None, F, TILE),
                        lambda b, c, p: (c, b, 0, p[b] // TILE))
    vmem = 2 * cols.size * cols.dtype.itemsize + 4 * lc * layer_bytes
    out = pl.pallas_call(
        functools.partial(_append_kernel, lc=lc, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Lr // lc),
            in_specs=[pl.BlockSpec(cols.shape, lambda b, c, p: (0, 0, 0)),
                      tile],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 * 2 ** 20, vmem + 4 * 2 ** 20)),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * Lr * B * layer_bytes
            + cols.size * cols.dtype.itemsize),
        interpret=interpret,
        name="kv_append",
    )(p, cols, view)
    out = out.reshape((Lr, B) + feat + (Smax,))
    out = jnp.transpose(out, (0, 1, 2 + nf) + tuple(range(2, 2 + nf)))
    return out.reshape(cache.shape)
