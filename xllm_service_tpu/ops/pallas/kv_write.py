"""Pallas TPU in-place write of a step's new K/V rows into the stacked pool.

The serving steps carry the whole pool `[L, N, Hc, BS, D]` through the
layer scan and must land at most a few hundred new rows per layer in it
without moving it. XLA's scatter indexed the natural way
(`[layer, blk, :, off]`) re-tiles the WHOLE stack around every update on
the chip (compiled for a v5e, PERF.md PR 29) — worse than the per-layer
copies the carry was introduced to remove. With every leading dim indexed
(kv_cache.scatter_rows) it writes in place, row by row: 1.8 ms a step for
128 decode rows and 3.7 ms for one 256-token chunk, measured on a v5e over
the benchmark's 36-layer stacks. This kernel aliases the pool
(`input_output_aliases`) and rewrites only the tiles the rows touch, in
the pool's resident tiling: 1.4 ms and 0.34 ms for the same writes.

Mosaic DMA rules shape it (ops/pallas/mosaic_rules.py): a DMA moves whole
(8, 128)-multiples on the last two dims and takes dynamic offsets only in
tile units, so a single row cannot be written on its own. A write is a
read-modify-write of one TILE — `tile` consecutive rows of one block,
all local heads: `[Hc, tile, D]`, 16 rows for bf16 — described by a UNIT:

  blk, sub   which tile: block id and tile index inside the block
  lo, hi     the rows (data) or lanes (int8 scale planes, where the
             token axis lies on lanes) of the tile that are new

The grid walks the LIVE units; Pallas pipelines the tile in, the body
selects `lo <= i < hi ? new : old`, and the tile goes back to where it
came from. Units are made outside the layer scan, once per step
(ops/kv_write.write_plan), live ones first: the grid's bound is their
count (a dynamic grid dimension), so a slot that holds no sequence costs
no grid step, and `order[u]` says which of the step's new tiles unit u
takes (read in the index map: the rows are not gathered). A unit with
`lo == hi` writes nothing and points at the reserved garbage block 0; the
one such unit a launch visits when nothing is live puts that block's tile
back as it was. No two units of one call name the same live tile (a
sequence's rows are consecutive positions and sequences own their
blocks), so the pipelined read of the next tile never races a write.

One body serves data tiles (mask along sublanes) and int8 scale tiles
`[Hc, G, BS]` (mask along lanes), K and V in one launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kv_write_kernel(
    # scalar prefetch
    blk_ref,    # [U] SMEM — block id of each unit (index maps only)
    sub_ref,    # [U] SMEM — tile index inside the block (index maps only)
    lo_ref,     # [U] SMEM — first new row/lane of the tile
    hi_ref,     # [U] SMEM — one past the last new row/lane
    order_ref,  # [U] SMEM — which new tile the unit takes (index maps only)
    layer_ref,  # [1] SMEM — which layer of the stack (index maps only)
    *refs,      # n new tiles [Hc, tile|1, D], n old tiles [Hc, tile, D],
    # then the n output tiles (the old tiles' home in the aliased pool)
    n: int,
    axis: int,  # -2: rows of a data tile; -1: lanes of a scale tile
):
    u = pl.program_id(0)
    lo, hi = lo_ref[u], hi_ref[u]
    for new, old, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        i = jax.lax.broadcasted_iota(jnp.int32, old.shape, old.ndim + axis)
        # Select in f32: every cache dtype (bf16, int8, f32) round-trips
        # through it exactly, and a 32-bit select needs no packed-mask
        # relayout. A one-row `new` broadcasts over the tile.
        out[...] = jnp.where(
            (i >= lo) & (i < hi),
            new[...].astype(jnp.float32),
            old[...].astype(jnp.float32),
        ).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "axis", "interpret"))
def kv_write_kernel(
    caches,   # tuple of stacked pools [L, N, Hc, X, Y] (K and V, or scales)
    tiles,    # tuple of new tiles [U, Hc, x, y], x/y the tile's or 1
    blk: jnp.ndarray,    # [U] int32, live units first
    sub: jnp.ndarray,    # [U] int32
    lo: jnp.ndarray,     # [U] int32
    hi: jnp.ndarray,     # [U] int32
    order: jnp.ndarray,  # [U] int32: unit u takes tiles[order[u]]
    n_live,              # int32 scalar: the units before this one are live
    layer,               # int32 scalar
    tile: int,           # rows per tile along X (X itself for scale planes)
    axis: int,
    interpret: bool = False,
):
    """Write `tiles` into `caches` in place; returns the updated caches
    (the same buffers: every cache operand is aliased to its output)."""
    n = len(caches)
    U = blk.shape[0]
    Hc = caches[0].shape[2]
    pre = 6  # scalar-prefetch operands ahead of the tensor inputs

    def pool_map(u, blk, sub, lo, hi, order, layer):
        return layer[0], blk[u], 0, sub[u], 0

    def tile_map(u, blk, sub, lo, hi, order, layer):
        return order[u], 0, 0, 0

    # one spec a pool: the K and V rows of a family may differ in lanes
    pool_specs = [
        pl.BlockSpec((None, None, Hc, tile, c.shape[-1]), pool_map)
        for c in caches
    ]
    tile_specs = [
        pl.BlockSpec((None,) + t.shape[1:], tile_map) for t in tiles
    ]
    # The grid's bound follows the step: the live units, and one (dead)
    # unit where there is none, so that the launch is never empty.
    visited = jnp.maximum(jnp.asarray(n_live, jnp.int32), 1)
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, n=n, axis=axis),
        name="kv_write_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=pre,
            grid=(visited,),
            in_specs=tile_specs + pool_specs,
            out_specs=pool_specs,
        ),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        input_output_aliases={pre + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=0,
            transcendentals=0,
            bytes_accessed=sum(
                2 * U * Hc * tile * c.shape[-1] * c.dtype.itemsize
                for c in caches
            ),
        ),
        interpret=interpret,
    )(
        blk.astype(jnp.int32), sub.astype(jnp.int32),
        lo.astype(jnp.int32), hi.astype(jnp.int32), order.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *tiles, *caches,
    )
    return tuple(out)
