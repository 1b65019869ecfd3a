"""The serving steps' cache write: a step's new K/V rows into the carried
stack, in place.

The step programs carry the STACKED pool through their layer scan
(models/llama.py `_scan_layers`; the latent stack of models/deepseek.py
rides the same carry) and land each layer's new rows in it without moving
it. Where the rows go is the same for every layer, so it is
worked out once per step, outside the scan (`write_plan`); each layer then
calls `write_kv` with its rows and its index.

Two routes, one result outside garbage block 0. Where the attention
kernels serve the pool (ops/attention.cache_kernel_route: on the chip,
whole tiles, per shard on a tp mesh) the rows go in through the Pallas
tile write ops/pallas/kv_write.py; elsewhere through XLA's scatter
(ops/kv_cache.scatter_rows with a layer index). Both write in place; the
kernel is there because the chip's scatter walks row by row: against the
scatter alone it is worth 2.3-3.7 % of `out_tokens_per_s` in decode-batch
and 11-17 % of `ttft_p50_ms` in chat-steady (PERF.md, PR 29 review round).

This module sits ABOVE ops/kv_cache.py (the pool's layout and its plain
reads and writes) and ops/attention.py (the kernels' platform, tile and
shard gate): it imports both, neither imports it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops.kv_cache import (
    CacheLike,
    PagedKV,
    as_paged,
    pack_rows,
    quantize_rows,
    raw,
    scatter_rows,
)
from xllm_service_tpu.ops.pallas.kv_write import kv_write_kernel


class _Units(NamedTuple):
    """Tiles one step's rows touch, at one granularity (docs/KV_CACHE.md,
    ops/pallas/kv_write.py): `tile` token positions per unit, `count`
    units per sequence. The units stand LIVE FIRST (a stable partition,
    made once a step): the launch walks the first `live` of them."""

    blk: jnp.ndarray  # [S*count] block id (0 = garbage, for dead units)
    sub: jnp.ndarray  # [S*count] tile index inside the block
    lo: jnp.ndarray  # [S*count] first new position of the tile
    hi: jnp.ndarray  # [S*count] one past the last (lo == hi: dead unit)
    order: jnp.ndarray  # [S*count] the unit's place in sequence order,
    # s*count + c: which of _unit_tiles' tiles it takes
    live: jnp.ndarray  # [] how many units write anything
    shift: jnp.ndarray  # [S] where a sequence's first tile starts in its
    # front-padded rows (see _unit_tiles)
    tile: int
    count: int


class WritePlan(NamedTuple):
    """Where one step's new K/V rows go: per token (`blk`, `off`) for the
    XLA scatter, or per tile (`units`, `scale_units`) for the Pallas
    write; a plan holds one or the other."""

    blk: Optional[jnp.ndarray]  # [S*W] block id per token (0 = garbage)
    off: Optional[jnp.ndarray]  # [S*W] in-block offset per token
    width: int  # W: token positions per sequence in this step
    units: Optional[_Units] = None
    scale_units: Optional[_Units] = None  # int8 pools: BS positions a tile
    ctx: Optional[tuple] = None  # (mesh, axis): one kernel launch per shard
    interpret: bool = False


def _plan_units(tables, start, length, width: int, bs: int, tile: int):
    count = (width + 2 * tile - 2) // tile  # tiles `width` rows can touch
    first = (start // tile)[:, None] + jnp.arange(count, dtype=jnp.int32)
    pos = first * tile  # [S, count] position of each tile's first row
    lo = jnp.clip(start[:, None] - pos, 0, tile)
    hi = jnp.clip((start + length)[:, None] - pos, 0, tile)
    live = hi > lo
    bi = jnp.minimum(pos // bs, tables.shape[1] - 1)
    blk = jnp.where(live, jnp.take_along_axis(tables, bi, axis=1), 0)
    sub = jnp.where(live, (pos % bs) // tile, 0)
    order = jnp.argsort(~live.reshape(-1), stable=True).astype(jnp.int32)
    return _Units(
        *(a.reshape(-1)[order] for a in (blk, sub, lo, hi)), order,
        jnp.sum(live, dtype=jnp.int32), tile - start % tile, tile, count,
    )


@region("cache_write")
def write_plan(
    cache: CacheLike,  # a stacked pool, read for its geometry only
    tables: jnp.ndarray,  # [S, CB] int32 block tables
    start: jnp.ndarray,  # [S] int32 position of each sequence's first row
    length: jnp.ndarray,  # [S] int32 rows to write (0 = nothing)
    width: int,  # W: rows per sequence in the step's [S, W] layout
    interpret: bool = False,  # tests: the Pallas write, interpreted
) -> WritePlan:
    """Plan one step's cache write: sequence s lands rows [0, length[s])
    of its W at positions start[s].. of its block table. Rows past
    `length` go nowhere (the scatter route sends them to garbage block 0,
    offset 0, as it always has).

    The Pallas route is taken where the attention kernels serve the pool
    (attention.cache_kernel_route); a tile is the dtype's native sublane
    count (16 rows of bf16)."""
    data = raw(cache)
    bs = data.shape[-2]
    start = start.astype(jnp.int32)
    length = length.astype(jnp.int32)
    kernel, ctx = attention.cache_kernel_route(cache, interpret)
    if kernel:
        quantized = isinstance(cache, PagedKV) and cache.quantized
        return WritePlan(
            None, None, width,
            _plan_units(
                tables, start, length, width, bs, 32 // data.dtype.itemsize
            ),
            _plan_units(tables, start, length, width, bs, bs)
            if quantized else None,
            ctx,
            interpret,
        )
    offs = jnp.arange(width, dtype=jnp.int32)[None, :]
    pos = start[:, None] + offs
    valid = offs < length[:, None]
    blk = jnp.where(valid, jnp.take_along_axis(tables, pos // bs, axis=1), 0)
    off = jnp.where(valid, pos % bs, 0)
    return WritePlan(blk.reshape(-1), off.reshape(-1), width)


def _unit_tiles(x: jnp.ndarray, units: _Units, lanes: bool) -> jnp.ndarray:
    """Cut a step's rows x [S, W, Hc, Y] into the tiles its units name:
    [S*count, Hc, tile, Y], or [S*count, Hc, Y, tile] with the token axis
    on `lanes` (scale planes). Row j of a sequence's tiles is position
    first_tile*tile + j, i.e. its row j - start % tile: front-pad by one
    tile and slice from `shift`. A single row (W == 1) is handed over as
    it is, [S, Hc, 1, Y]: the kernel broadcasts it over the tile and the
    unit's lo/hi pick its place."""
    S, W, Hc, Y = x.shape
    if W == 1:
        return x[:, 0, :, :, None] if lanes else x[:, 0, :, None, :]
    g, C = units.tile, units.count
    xp = jnp.pad(x, ((0, 0), (g, C * g - W), (0, 0), (0, 0)))
    t = jax.vmap(
        lambda r, o: jax.lax.dynamic_slice_in_dim(r, o, C * g, axis=0)
    )(xp, units.shift)
    t = jnp.moveaxis(t.reshape(S, C, g, Hc, Y), 2, 4 if lanes else 3)
    return t.reshape(S * C, *t.shape[2:])


def _write_units(caches, rows, units: _Units, layer, lanes, plan):
    """One Pallas launch over `units` for every (cache, rows) pair; under
    a tp shard context one launch per shard over its own heads (the pool
    and the rows both carry the head axis; the units replicate)."""
    tiles = tuple(_unit_tiles(r, units, lanes) for r in rows)

    def body(caches, tiles, blk, sub, lo, hi, order, live, layer):
        return kv_write_kernel(
            caches, tiles, blk, sub, lo, hi, order, live, layer,
            tile=caches[0].shape[-2] if lanes else units.tile,
            axis=-1 if lanes else -2, interpret=plan.interpret,
        )

    if plan.ctx is not None:
        mesh, axis = plan.ctx
        pool, tile = P(None, None, axis), P(None, axis)
        body = jax.shard_map(
            body, mesh=mesh,
            in_specs=((pool,) * len(caches), (tile,) * len(tiles))
            + (P(),) * 7,
            out_specs=(pool,) * len(caches), check_vma=False,
        )
    return body(
        tuple(caches), tiles, units.blk, units.sub, units.lo, units.hi,
        units.order, units.live, jnp.asarray(layer, jnp.int32),
    )


@region("cache_write")
def write_rows(
    caches: Tuple[CacheLike, ...],  # stacked pools [L, N, Hc, BS, Dc]
    plan: WritePlan,
    rows: Tuple[jnp.ndarray, ...],  # one [S*W, H, D] per pool, plan order
    layer,  # int32 scalar
) -> Tuple[CacheLike, ...]:
    """Land one layer's new rows in the stacked pools, in place: one pool
    for a one-cache family (the latent stack of models/deepseek.py), K
    and V together for the llama family (`write_kv`). Packed caches
    (Hc < H, see kv_pack_factor) take the rows reshaped to the packed
    layout; int8 caches quantize them on the way.

    The plan says which route (module docstring); neither moves a pool."""
    rows = tuple(pack_rows(r, c) for r, c in zip(rows, caches))
    if plan.units is None:
        return tuple(
            scatter_rows(c, plan.blk, plan.off, r, layer)
            for c, r in zip(caches, rows)
        )
    bare = not isinstance(caches[0], PagedKV)
    caches = tuple(as_paged(c) for c in caches)
    seg = lambda x: x.reshape(-1, plan.width, *x.shape[1:])
    if caches[0].quantized:
        groups = caches[0].scale.shape[-2]
        rows, scales = zip(*(quantize_rows(r, groups) for r in rows))
        scales = _write_units(
            tuple(c.scale for c in caches), tuple(seg(x) for x in scales),
            plan.scale_units, layer, True, plan,
        )
    else:
        rows = tuple(r.astype(c.dtype) for r, c in zip(rows, caches))
        scales = (None,) * len(caches)
    data = _write_units(
        tuple(c.data for c in caches), tuple(seg(r) for r in rows),
        plan.units, layer, False, plan,
    )
    if bare:
        return tuple(data)
    return tuple(PagedKV(d, sc) for d, sc in zip(data, scales))


def write_kv(
    k_cache: CacheLike,  # stacked pools [L, N, Hc, BS, Dc]
    v_cache: CacheLike,
    plan: WritePlan,
    k: jnp.ndarray,  # [S*W, Hkv, D] this layer's new rows, plan order
    v: jnp.ndarray,
    layer,  # int32 scalar
) -> Tuple[CacheLike, CacheLike]:
    """write_rows for the K and V pools of one layer, one launch."""
    return write_rows((k_cache, v_cache), plan, (k, v), layer)
