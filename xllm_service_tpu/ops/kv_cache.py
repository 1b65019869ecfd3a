"""Paged KV cache representation, including the int8-quantized variant.

Decode attention is HBM-bandwidth-bound: every step streams the whole live
context's K/V through the chip (SURVEY.md §7 hard part 1). Storing the
cache as int8 with one scale per (token-row, kv-head) halves that traffic
— the decisive lever on v5e where HBM BW (~819 GB/s), not MXU FLOPs, caps
decode throughput. The reference's engine-side analog is its KV-cache
quantization config (engine tier, absent submodule; service-visible
contract is only the block/hash layout, which is unchanged here: the
block-size and chained-hash contract hashes TOKEN IDS, not cache bytes).

Representation: a `PagedKV` NamedTuple so the cache flows through
`jax.lax.scan`/`jit`/donation as a pytree wherever a plain array did.

  * bf16 mode:  PagedKV(data=[..., N, H, BS, D] bf16, scale=None)
  * int8 mode:  PagedKV(data=[..., N, H, BS, D] int8,
                        scale=[..., N, H, G, BS] f32), G % 8 == 0

ONE scale layout for both families: sub-channel grouped, G groups per
row on the SUBLANE axis with BS on lanes (GQA: H = Hkv kv-heads, G = 8
groups of D/8 lanes; MLA: H = 1, D = the lane-padded latent dim, G from
mla_scale_groups). The layout is dictated by real-hardware Mosaic DMA
rules (learned on chip, round 3): a DMA slice's shape must be a multiple
of the (8, 128) tile on the last two dims — even at full extent — and
dynamic offsets may ride only on untiled leading dims. [G, BS] per
(block, head) with G % 8 == 0 satisfies that on EVERY tp shard (a
per-head or head-padded plane would go sub-tile once tp slices Hkv below
8, which is exactly the llama tp=8 production layout); heads stay a
leading dim so the scale plane shards identically to the data
(parallel/sharding.kv_scale_sharding). The MLA latent dim C is itself
lane-padded to 128 by `ModelConfig.mla_cache_dim` for the same reason.

Quantization is symmetric per (row, group): scale = max|group| / 127,
data = round(group / scale). Sub-channel grouping also quantizes a
high-magnitude segment independently of its neighbors (ADVICE r2 for the
MLA concat(c_kv, k_pe) row; for GQA it just buys precision). Dequantized
compute stays bf16/f32; only storage and HBM transfer shrink.

Plain jnp.ndarray caches remain accepted everywhere (`as_paged`), so the
bf16 path and all existing callers/tests are untouched.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp


class PagedKV(NamedTuple):
    data: jnp.ndarray
    scale: Optional[jnp.ndarray] = None

    @property
    def quantized(self) -> bool:
        return self.scale is not None

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype


CacheLike = Union[jnp.ndarray, PagedKV]


def _ceil8(x: int) -> int:
    return (x + 7) // 8 * 8


# Sub-channel groups per GQA cache row (head dims are 8-multiples, so 8
# groups of D/8 lanes always divide evenly and the [G, BS] scale tile is
# Mosaic-legal).
GQA_SCALE_GROUPS = 8


def kv_pack_factor(num_kv_heads: int, head_dim: int) -> int:
    """KV heads PACKED per cache row for head_dim < 128 models.

    Mosaic DMA slices need 128-multiple lane extents (chip finding,
    round 3), so a [BS, 64] per-(block, head) tile can never ride the
    Pallas kernels. Packing P = 128 // head_dim consecutive heads into
    one 128-lane row ([N, Hkv/P, BS, P*D]) makes every model with a
    dividing head_dim kernel-eligible: kernels see an ordinary D'=128
    cache; wrappers embed queries block-diagonally (zeros in the other
    heads' lanes keep scores exact) and slice outputs back. Returns 1
    (no packing) when head_dim >= 128, doesn't divide 128, or doesn't
    divide the head count."""
    if head_dim >= 128 or 128 % head_dim or num_kv_heads % (128 // head_dim):
        return 1
    return 128 // head_dim


def mla_scale_groups(
    kv_lora_rank: int, rope_dim: int, cache_dim: Optional[int] = None
) -> int:
    """Scale-group count for an int8 MLA latent cache row.

    Constraints: the group size must (a) divide kv_lora_rank so the
    latent/RoPE boundary falls on a group boundary (the two segments
    quantize independently — ADVICE r2), (b) divide the (lane-padded)
    cache_dim exactly, and (c) yield a group COUNT that is a multiple of
    8, because the groups live on the sublane axis of the pool's
    [..., G, BS] scale plane and Mosaic DMA requires 8-aligned sublane
    extents. Start from gcd(kvr, rope, 128) — a power of two — and halve
    until the count is 8-aligned (always terminates: cache_dim is a
    multiple of 128 when padded, and gsz=1 gives a 128-multiple count)."""
    dim = cache_dim if cache_dim is not None else kv_lora_rank + rope_dim
    gsz = math.gcd(math.gcd(kv_lora_rank, rope_dim), 128)
    while gsz > 1 and (dim % gsz or (dim // gsz) % 8):
        gsz //= 2
    return dim // gsz


def as_paged(cache: CacheLike) -> PagedKV:
    return cache if isinstance(cache, PagedKV) else PagedKV(cache, None)


def raw(cache: CacheLike) -> jnp.ndarray:
    """The storage array (for shape/dtype introspection)."""
    return cache.data if isinstance(cache, PagedKV) else cache


def scale_groups_of(cache: PagedKV) -> int:
    """Sub-channel group count of a quantized pool cache."""
    return cache.scale.shape[-2] if cache.scale is not None else 1


def quantize_rows(
    rows: jnp.ndarray, groups: int = 1
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """rows [..., D] -> (int8 [..., D], scale) symmetric per-row.

    groups=1: one scale per row (scale [...]).
    groups=S: sub-channel quantization — the D lanes split into S equal
    segments, each with its own scale (scale [..., S], groups LAST; pool
    planes store them with BS last — the write paths below relayout)."""
    f = rows.astype(jnp.float32)
    if groups > 1:
        g = f.reshape(*f.shape[:-1], groups, f.shape[-1] // groups)
        scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-1), 1e-8) / 127.0
        q = jnp.clip(jnp.round(g / scale[..., None]), -127, 127)
        return q.reshape(rows.shape).astype(jnp.int8), scale
    amax = jnp.max(jnp.abs(f), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(data: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16):
    """Row-layout inverse of quantize_rows: data int8 [..., D], scale
    [...] or [..., S] (grouped, groups LAST) -> [..., D]. Grouping is
    inferred from rank: scale.ndim == data.ndim means the last scale axis
    is the per-row group count."""
    if scale.ndim == data.ndim:
        S = scale.shape[-1]
        g = data.astype(jnp.float32).reshape(
            *data.shape[:-1], S, data.shape[-1] // S
        )
        return (g * scale[..., None]).reshape(data.shape).astype(dtype)
    return (data.astype(jnp.float32) * scale[..., None]).astype(dtype)


def dequantize_pool(data: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16):
    """Pool-LAYOUT dequant: data int8 [..., H, BS, D] with grouped scale
    [..., H, G, BS] — transpose to row-major groups-last and delegate."""
    return dequantize(data, jnp.swapaxes(scale, -1, -2), dtype)


def set_rows(
    cache: CacheLike,
    data_index,
    scale_index,
    rows: jnp.ndarray,
    mode: str = "token",
):
    """Generic quantize-or-cast cache write: `rows` [..., D] land at
    `cache.data[data_index]` (and, when quantized, their per-row scales at
    `cache.scale[scale_index]`). The single place the write-side
    quantization branch lives — scatter_rows / block import / SP scatter
    all route through here.

    `mode` tells set_rows how the scale slot is laid out so the quantized
    scale values (groups LAST, from quantize_rows) can be relayouted into
    the pool's tile-aligned [..., H, G, BS] planes:
      * "token": scale_index consumed the in-block (BS) position — the
        slot already trails with the group axis; values land as-is.
      * "block": scale_index addresses whole blocks — the slot keeps the
        pool's trailing [G, BS] dims, so the quantized [..., BS, G]
        values transpose.
    """
    if isinstance(cache, PagedKV) and cache.quantized:
        q, s = quantize_rows(rows, cache.scale.shape[-2])
        if mode == "block":
            s = jnp.swapaxes(s, -1, -2)  # [..., G, BS]
        return PagedKV(
            cache.data.at[data_index].set(q),
            cache.scale.at[scale_index].set(s),
        )
    if isinstance(cache, PagedKV):
        return PagedKV(
            cache.data.at[data_index].set(rows.astype(cache.data.dtype)),
            None,
        )
    return cache.at[data_index].set(rows.astype(cache.dtype))


def scatter_rows(
    cache: CacheLike,
    blk: jnp.ndarray,  # [T] int32 block ids (0 = garbage block)
    offset: jnp.ndarray,  # [T] int32 in-block offsets
    rows: jnp.ndarray,  # [T, Hkv, D] model-dtype K or V rows
    layer=None,  # int32 scalar: the cache is the STACK [L, N, Hkv, BS, D]
) -> CacheLike:
    """Write per-token rows into cache slots. Without `layer` the cache
    is one layer's [N, Hkv, BS, D] (models/deepseek.py's scans slice the
    layer axis off); with it the cache is the stacked pool and the rows
    land at [layer, blk, :, offset, :] — the llama-family layer scans
    carry the stack and never slice a layer out (docs/KV_CACHE.md).

    Into the stack every LEADING dim is indexed (the heads by an iota)
    and only trailing dims are windows: the scatter XLA needs no
    transposed copy of its operand for. Indexed as `[layer, blk, :, off]`
    the window (heads, D) straddles the scattered `off`, and XLA copies
    the whole stack into another dim order and back, every layer (read
    in the CPU's and the chip's HLO, PR 29)."""
    if layer is None:
        return set_rows(
            cache,
            (blk, slice(None), offset, slice(None)),
            # Pool scales are [N, H, G, BS]: offset picks the BS lane, the
            # slices keep heads and groups -> slot [T, H, G], matching the
            # groups-last quantized values exactly.
            (blk, slice(None), slice(None), offset),
            rows,
            mode="token",
        )
    heads = jnp.arange(rows.shape[-2], dtype=jnp.int32)
    b, h, o = blk[:, None], heads[None, :], offset[:, None]
    scale_index = None  # read by set_rows for quantized pools only
    if isinstance(cache, PagedKV) and cache.quantized:
        # [L, N, H, G, BS] scale planes: slot [T, H, G], every dim indexed.
        groups = jnp.arange(cache.scale.shape[-2], dtype=jnp.int32)
        scale_index = (layer, b[..., None], h[..., None], groups, o[..., None])
    return set_rows(
        cache, (layer, b, h, o, slice(None)), scale_index, rows, mode="token"
    )


def set_blocks(cache: CacheLike, ids: jnp.ndarray, blocks: jnp.ndarray):
    """Write whole blocks [..., P, heads, BS, D] at block ids along the N
    axis of a pooled cache [..., N, heads, BS, D] (leading layer dims
    untouched). Used by the PD/tier migration import path."""
    idx = (slice(None), ids)
    return set_rows(cache, idx, idx, blocks, mode="block")


def pack_rows(rows: jnp.ndarray, cache: "CacheLike") -> jnp.ndarray:
    """Relayout per-token rows [..., Hkv, D] to a cache's packed row shape
    [..., Hc, Dc] (consecutive heads concatenate on lanes — the inverse of
    unpack_rows). No-op for unpacked caches. The ONE place the write-side
    packing reshape lives."""
    hc = raw(cache).shape[-3]
    if hc == rows.shape[-2]:
        return rows
    return rows.reshape(*rows.shape[:-2], hc, -1)


def unpack_rows(x: jnp.ndarray, pack: int) -> jnp.ndarray:
    """Undo kv_pack_factor packing on a gathered cache slice
    [..., Hc, BS, Dc] -> [..., Hc*pack, BS, Dc/pack] (consecutive heads
    were concatenated on lanes, so head order is preserved)."""
    if pack == 1:
        return x
    *lead, hc, bs, dc = x.shape
    x = x.reshape(*lead, hc, bs, pack, dc // pack)
    x = jnp.moveaxis(x, -2, -3)
    return x.reshape(*lead, hc * pack, bs, dc // pack)


def quantize_pool(cache: jnp.ndarray, groups: int = GQA_SCALE_GROUPS) -> PagedKV:
    """Quantize a whole dense cache array [..., N, H, BS, D] into a
    pool-LAYOUT PagedKV ([..., N, H, G, BS] scales). Test/bench helper —
    production pools allocate zeroed via alloc_cache and quantize
    incrementally through set_rows."""
    if groups % 8 or cache.shape[-1] % groups:
        raise ValueError(
            f"quantize_pool: groups={groups} must be a multiple of 8 "
            f"dividing the row dim {cache.shape[-1]} (see alloc_cache)"
        )
    q, s = quantize_rows(cache, groups)
    return PagedKV(q, jnp.swapaxes(s, -1, -2))


def gather_block(cache: CacheLike, block_id, dtype=jnp.bfloat16, layer=None):
    """One block [Hkv, BS, D] dequantized to `dtype` (blockwise prefill).
    `layer` indexes a stacked pool: the block is gathered, never a layer."""
    idx = block_id if layer is None else (layer, block_id)
    if isinstance(cache, PagedKV) and cache.quantized:
        return dequantize_pool(cache.data[idx], cache.scale[idx], dtype)
    return raw(cache)[idx].astype(dtype)


def gather_blocks(
    cache: CacheLike, block_table: jnp.ndarray, dtype=None, layer=None
):
    """Gather + dequantize blocks via a block table of any shape [...B];
    returns [...B, Hkv, BS, D]. `layer` indexes a stacked pool
    (`cache[layer, table]`: blocks are gathered, never a layer)."""
    idx = block_table if layer is None else (layer, block_table)
    if isinstance(cache, PagedKV) and cache.quantized:
        return dequantize_pool(
            cache.data[idx], cache.scale[idx], dtype or jnp.bfloat16
        )
    out = raw(cache)[idx]
    return out if dtype is None else out.astype(dtype)


def alloc_cache(
    shape: Tuple[int, ...],  # [..., N, H, BS, D]
    dtype,
    quantized: bool,
    scale_groups: int = GQA_SCALE_GROUPS,
) -> PagedKV:
    if quantized:
        if scale_groups % 8 or shape[-1] % scale_groups:
            raise ValueError(
                f"scale_groups={scale_groups} must be a multiple of 8 "
                f"dividing the row dim {shape[-1]} (Mosaic sublane tiling"
                f" of the [..., G, BS] scale plane)"
            )
        # [..., N, H, G, BS] — groups on sublanes, BS on lanes.
        scale_shape = shape[:-2] + (scale_groups, shape[-2])
        return PagedKV(
            jnp.zeros(shape, jnp.int8), jnp.zeros(scale_shape, jnp.float32)
        )
    return PagedKV(jnp.zeros(shape, dtype), None)
