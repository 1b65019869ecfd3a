"""Static Mosaic DMA layout-legality rules, enforced at trace time.

Round 3's first silicon contact surfaced two Mosaic rules that interpret
mode NEVER enforces (BASELINE.md, third round-3 session) — an entire
round-2 int8 scale layout passed every CPU test and failed on first chip
contact. This module encodes those rules so a kernel layout can never
again pass interpret and fail silicon:

  1. **Tile-multiple extents.** A DMA slice's extents on the last two
     (tiled) axes must be (8, 128)-tile multiples even at full extent —
     exactly the bound the chip enforced; dtype-finer tiling (bf16
     (16,128), int8 (32,128)) has not been observed to reject 8-row
     multiples, so 8 is the rule until silicon says otherwise. The
     round-2/3 failures this catches: a flat [N, BS*G] f32 scale plane
     sliced [1, BS*G] (1 sublane row), a [..., BS, G] plane with G=8
     lanes, and the unpadded [BS, 576] MLA latent row (576 % 128 != 0).
  2. **Dynamic offsets ride only on untiled leading dims.** A traced
     (non-Python-int) index may address any dim strictly before the last
     two; the tiled trailing dims take only static offsets.

`async_copy` is a drop-in for `pltpu.make_async_copy` that validates
both endpoint shapes (shapes are static at Pallas trace time, so these
are plain Python checks — zero runtime cost on chip, and they fire in
interpret mode and under CPU tests alike). `check_slice_indices`
validates rule 2 for an `.at[...]` index tuple; kernels route their
`.at` slicing through `checked_at`.

tests/test_pallas_kernels.py pins the ruleset: the known-bad round-2
layouts are rejected, every current kernel's copies pass.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


class MosaicLayoutError(ValueError):
    """A DMA layout that interpret mode accepts but real Mosaic rejects."""


SUBLANE = 8  # empirically enforced sublane granularity (see module doc)


def check_copy_shape(shape: Sequence[int], dtype, what: str = "copy") -> None:
    """Rule 1: extents on the last two dims must be (8, 128) multiples."""
    if len(shape) == 0:
        return
    lanes = shape[-1]
    if lanes % 128:
        raise MosaicLayoutError(
            f"{what}: lane extent {lanes} (shape {tuple(shape)}, dtype "
            f"{jnp.dtype(dtype).name}) is not a multiple of 128 — Mosaic "
            f"rejects this DMA on real hardware even though interpret "
            f"mode accepts it (chip finding, round 3). Lane-pad the "
            f"layout (see kv_cache.mla_cache_dim / kv_pack_factor)."
        )
    if len(shape) >= 2 and shape[-2] % SUBLANE:
        raise MosaicLayoutError(
            f"{what}: sublane extent {shape[-2]} (shape {tuple(shape)}, "
            f"dtype {jnp.dtype(dtype).name}) is not a multiple of the "
            f"{SUBLANE}-row tile — Mosaic rejects sub-tile sublane "
            f"slices on real hardware (the round-2 flat scale plane "
            f"failed exactly here). Group rows so the slice covers "
            f"whole tiles (see kv_cache GQA_SCALE_GROUPS)."
        )


def check_slice_indices(ndim: int, idx: Sequence[Any], what: str = "at") -> None:
    """Rule 2: dynamic (traced) offsets only on dims before the last two.

    `idx` holds the per-dim indices passed to `.at[...]` (ints, traced
    scalars, or `pl.ds(...)` objects). A python int is static; anything
    else is treated as dynamic unless it is a `pl.ds` whose start is a
    python int."""
    for d, ix in enumerate(idx):
        if isinstance(ix, int) or ix is None or isinstance(ix, slice):
            continue
        start = getattr(ix, "start", None)
        if start is not None and isinstance(start, int):
            continue  # static pl.ds
        if d >= ndim - 2:
            raise MosaicLayoutError(
                f"{what}: dynamic offset on dim {d} of a {ndim}-d ref — "
                f"Mosaic only accepts dynamic DMA offsets on untiled "
                f"leading dims (before the last two). Restructure the "
                f"layout so the dynamic index (block id, head) rides a "
                f"leading dim (chip finding, round 3)."
            )


def checked_at(ref, *idx):
    """`ref.at[*idx]` with rule-2 validation on the index tuple."""
    check_slice_indices(len(ref.shape), idx)
    return ref.at[tuple(idx)]


def async_copy(src, dst, sem):
    """`pltpu.make_async_copy` with rule-1 validation on both endpoints.

    The copied extents are the (already-sliced) ref shapes; dims of size
    1 at the front (e.g. the [1, BS, C] result of `.at[blk, 0]` keeping
    a unit axis) don't participate in tiling and are ignored beyond the
    last two."""
    check_copy_shape(src.shape, src.dtype, what="DMA src")
    check_copy_shape(dst.shape, dst.dtype, what="DMA dst")
    return pltpu.make_async_copy(src, dst, sem)
