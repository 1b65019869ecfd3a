"""Pallas TPU kernel of the Mamba-2 state pool (ops/mamba.py has the
equations, the layout and the `jax.numpy` route this must equal).

A sequence's SSM state is one slot of `S [Lm, slots, H/k, N, k*P]`: `k`
heads side by side on the lanes (k*P = 128 lanes at P = 64: two heads), the
state dimension N on the sublanes, so that the read-out `y = S C` is a sum
over SUBLANES (plain vector adds, one 8-row fold at the end) and not 8192
cross-lane reductions a row and layer, and every tile is a whole
(8, 128) float32 tile. The kernel aliases the pool
(`input_output_aliases`) and touches only the slots of the step's live
rows, in the pool's resident tiling, so the stack that rides the layer
scan's carry never moves (PERF.md, PR 29 and PR 31).

`mamba_update_kernel` (decode: one token into each live row's slot; the
slot IS the row, so there is no slot table): grid (unit, head tile). Per
lane row `[N, k*P]` (a head pair at P = 64, one head at P = 128) the body is

    S <- a * S + B (x) (dt x)        a, dt x along lanes; B along sublanes
    y  = sum_n S[n, :] C[n]

all on the VPU in float32 (B and C arrive broadcast along lanes, made by
XLA: 64 KiB a row beside the 4 MiB of state). It reads and writes every
state byte of a live row once: HBM-bound.

**Groups.** With G > 1 groups of B and C the operands are `[R, G, N,
lanes]` and a head tile's block spec picks ITS group's plane: a tile is
a divisor of the lane rows of ONE group (`head_tile`), so it never
straddles two, and the body is the same. At G = 1 the operands stay
`[R, N, lanes]` and the launch is what it was (Granite's lowered text
does not move). A lane row that held heads of two groups (k > 1 and
(H / G) % k != 0) is not a shape of this kernel (`ops/mamba.py
kernel_eligible`).

**The tile.** A grid step moves `tile` lane rows of `N x lanes` float32
in and out, double-buffered: 4 x tile x plane bytes of VMEM. At Granite's
64 KiB plane (N = 128) 16 rows are 1 MiB a block; at a 128 KiB plane
(N = 256) `head_tile` halves the rows to keep the block at TILE_BYTES
(PERF.md section 6, PR 53, has the chip's reading of 8 against 16 rows).

Live rows come first in the unit order; a dead unit (an inactive decode
row) keeps the block indices of the last live step, so Pallas moves
nothing for it and its body is skipped. With no live row at all the one
block that is visited is copied through.

The chunk form of a prefill stays in XLA (ops/mamba.py `chunk_update`,
and PERF.md for the reading that decided it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_TILE = 16  # lane rows (head pairs at P = 64) of one grid step: 1 MiB
TILE_BYTES = HEAD_TILE * 128 * 128 * 4  # ... which is the block a step moves


def head_tile(rows: int, plane_bytes: int = 128 * 128 * 4, groups: int = 1) -> int:
    """Lane rows of one grid step: the largest divisor of `rows` (the lane
    rows of ONE group) that is at most HEAD_TILE, keeps the block at
    TILE_BYTES, and is whole 8-sublane tiles of the per-head operands
    `[R, HP, lanes]` or all of their rows (Mosaic's block rule). 0: none."""
    most = max(1, min(HEAD_TILE, TILE_BYTES // plane_bytes))
    fits = [t for t in range(1, most + 1)
            if rows % t == 0 and (t % 8 == 0 or (groups == 1 and t == rows))]
    return max(fits, default=0)


def _update_kernel_body(meta, rows, s_ref, a_ref, dtx_ref, b_ref, c_ref,
                        o_ref, y_ref, *, tile: int):
    u = pl.program_id(0)

    @pl.when(u < meta[0])
    def _live():
        bb, cb = b_ref[...], c_ref[...]  # [N, lanes]
        for j in range(tile):
            a = a_ref[pl.ds(j, 1), :]  # [1, lanes]: the row's decay
            dtx = dtx_ref[pl.ds(j, 1), :]
            s = s_ref[j].astype(jnp.float32) * a + bb * dtx
            o_ref[j] = s.astype(o_ref.dtype)
            y_ref[pl.ds(j, 1), :] = jnp.sum(s * cb, axis=0, keepdims=True)

    @pl.when(meta[0] == 0)
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


def mamba_update_kernel(S, layer, unit_rows, n_live, a, dtx, bb, cb, *,
                        interpret=False):
    """S [Lm, slots, HP, N, lanes] (slots >= R; a row's slot is its
    index); per decode row: a, dtx [R, HP, lanes] f32 (the decay and
    dt * x of each head along its lanes), bb, cb f32, B and C broadcast
    along lanes: [R, N, lanes] (one group) or [R, G, N, lanes] (lane rows
    [g HP / G, (g + 1) HP / G) read group g). unit_rows [R]: live rows
    first, then the last live row repeated; n_live how many are live.
    Returns (S', y [R, HP, lanes] f32; rows of dead units are not
    written)."""
    _, _, HP, N, lanes = S.shape
    R = a.shape[0]
    G = bb.shape[1] if bb.ndim == 4 else 1
    if HP % G:
        raise ValueError(f"mamba_update_kernel: {HP} lane rows over {G} groups")
    # (interpret mode takes any divisor: the tests' shapes are small)
    tile = head_tile(HP // G, N * lanes * 4, G) or (1 if interpret else 0)
    if not tile or (HP // G) % tile:
        raise ValueError(
            f"mamba_update_kernel: no tile of whole 8-row blocks inside a group of "
            f"{HP // G} lane rows (tile {tile})"
        )
    T = HP // tile
    meta = jnp.stack([jnp.asarray(n_live, jnp.int32), jnp.asarray(layer, jnp.int32)])

    def tile_of(u, t, meta):
        return jnp.where(u < meta[0], t, T - 1)

    def state(u, t, meta, rows):
        return (meta[1], rows[u], tile_of(u, t, meta), 0, 0)

    def head(u, t, meta, rows):
        return (rows[u], tile_of(u, t, meta), 0)

    def whole(u, t, meta, rows):
        return (rows[u], 0, 0)

    def group(u, t, meta, rows):  # the plane of the tile's group
        return (rows[u], tile_of(u, t, meta) // (T // G), 0, 0)

    s_spec = pl.BlockSpec((None, None, tile, N, lanes), state)
    h_spec = pl.BlockSpec((None, tile, lanes), head)
    if bb.ndim == 4:
        n_spec = pl.BlockSpec((None, None, N, lanes), group)
    else:
        n_spec = pl.BlockSpec((None, N, lanes), whole)
    return pl.pallas_call(
        functools.partial(_update_kernel_body, tile=tile),
        name="mamba_update_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, T),
            in_specs=[s_spec, h_spec, h_spec, n_spec, n_spec],
            out_specs=[s_spec, h_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, HP, lanes), jnp.float32),
        ],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=5 * R * HP * N * lanes, transcendentals=0,
            bytes_accessed=2 * R * HP * N * lanes * S.dtype.itemsize,
        ),
        interpret=interpret,
    )(meta, unit_rows.astype(jnp.int32), S, a, dtx, bb, cb)
