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
head pair `[N, k*P]` the body is

    S <- a * S + B (x) (dt x)        a, dt x along lanes; B along sublanes
    y  = sum_n S[n, :] C[n]

all on the VPU in float32 (B and C arrive broadcast along lanes, made by
XLA: 64 KiB a row beside the 4 MiB of state). It reads and writes every
state byte of a live row once: HBM-bound.

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


def head_tile(rows: int) -> int:
    """The largest divisor of `rows` that is at most HEAD_TILE."""
    return max(t for t in range(1, HEAD_TILE + 1) if rows % t == 0)


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
    dt * x of each head along its lanes), bb, cb [R, N, lanes] f32 (B and
    C broadcast along lanes). unit_rows [R]: live rows first, then the
    last live row repeated; n_live how many are live. Returns (S',
    y [R, HP, lanes] f32; rows of dead units are not written)."""
    _, _, HP, N, lanes = S.shape
    R = a.shape[0]
    tile = head_tile(HP)
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

    s_spec = pl.BlockSpec((None, None, tile, N, lanes), state)
    h_spec = pl.BlockSpec((None, tile, lanes), head)
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
