"""Pallas TPU kernel of the KDA state pool (ops/kda.py has the equations,
the layout and the `jax.numpy` route this must equal).

A sequence's delta-rule state is one slot of `S [Lk, slots, H, dk, dv]`
float32: a head's `[dk, dv]` matrix with the KEY lane on the sublanes and
the value lane on the lanes, whole (8, 128) tiles at dk = dv = 128. Every
reduction of the update is over the key lane, so it is a sum over
SUBLANES (plain vector adds and one 8-row fold, as ops/pallas/mamba.py),
and what multiplies a key lane (the decay, k, beta k, q) must run along
the sublanes. Those four vectors arrive as COLUMNS: `cols [R, T, dk,
4 * tile]`, one head tile's `tile` heads of each vector side by side on
the lanes (128 lanes at tile = 32: nothing padded, 64 KiB beside the
tile's 2 MiB of state); XLA makes them from the `[R, H, dk]` rows, which
is a transpose of 128 KiB a row and layer. The kernel aliases the pool
(`input_output_aliases`) and touches only the slots of the step's live
rows, in the pool's resident tiling, so the stack that rides the layer
scan's carry never moves.

`kda_update_kernel` (decode: one token into each live row's slot; the
slot IS the row): grid (unit, head tile). Per head the body is

    S <- alpha * S                      alpha along the sublanes
    u  = v - sum_k S[k, :] k[k]         the delta rule's correction, a row
    S <- S + (beta k) (x) u
    o  = sum_k S[k, :] q[k]

all on the VPU in float32: about ten operations a state entry beside its
one read and one write from HBM, which bound it.

Live rows come first in the unit order; a dead unit keeps the block
indices of the last live step, so Pallas moves nothing for it and its
body is skipped. With no live row at all the one block that is visited is
copied through.

The chunk form of a prefill stays in XLA (ops/kda.py `chunk_update`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_TILE = 32  # heads of one grid step: 2 MiB of state, 4 x 32 = 128 column lanes


def head_tile(heads: int) -> int:
    """The largest divisor of `heads` that is at most HEAD_TILE."""
    return max(t for t in range(1, HEAD_TILE + 1) if heads % t == 0)


def _update_kernel_body(meta, rows, s_ref, cols_ref, v_ref, o_ref, y_ref, *,
                        tile: int):
    u = pl.program_id(0)

    @pl.when(u < meta[0])
    def _live():
        cols = cols_ref[...]  # [dk, 4 * tile]

        def col(c, j):  # vector c of head j, along the sublanes
            return cols[:, c * tile + j:c * tile + j + 1]

        for j in range(tile):
            s = s_ref[j].astype(jnp.float32) * col(0, j)
            ks = jnp.sum(s * col(1, j), axis=0, keepdims=True)  # [1, dv]
            s = s + col(2, j) * (v_ref[pl.ds(j, 1), :] - ks)
            o_ref[j] = s.astype(o_ref.dtype)
            y_ref[pl.ds(j, 1), :] = jnp.sum(s * col(3, j), axis=0, keepdims=True)

    @pl.when(meta[0] == 0)
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


def kda_update_kernel(S, layer, unit_rows, n_live, cols, v, *, interpret=False):
    """S [Lk, slots, H, dk, dv] (slots >= R; a row's slot is its index);
    per decode row: cols [R, H / tile, dk, 4 * tile] f32 (alpha, k,
    beta k and q of a head tile's heads as columns, vector-major on the
    lanes: `columns` in ops/kda.py), v [R, H, dv] f32. unit_rows [R]:
    live rows first, then the last live row repeated; n_live how many are
    live. Returns (S', o [R, H, dv] f32; rows of dead units are not
    written)."""
    _, _, H, dk, dv = S.shape
    R, T = cols.shape[:2]
    tile = H // T
    meta = jnp.stack([jnp.asarray(n_live, jnp.int32), jnp.asarray(layer, jnp.int32)])

    def tile_of(u, t, meta):
        return jnp.where(u < meta[0], t, T - 1)

    def state(u, t, meta, rows):
        return (meta[1], rows[u], tile_of(u, t, meta), 0, 0)

    def column(u, t, meta, rows):
        return (rows[u], tile_of(u, t, meta), 0, 0)

    def head(u, t, meta, rows):
        return (rows[u], tile_of(u, t, meta), 0)

    s_spec = pl.BlockSpec((None, None, tile, dk, dv), state)
    c_spec = pl.BlockSpec((None, None, dk, 4 * tile), column)
    h_spec = pl.BlockSpec((None, tile, dv), head)
    return pl.pallas_call(
        functools.partial(_update_kernel_body, tile=tile),
        name="kda_update_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, T),
            in_specs=[s_spec, c_spec, h_spec],
            out_specs=[s_spec, h_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, H, dv), jnp.float32),
        ],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * R * H * dk * dv, transcendentals=0,
            bytes_accessed=2 * R * H * dk * dv * S.dtype.itemsize,
        ),
        interpret=interpret,
    )(meta, unit_rows.astype(jnp.int32), S, cols, v)
