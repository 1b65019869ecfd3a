"""Pallas TPU kernel of the Lightning state pool (ops/lightning.py has the
equations, the layout and the `jax.numpy` route this must equal).

A sequence's state is one slot of `S [Ls, slots, H, d, d]` float32: a head's
plane has the KEY dimension on the sublanes and the VALUE dimension on the
lanes, so the update `S <- lambda S + k^T v` is one multiply-add a tile with
`v` along the lanes and `k` down the sublanes, and the read-out `o = q S` is
a sum over SUBLANES (plain vector adds and one 8-row fold, as
ops/pallas/mamba.py's). The kernel aliases the pool
(`input_output_aliases`) and touches only the slots of the step's live rows,
so the stack that rides the layer scan's carry never moves.

`lightning_update_kernel` (decode: one token into each live row's slot; the
slot IS the row): grid (unit, head tile). Every head has a key and a query
of its own (Mamba-2's B and C are a group's), so they do not arrive
broadcast along the lanes (that would be the state's bytes twice more a
step): they arrive TRANSPOSED, `[R, tiles, d, tile]` (a head a lane, made
by XLA: 8 KiB a head beside its 64 KiB plane), and the body broadcasts a
head's column along the lanes. Per head the body is

    S <- lambda * S + k (x) v         lambda a scalar of the head
    o  = sum_k S[k, :] q[k]

on the VPU in float32. It reads and writes every state byte of a live row
once: HBM-bound.

Live rows come first in the unit order; a dead unit keeps the block indices
of the last live step, so Pallas moves nothing for it and its body is
skipped (ops/mamba.py `_units`). With no live row at all the one block that
is visited is copied through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas.mamba import head_tile


def _update_kernel_body(meta, rows, s_ref, lam_ref, q_ref, k_ref, v_ref,
                        o_ref, y_ref, *, tile: int):
    u = pl.program_id(0)

    @pl.when(u < meta[0])
    def _live():
        d = s_ref.shape[-1]
        for j in range(tile):
            lam = lam_ref[pl.ds(j, 1), :]  # [1, d]: the head's decay along the lanes
            v = v_ref[pl.ds(j, 1), :]  # [1, d]
            k = jnp.broadcast_to(k_ref[:, j:j + 1], (k_ref.shape[0], d))  # down the sublanes
            q = jnp.broadcast_to(q_ref[:, j:j + 1], (q_ref.shape[0], d))
            s = s_ref[j].astype(jnp.float32) * lam + k * v
            o_ref[j] = s.astype(o_ref.dtype)
            y_ref[pl.ds(j, 1), :] = jnp.sum(s * q, axis=0, keepdims=True)

    @pl.when(meta[0] == 0)
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


def lightning_update_kernel(S, layer, unit_rows, n_live, lam, q, k, v, *,
                            interpret=False):
    """S [Ls, slots, H, d, d] (slots >= R; a row's slot is its index); lam
    [H] f32 the heads' decays; per decode row q, k, v [R, H, d] f32.
    unit_rows [R]: live rows first, then the last live row repeated;
    n_live how many are live. Returns (S', o [R, H, d] f32; rows of dead
    units are not written)."""
    _, _, H, dk, dv = S.shape
    R = q.shape[0]
    # (interpret mode takes any divisor: the tests' shapes are small)
    tile = head_tile(H, dk * dv * 4) or (1 if interpret else 0)
    if not tile or H % tile:
        raise ValueError(f"lightning_update_kernel: no tile of whole 8-row blocks in {H} heads")
    T = H // tile
    meta = jnp.stack([jnp.asarray(n_live, jnp.int32), jnp.asarray(layer, jnp.int32)])
    f32 = jnp.float32
    # a head a lane: [R, H, d] -> [R, T, d, tile]
    columns = lambda x: jnp.swapaxes(x.astype(f32).reshape(R, T, tile, -1), 2, 3)
    lam_lanes = jnp.broadcast_to(lam.astype(f32)[:, None], (H, dv))

    def tile_of(u, t, meta):
        return jnp.where(u < meta[0], t, T - 1)

    def state(u, t, meta, rows):
        return (meta[1], rows[u], tile_of(u, t, meta), 0, 0)

    def head(u, t, meta, rows):
        return (rows[u], tile_of(u, t, meta), 0)

    def column(u, t, meta, rows):
        return (rows[u], tile_of(u, t, meta), 0, 0)

    s_spec = pl.BlockSpec((None, None, tile, dk, dv), state)
    h_spec = pl.BlockSpec((None, tile, dv), head)
    c_spec = pl.BlockSpec((None, None, dk, tile), column)
    l_spec = pl.BlockSpec((tile, dv), lambda u, t, meta, rows: (tile_of(u, t, meta), 0))
    return pl.pallas_call(
        functools.partial(_update_kernel_body, tile=tile),
        name="lightning_update_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, T),
            in_specs=[s_spec, l_spec, c_spec, c_spec, h_spec],
            out_specs=[s_spec, h_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, H, dv), f32),
        ],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=5 * R * H * dk * dv, transcendentals=0,
            bytes_accessed=2 * R * H * dk * dv * S.dtype.itemsize,
        ),
        interpret=interpret,
    )(meta, unit_rows.astype(jnp.int32), S, lam_lanes, columns(q), columns(k),
      v.astype(f32))
