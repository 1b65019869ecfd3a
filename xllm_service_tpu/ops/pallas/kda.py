"""Pallas TPU kernels of the KDA state pool and of its chunk form's gram
(ops/kda.py has the equations, the layout and the `jax.numpy` routes these
must equal).

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

The chunk form of a prefill stays in XLA (ops/kda.py `chunk_update`) but
for the diagonal sub-blocks of its decayed gram:

`kda_gram_kernel` (a prefill chunk: `M[x]_ij = sum_c x_i[c] k_j[c]
exp(G_i[c] - G_j[c])` for the pairs i >= j of one sub-block of 16 tokens):
the 16 POSITIONS of a sub-block are the unit of the body, and 128 (head,
sub-block) pairs ride the lanes. Grid (token tile, head tile): for each of
`G`, `k` and the row sets x, and each head of the tile, a `[T, d]` block of
the tokens as the projections leave them (`[N, H * d]`: a head is 128
lanes of a row). The body first lays each operand out BY POSITION: rows i,
i + 16, i + 32, ... of a head (one strided load) are position i of its
sub-blocks; the heads' rows stacked and transposed once give `[d, 128]`:
the KEY lane on the sublanes, as above, and a lane a (head, sub-block).
Token i against token j of the same sub-block is then tile i against tile
j, lane by lane: no roll, no gather, and only the pairs i >= j are formed
(136 of 256: the mask comes before the exponential by construction, and
what is above the diagonal is written as 0). Per pair and slab of 8 key
lanes, in float32 on the VPU:

    pair    = k_j * exp(min(G_i - G_j, 0))       once for every row set
    M_ij   += sum_c x_i * pair                   a sum over sublanes

so no exponent is positive. The pair terms `[16, 16, d]` of a sub-block,
which XLA writes to HBM and reads again, live in vregs: for each i a loop
over the slabs carries the sums of its i + 1 pairs, all j at once. The 256
entries of a (head, sub-block) come out as one row of `[128, 256]`
(transposed in VMEM), so ops/kda.py only reshapes. About 8 VPU operations
and 1 exponential a vreg and pair: VPU-bound, far above its bytes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_TILE = 32  # heads of one grid step: 2 MiB of state, 4 x 32 = 128 column lanes


def head_tile(heads: int, most: int = HEAD_TILE) -> int:
    """The largest divisor of `heads` that is at most `most`."""
    return max(t for t in range(1, most + 1) if heads % t == 0)


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


GRAM_LANES = 128  # (head, sub-block) pairs of one grid step: the lanes of its tiles


def _gram_kernel_body(*refs, block: int, sets: int, heads: int, nb: int):
    arrays = 2 + sets  # G, k and the row sets: `heads` refs [T, d] each, a head a ref
    ins = [refs[a * heads:(a + 1) * heads] for a in range(arrays)]
    o_ref = refs[arrays * heads]  # [sets, lanes, block * block]
    g_s, k_s, *x_s = refs[arrays * heads + 1:arrays * (heads + 1) + 1]  # [block, d, lanes] each
    m_s = refs[-1]  # [sets, block * block, lanes]: row i * block + j is M_ij of every lane
    d = g_s.shape[1]

    def by_position(i, carry):
        # position i of every sub-block (rows i, i + block, ... of a head's
        # tokens) side by side on the lanes, the key lane on the sublanes
        for head_refs, out in zip(ins, (g_s, k_s, *x_s)):
            rows = [ref[pl.ds(i, nb, stride=block), :] for ref in head_refs]
            if heads * nb < GRAM_LANES:
                rows.append(jnp.zeros((GRAM_LANES - heads * nb, d), jnp.float32))
            out[i] = jnp.concatenate(rows, axis=0).T
        return carry

    jax.lax.fori_loop(0, block, by_position, 0)
    m_s[...] = jnp.zeros(m_s.shape, jnp.float32)  # above the diagonal: 0
    rows = 8 if d % 8 == 0 else d  # key lanes of one slab: a vreg's sublanes
    for i in range(block):  # token i against every j <= i of its sub-block, all j at once

        def slab(c, sums, i=i):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            # i >= j: the mask, before the exponential
            decay = jnp.exp(jnp.minimum(g_s[i, at, :] - g_s[:i + 1, at, :], 0.0))
            pair = k_s[:i + 1, at, :] * decay  # [i + 1, rows, lanes], once for every row set
            return tuple(a + x[i, at, :] * pair for a, x in zip(sums, x_s))

        zero = jnp.zeros((i + 1, rows, GRAM_LANES), jnp.float32)
        sums = jax.lax.fori_loop(0, d // rows, slab, (zero,) * sets)
        for s in range(sets):
            m_s[s, i * block:i * block + i + 1, :] = jnp.sum(sums[s], axis=1)
    for s in range(sets):  # a (head, sub-block)'s block is one row of the output
        for c in range(0, block * block, GRAM_LANES):
            o_ref[s, :, c:c + GRAM_LANES] = m_s[s, c:c + GRAM_LANES, :].T


def kda_gram_kernel(G, k, xs, *, block: int, interpret=False):
    """The diagonal sub-blocks of the decayed gram of each row set of
    `xs` against k. G, k and every x [N, H, d] f32: tokens in sub-blocks
    of `block` (N a multiple of it; block * block a multiple of 128), G
    falling inside a sub-block. Returns [len(xs), H, N / block, block,
    block] f32: for tokens i >= j of sub-block b
    out[s, h, b, i, j] = sum_c x_s[i, h, c] k[j, h, c] exp(G[i, h, c] - G[j, h, c]),
    0 above the diagonal."""
    N, H, d = k.shape
    S = len(xs)
    pad = -N % GRAM_LANES
    flat = lambda t: jnp.pad(t.reshape(N, H * d), ((0, pad), (0, 0)))
    Np = N + pad
    T = GRAM_LANES * math.gcd(Np // GRAM_LANES, block)  # tokens of a grid step
    nb = T // block  # ... their sub-blocks a head: at most 128
    heads = head_tile(H, GRAM_LANES // nb)
    tiles = (Np // T, H // heads)
    head_rows = [pl.BlockSpec((T, d), lambda t, g, h=h: (t, g * heads + h)) for h in range(heads)]
    out = pl.pallas_call(
        functools.partial(_gram_kernel_body, block=block, sets=S, heads=heads, nb=nb),
        name="kda_gram_kernel",  # op name in the device trace
        grid=tiles,
        in_specs=head_rows * (2 + S),
        out_specs=pl.BlockSpec(
            (None, None, S, GRAM_LANES, block * block), lambda t, g: (t, g, 0, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(tiles + (S, GRAM_LANES, block * block), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, d, GRAM_LANES), jnp.float32)] * (2 + S)
        + [pltpu.VMEM((S, block * block, GRAM_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=48 * 2 ** 20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=(2 + S) * Np * H * d * (block + 1), transcendentals=Np * H * d * (block + 1) // 2,
            bytes_accessed=4 * Np * H * ((2 + S) * d + S * block),
        ),
        interpret=interpret,
    )(*(a for t in (G, k, *xs) for a in [flat(t)] * heads))
    # [token tile, head tile, S, (head, sub-block), i * block + j] -> [S, H, sub-block, i, j]
    out = out[:, :, :, :heads * nb].reshape(*tiles, S, heads, nb, block, block)
    out = out.transpose(2, 1, 3, 0, 4, 5, 6).reshape(S, H, Np // block, block, block)
    return out[:, :, :N // block]
