"""Pallas TPU kernels of the KDA state pool: the decode update, the chunk
form of a prefill chunk, and (alone, for the `jax.numpy` chunk form) the
diagonal of its gram (ops/kda.py has the equations, the layout and the
`jax.numpy` routes these must equal).

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

`kda_chunk_kernel` (a prefill chunk a row: ops/kda.py's chunk form, whole,
from the convolution's output): grid (unit, head tile, token tile of up to
512 tokens), a row's token tiles in order. The operands are the rows AS THE
PROJECTIONS LEAVE THEM, `[tokens, lanes]` in (8, 128) tiles of (token,
lane): the convolution's `[P L, 3 H d]` (q | k | v) and the log decay
`[P L, H d]`; a head is 128 lanes, so its tokens are one `[512, 128]` block
(a ref a head and operand: Mosaic's strided load wants a 128-lane ref), and
nothing is copied heads-first on the way in or out. A grid step takes 4
heads (128 lanes of (head, sub-block) at 32 sub-blocks a head) and

  1. BY POSITION (`_lay_by_position`, the gram kernel's layout: a lane a
     (head, sub-block), position i of every sub-block one `[d, 128]` tile):
     lays out g, k and q; l2-normalises q and k there (a head's lanes are
     the sublanes: a sum over sublanes a token); sums g from its chunk's
     first token (15 tile adds, then the chunk's earlier sub-blocks' totals
     by three lane rolls), a token past the row's length counting 0; forms
     the diagonal sub-blocks of both grams (`_gram_pairs`, the body
     `kda_gram_kernel` has); inverts `I + diag(beta) M[k]` of every diagonal
     sub-block by forward substitution IN THAT LAYOUT (row i of 128
     inverses is 16 multiply-adds of two vregs);
  2. goes back to a row a token: the inverses and `M[q]` as `[token, 16]`
     rows (two 128 x 128 transposes, lane rolls, strided stores), G and the
     normalised k and q as `[head, 512, d]` (a transpose a position);
  3. chunk by chunk, two heads a loop trip, on the MXU in float32
     (HIGHEST) around exponentials that are never positive. What is a
     head's own tokens-by-tokens matrix (the gram, the inverse) is a
     diagonal block of ONE `[128, 128]` matrix of the two heads, so the
     products are whole tiles (as `[64, 64]` operands the inverse's joins
     alone cost 345 us a layer and chunk, as whole tiles all eight square
     products 447: docs/KERNELS.md): the gram below the diagonal
     sub-blocks (a row block against the earlier tokens through its entry
     point), the inverse's joins level by level, `K+ S` and `(q exp G) S`
     in one product against each head's state, `U~ = T (V - K+ S)`, the
     output `(q exp G) S + M[q] U~` written into the head's lanes of the
     token-major output block, and the state `Diag(exp G_C) S + (k exp(G_C
     - G))^T U~`.

The head's `[d, d]` state lives in the step's OUTPUT block of the pool (the
row's slot, the tile's heads), which Pallas keeps in VMEM while the slot
does not change: read from the slot at the row's first token tile (zeros
if the row starts at 0), written back once after its last. Dead units
repeat the last live step's blocks and run nothing; with no live row the
one block visited is copied through. U, W, the grams and the normalised q
and k never reach HBM.

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


def _lay_by_position(ins, outs, *, block: int, nb: int):
    """Each array of `ins` (one ref `[T, d]` a head) into its `[block, d,
    lanes]` scratch of `outs`: position i of every sub-block (rows i,
    i + block, ... of a head's tokens: one strided load) side by side on
    the lanes, the key lane on the sublanes."""
    heads, d = len(ins[0]), outs[0].shape[1]

    def by_position(i, carry):
        for head_refs, out in zip(ins, outs):
            rows = [ref[pl.ds(i, nb, stride=block), :] for ref in head_refs]
            if heads * nb < GRAM_LANES:
                rows.append(jnp.zeros((GRAM_LANES - heads * nb, d), jnp.float32))
            out[i] = jnp.concatenate(rows, axis=0).T
        return carry

    jax.lax.fori_loop(0, block, by_position, 0)


def _gram_pairs(g_s, k_s, x_s, m_s, *, block: int):
    """The diagonal sub-blocks' decayed grams of the row sets `x_s` against
    `k_s`, all by position (`_lay_by_position`): row i * block + j of
    `m_s[s]` is M_ij of every lane, 0 above the diagonal."""
    d, sets = g_s.shape[1], len(x_s)
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


def _gram_kernel_body(*refs, block: int, sets: int, heads: int, nb: int):
    arrays = 2 + sets  # G, k and the row sets: `heads` refs [T, d] each, a head a ref
    ins = [refs[a * heads:(a + 1) * heads] for a in range(arrays)]
    o_ref = refs[arrays * heads]  # [sets, lanes, block * block]
    g_s, k_s, *x_s = refs[arrays * heads + 1:arrays * (heads + 1) + 1]  # [block, d, lanes] each
    m_s = refs[-1]  # [sets, block * block, lanes]
    _lay_by_position(ins, (g_s, k_s, *x_s), block=block, nb=nb)
    _gram_pairs(g_s, k_s, x_s, m_s, block=block)
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


_HI = jax.lax.Precision.HIGHEST
CHUNK_TILE = 512  # tokens of one grid step of the chunk kernel, at most
HEADS_TOGETHER = 2  # heads of one trip of its loop: 2 x 64 tokens make its products whole tiles


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HI, preferred_element_type=jnp.float32
    )


def _chunk_kernel_body(meta, rows, slots, start, length, *refs, heads: int, together: int,
                       nb: int, block: int, chunk: int, eps: float):
    f32 = jnp.float32
    q_in, k_in, v_in, g_in = (refs[a * heads:(a + 1) * heads] for a in range(4))  # [TL, d] a head
    bpos_ref, bt_ref, s_ref, o_ref, so_ref = refs[4 * heads:4 * heads + 5]
    g_s, k_s, q_s, m_s, inv_s, a_s, tokt_s, tokm_s, G_t, k_t, q_t, v_t = refs[4 * heads + 5:]
    u, tile = pl.program_id(0), pl.program_id(2)
    TL, d = k_in[0].shape
    L, nbc, n_c = GRAM_LANES, chunk // block, TL // chunk

    def sub_blocks(shape):  # (sub-block of the row, sub-block of the column)
        return tuple(jax.lax.broadcasted_iota(jnp.int32, shape, a) // block for a in (0, 1))

    def by_position():
        """Stage 1: g, k and q `[block, d, L]` (`_lay_by_position`), then
        in that layout, where a token is a lane and a head's lane a
        sublane: q and k l2-normalised (q scaled by d ** -0.5), and g
        summed from its chunk's first token (G)."""
        _lay_by_position((g_in, k_in, q_in), (g_s, k_s, q_s), block=block, nb=nb)

        def normalise(i, carry):
            for ref, scale in ((k_s, 1.0), (q_s, d ** -0.5)):
                x = ref[i]
                ref[i] = x * (scale * jax.lax.rsqrt(jnp.sum(x * x, axis=0, keepdims=True) + eps))
            return carry

        jax.lax.fori_loop(0, block, normalise, 0)

        # a token past the row's length is inert: its g is 0 (beta comes masked)
        lane = jax.lax.broadcasted_iota(jnp.int32, (d, L), 1)
        left = length[rows[u]] - tile * TL - lane % nb * block  # valid positions of a lane's sub-block

        def within(i, run):  # ... over the tokens of a sub-block
            run = run + jnp.where(i < left, g_s[i], 0.0)
            g_s[i] = run
            return run

        jax.lax.fori_loop(0, block, within, jnp.zeros((d, L), f32))
        if nbc > 1:  # ... and over the sub-blocks before it in its chunk: lanes to the left
            total = g_s[block - 1]
            place = lane % nb % nbc
            before = jnp.zeros_like(total)
            for n in range(1, nbc):
                before = before + jnp.where(place >= n, pltpu.roll(total, n, 1), 0.0)

            def across(i, carry):
                g_s[i] = g_s[i] + before
                return carry

            jax.lax.fori_loop(0, block, across, 0)

    def inverse_rows():
        """(I + A)^-1 of every diagonal sub-block by forward substitution,
        by position: row i * block + k of `inv_s` is entry (i, k) of every
        lane's sub-block. A_ij = beta_i M[k]_ij below the diagonal."""
        kk = jax.lax.broadcasted_iota(jnp.int32, (block, L), 0)

        def unit(i, carry):
            inv_s[pl.ds(pl.multiple_of(i * block, block), block), :] = (kk == i).astype(f32)
            return carry

        jax.lax.fori_loop(0, block, unit, 0)

        def row(i, carry):  # row i from the rows above it (a_ij = 0 from j = i on)
            at = pl.ds(pl.multiple_of(i * block, block), block)
            a_s[...] = jnp.where(kk < i, m_s[0, at, :] * bpos_ref[pl.ds(i, 1), :], 0.0)

            def term(j, acc):
                above = inv_s[pl.ds(pl.multiple_of(j * block, block), block), :]
                return acc + a_s[pl.ds(j, 1), :] * above

            acc = jax.lax.fori_loop(0, block, term, jnp.zeros((block, L), f32))
            inv_s[at, :] = (kk == i).astype(f32) - acc
            return carry

        jax.lax.fori_loop(1, block, row, 0)

    def token_rows(src, out):
        """By position `[block * block, L]` -> a row a token: row
        (lane * block + i) of `out`, which is token (sub-block, i) of the
        lane's head, holds entries (i, 0..block) of its sub-block on its
        first `block` lanes (the other lanes hold other rows' entries)."""
        per = L // block  # positions of one transposed tile
        for half in range(block * block // L):
            tile_t = src[half * L:(half + 1) * L, :].T  # [lane, (i, k)]
            for ii in range(per):
                piece = tile_t if ii == 0 else pltpu.roll(tile_t, L - ii * block, 1)
                out[pl.ds(half * per + ii, L, stride=block), :] = piece

    def token_major():
        """G, k and q back to a row a token, a head its own `[TL, d]`."""
        def back(i, carry):
            for src, out in ((g_s, G_t), (k_s, k_t), (q_s, q_t)):
                rows_t = src[i].T  # [lane, d]
                for h in range(heads):
                    out[h, pl.ds(i, nb, stride=block), :] = rows_t[h * nb:(h + 1) * nb]
            return carry

        jax.lax.fori_loop(0, block, back, 0)
        for h in range(heads):
            v_t[h] = v_in[h][...]

    W = together * chunk  # rows of the heads of one trip, a head after the other

    def block_diagonal(rows_, masks):
        """[W, W]: the sub-blocks of `rows_` (token rows [W, L]) on the
        diagonal, 0 elsewhere."""
        rb, cb = masks
        out = jnp.zeros((W, L), f32)
        for b in range(W // block):
            piece = pltpu.roll(rows_, b * block, 1) if b else rows_
            out = jnp.where((rb == b) & (cb == b), piece, out)
        return out[:, :W]

    def per_heads(first, c, masks):
        """Stage 3, chunk c of `together` heads from `first` on against
        their states: what is a head's own matrix of tokens by tokens (the
        gram, the inverse) is a diagonal block of ONE [W, W] matrix, so
        that the products are whole MXU tiles."""
        wide, own_head, pairs = masks
        hs_ = [first + j for j in range(together)]
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        G, k, q, v = ([ref[h, at, :] for h in hs_] for ref in (G_t, k_t, q_t, v_t))
        beta = bt_ref[pl.ds(first // together * n_c + c, 1), :]  # [1, W]: a column's factor
        # the gram below the diagonal sub-blocks: a row block against every
        # earlier token through its entry point (no exponent positive)
        mk, mq = [[jnp.zeros((block, W), f32)] for _ in hs_], [[jnp.zeros((block, W), f32)] for _ in hs_]
        for a in range(1, nbc):
            own, before = slice(a * block, (a + 1) * block), slice(0, a * block)
            xk, xq, km = [], [], []
            for j in range(together):
                ref = G[j][a * block - 1:a * block, :]
                e = jnp.exp(jnp.minimum(G[j][own] - ref, 0.0))
                xk.append(k[j][own] * e)
                xq.append(q[j][own] * e)
                km.append(k[j][before] * jnp.exp(jnp.minimum(ref - G[j][before], 0.0)))
                km.append(jnp.zeros((chunk - a * block, d), f32))
            # [2 W / nbc, W]: a head's rows against its own tokens only, 0 from its own column on
            p = _dot(jnp.concatenate(xk + xq, axis=0), jnp.concatenate(km, axis=0), ((1,), (1,)))
            p = jnp.where(own_head, p, 0.0)
            for j in range(together):
                mk[j].append(p[j * block:(j + 1) * block])
                mq[j].append(p[(together + j) * block:(together + j + 1) * block])
        Mk = jnp.concatenate([piece for m in mk for piece in m], axis=0)
        # T = (I + diag(beta) M[k])^-1 diag(beta): the diagonal sub-blocks' inverses, neighbours
        # joined level by level (beta scales a column of the left factor: X diag(beta) M X)
        tok_at = [pl.ds(pl.multiple_of(h * TL + c * chunk, chunk), chunk) for h in hs_]
        X = block_diagonal(jnp.concatenate([tokt_s[t, :] for t in tok_at], axis=0), wide)
        for pair in pairs:
            X = X - _dot(X * beta, _dot(jnp.where(pair, Mk, 0.0), X))
        Mq = block_diagonal(jnp.concatenate([tokm_s[t, :] for t in tok_at], axis=0), wide) \
            + jnp.concatenate([piece for m in mq for piece in m], axis=0)
        S = [so_ref[h].astype(f32) for h in hs_]
        eG = [jnp.exp(x) for x in G]
        KQ = [_dot(jnp.concatenate([k[j] * eG[j], q[j] * eG[j]], axis=0), S[j])  # K+ S | (q exp G) S
              for j in range(together)]
        rest = jnp.concatenate([v[j] - KQ[j][:chunk] for j in range(together)], axis=0)
        Ut = _dot(X * beta, rest)  # T (V - K+ S), the heads' one under the other
        o = jnp.concatenate([x[chunk:] for x in KQ], axis=0) + _dot(Mq, Ut)
        for j, h in enumerate(hs_):
            o_ref[at, pl.ds(pl.multiple_of(h * d, d), d)] = o[j * chunk:(j + 1) * chunk]
            GC = G[j][chunk - 1:chunk, :]
            decay = jnp.broadcast_to(jnp.exp(GC), (8, d)).T[:, :1]  # along the key lane: a column
            moved = _dot(k[j] * jnp.exp(GC - G[j]), Ut[j * chunk:(j + 1) * chunk], ((0,), (0,)))
            so_ref[h] = (decay * S[j] + moved).astype(so_ref.dtype)

    @pl.when(u < meta[0])
    def _live():
        @pl.when(tile == 0)
        def _first():  # the state rides the output block; a row that starts at 0 ignores its slot
            so_ref[...] = jnp.where(start[rows[u]] > 0, s_ref[...], jnp.zeros_like(s_ref))

        by_position()
        _gram_pairs(g_s, k_s, (k_s, q_s), m_s, block=block)
        inverse_rows()
        token_rows(inv_s, tokt_s)
        token_rows(m_s.at[1], tokm_s)
        token_major()
        # what no trip changes, once: the sub-block of a row and of a lane, whose rows are
        # whose tokens, and the pairs of sub-blocks that each level of the inverse joins
        rb, cb = sub_blocks((W, W))
        pairs, size = [], 1
        while size < nbc:
            pairs.append((rb // size % 2 == 1) & (cb // size == rb // size - 1))
            size *= 2
        rows_of, tokens_of = sub_blocks((2 * together * block, W))
        own_head = rows_of % together == tokens_of // nbc
        masks = (sub_blocks((W, L)), own_head, pairs)
        trips = heads // together

        def step(it, carry):  # a chunk after the one before it, every head
            per_heads(it % trips * together, it // trips, masks)
            return carry

        jax.lax.fori_loop(0, n_c * trips, step, 0)

    @pl.when(meta[0] == 0)
    def _none():
        so_ref[...] = s_ref[...]


def chunk_tile(length: int, chunk: int) -> int:
    """Tokens of one grid step: the largest whole number of chunks that
    divides `length` and is at most CHUNK_TILE."""
    return max(t for t in range(chunk, max(min(length, CHUNK_TILE), chunk) + 1, chunk)
               if length % t == 0)


def kda_chunk_kernel(S, layer, unit_rows, n_live, slots, start, length, qkv, g, beta, *,
                     chunk: int, block: int, eps: float, interpret=False):
    """The chunk form of a prefill chunk a row, against the row's slot of
    S [Lk, slots, H, dk, dv], from the convolution's output: qkv
    [P, L, 3 H d] f32 (q | k | v side by side, NOT normalised: the kernel
    l2-normalises q and k over a head's lanes under the root of (sum of
    squares + eps) and scales q by d ** -0.5), g [P, L, H, d] and beta
    [P, L, H] f32, all token-major as the projections leave them; L a
    whole number of `chunk`s (of `block`-token sub-blocks, a power of two
    of them). A token past its row's `length` is inert: the kernel reads
    its g as 0, and its beta comes as 0. unit_rows [P]: live rows first,
    then the last live row repeated; n_live how many; slots, start,
    length [P] int32 (start 0: the slot's content is ignored). Returns (o [P, L, H, d] f32; a dead row's is not
    written, S')."""
    P, Lp, H, d = g.shape
    TL = chunk_tile(Lp, chunk)
    nL, nb, n_c = Lp // TL, TL // block, TL // chunk
    heads = head_tile(H, GRAM_LANES // nb)  # of one grid step: 128 lanes of (head, sub-block)
    T = H // heads
    i32 = jnp.int32
    meta = jnp.stack([jnp.asarray(n_live, i32), jnp.asarray(layer, i32)])
    # beta twice, both small: by position [.., i, (head, sub-block)], and a row a (trip of
    # heads, chunk)
    bpos = beta.reshape(P, nL, nb, block, T, heads).transpose(0, 1, 4, 3, 5, 2)
    bpos = jnp.pad(bpos.reshape(P, nL, T, block, heads * nb),
                   ((0, 0),) * 4 + ((0, GRAM_LANES - heads * nb),))
    together = HEADS_TOGETHER if heads % HEADS_TOGETHER == 0 else 1
    bt = beta.reshape(P, nL, n_c, chunk, T, heads // together, together)
    bt = bt.transpose(0, 1, 4, 5, 2, 6, 3).reshape(P, nL, T, heads // together * n_c, together * chunk)

    def where(u, t, l, meta):  # a dead unit keeps the last live step's blocks
        live = u < meta[0]
        return jnp.where(live, t, T - 1), jnp.where(live, l, nL - 1)

    def head_rows(part, h):  # a head's lanes of a row of tokens: of q, k, v (qkv) or of g
        def index(u, t, l, meta, rows, *_):
            t, l = where(u, t, l, meta)
            return (rows[u] * nL + l, part * H + t * heads + h)
        return pl.BlockSpec((TL, d), index)

    def tile_rows(u, t, l, meta, rows, *_):
        t, l = where(u, t, l, meta)
        return (rows[u] * nL + l, t)

    def small(u, t, l, meta, rows, *_):
        t, l = where(u, t, l, meta)
        return (rows[u], l, t, 0, 0)

    def state(u, t, l, meta, rows, slots, *_):
        return (meta[1], slots[rows[u]], where(u, t, l, meta)[0], 0, 0)

    s_spec = pl.BlockSpec((None, None, heads, d, d), state)
    by_head = pltpu.VMEM((heads, TL, d), jnp.float32)
    by_position = pltpu.VMEM((block, d, GRAM_LANES), jnp.float32)
    token_rows = pltpu.VMEM((GRAM_LANES * block, GRAM_LANES), jnp.float32)
    qkv2, g2 = qkv.reshape(P * Lp, 3 * H * d), g.reshape(P * Lp, H * d)
    o, S = pl.pallas_call(
        functools.partial(_chunk_kernel_body, heads=heads, together=together, nb=nb, block=block,
                          chunk=chunk, eps=eps),
        name="kda_chunk_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(P, T, nL),
            in_specs=[head_rows(part, h) for part in (0, 1, 2, 0) for h in range(heads)] + [
                pl.BlockSpec((None, None, None, block, GRAM_LANES), small),
                pl.BlockSpec((None, None, None, heads // together * n_c, together * chunk), small),
                s_spec,
            ],
            out_specs=[pl.BlockSpec((TL, heads * d), tile_rows), s_spec],
            scratch_shapes=[by_position] * 3 + [
                pltpu.VMEM((2, block * block, GRAM_LANES), jnp.float32),
                pltpu.VMEM((block * block, GRAM_LANES), jnp.float32),
                pltpu.VMEM((block, GRAM_LANES), jnp.float32),
                token_rows, token_rows,
            ] + [by_head] * 4,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P * Lp, H * d), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
        ],
        input_output_aliases={5 + 4 * heads + 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=P * Lp * H * (2 * d * (block + 1) + 2 * (3 * d * d + 4 * chunk * d + 2 * chunk * chunk)),
            transcendentals=P * Lp * H * d * (block + 1) // 2,
            bytes_accessed=4 * (5 * P * Lp * H * d + 2 * P * H * d * d),
        ),
        interpret=interpret,
    )(meta, unit_rows.astype(i32), slots.astype(i32), start.astype(i32), length.astype(i32),
      *([qkv2] * (3 * heads)), *([g2] * heads), bpos, bt, S)
    return o.reshape(P, Lp, H, d), S
