"""Pallas TPU kernels of the power-retention state pool (ops/retention.py
has the layout, the equations and the `jax.numpy` route these must equal).

A sequence's state is one slot of `S [L, slots, Hkv, T, tR, d, d]`
(feature row r = t*tR + j, value lane v on sublanes, feature lane c on
lanes) and of the normaliser `z [L, slots, Hkv, T, tR, d]`. Both kernels
alias the pool (`input_output_aliases`) and touch only the slots of the
step's live rows, in the pool's resident tiling, so the stack that rides
the layer scan's carry never moves (PERF.md, PR 29 and PR 31).

Both kernels build the feature rows phi(q)_r, phi(k)_r in VMEM by
rotating q and k one lane a row (scratch that persists across the tile
axis; the rotated copy is exact), never in HBM: a chunk's would be 340 MB
a layer, and a decode step's, gathered by XLA, cost 4.8 ms of a 29 ms
step (PERF.md, PR 31).

`retention_update_kernel` (decode: one token into each live row's slot):
grid (unit, KV head, feature tile). Per tile `[tR, d, d]` the body is
`S <- decay * S + v (x) phi(k)_r` on the VPU (v arrives pre-broadcast
along lanes, phi(k)_r is a row broadcast along sublanes: exact float32),
the same for `z`, and `y += phi(q)_r . S_r^T` on the MXU with S and
phi(q) split into bf16 high and low parts (three of the four cross
terms: 2**-16 relative). It reads and writes every state byte of a live
row once: HBM-bound.

`retention_chunk_kernel` (prefill: one chunk of Lc tokens against the
carried state): same grid. Per feature row: the inter-chunk read
`y += (b_t phi(q_t))_r . S_r^T` with its normaliser `den += (..)_r * z_r`,
then `S_r <- b_L S_r + (w v)^T phi(k)_r` and `z_r <- b_L z_r + w phi(k)_r`
on the MXU in three bf16 passes each: MXU-bound. The intra-chunk part
(attention form, 5 % of the chunk's FLOPs) stays in XLA (ops/retention.py).

Live rows come first in the unit order; a dead unit (an inactive decode
row, a padding chunk) keeps the block indices of the last live step, so
Pallas moves nothing for it and its body is skipped. With no live row at
all the one block that is visited is copied through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a [M, K] . b [N, K] -> [M, N]
_NN = (((1,), (0,)), ((), ()))


def _split(x):
    """float32 -> (bf16 high, bf16 low) with high + low = x to 2**-16."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _dot3(a, b, dims):
    """float32 matmul in three bf16 MXU passes (a_lo . b_lo is dropped)."""
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    f = functools.partial(
        jax.lax.dot_general, dimension_numbers=dims,
        preferred_element_type=jnp.float32,
    )
    return f(a_hi, b_hi) + f(a_hi, b_lo) + f(a_lo, b_hi)


def _unit_maps(n_heads: int, n_tiles: int):
    """Index maps of a (unit, head, tile) grid over prefetched
    (meta [n_live, layer], unit_slots, unit_rows): a dead unit keeps the
    last live step's indices."""

    def coords(u, h, t, meta):
        live = u < meta[0]
        return jnp.where(live, h, n_heads - 1), jnp.where(live, t, n_tiles - 1)

    def state(u, h, t, meta, slots, rows):
        hh, tt = coords(u, h, t, meta)
        return (meta[1], slots[u], hh, tt, 0, 0, 0)

    def norm(u, h, t, meta, slots, rows):
        hh, tt = coords(u, h, t, meta)
        return (meta[1], slots[u], hh, tt, 0, 0)

    def row(u, h, t, meta, slots, rows):  # [rows, Hkv, ...]: whole per head
        hh, _ = coords(u, h, t, meta)
        return (rows[u], hh, 0, 0)

    return state, norm, row


# ---------------------------------------------------------------- decode


def retention_update_kernel(S, z, layer, unit_slots, unit_rows, n_live,
                            q16, k8, vb, decay, *, interpret=False):
    """S [L, NS, Hkv, T, tR, d, d], z [L, NS, Hkv, T, tR, d]; per decode
    row and KV head: q16 [R, Hkv, 16, d] f32 (the group's query heads in
    rows 0-7, zero padded, and again in rows 8-15), k8 [R, Hkv, 8, d] f32
    (k in every row), vb [R, Hkv, d, d] f32 (v broadcast along lanes),
    decay [R, Hkv, 1, d] f32. Returns (S', z', y [R, Hkv, 16, d] f32:
    rows 0-7 + rows 8-15 is the read-out of the group's query heads,
    den [R, Hkv, 16, d] f32: the lane sum of rows 0-7 is the normaliser;
    rows of dead units are not written)."""
    _, _, Hkv, T, t_r, d, _ = S.shape
    R = q16.shape[0]
    state, norm, row = _unit_maps(Hkv, T)
    meta = jnp.stack([jnp.asarray(n_live, jnp.int32), jnp.asarray(layer, jnp.int32)])
    s_spec = pl.BlockSpec((None, None, None, None, t_r, d, d), state)
    z_spec = pl.BlockSpec((None, None, None, None, t_r, d), norm)
    y_spec = pl.BlockSpec((None, None, 16, d), row)
    return pl.pallas_call(
        functools.partial(
            _update_kernel_body, t_r=t_r, n_rows=T * t_r, scale=float(d) ** -0.5
        ),
        name="retention_update_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, Hkv, T),
            in_specs=[
                s_spec, z_spec, y_spec,
                pl.BlockSpec((None, None, 8, d), row),
                pl.BlockSpec((None, None, d, d), row),
                pl.BlockSpec((None, None, 1, d), row),
            ],
            out_specs=[s_spec, z_spec, y_spec, y_spec],
            scratch_shapes=[
                pltpu.VMEM((16, d), jnp.float32), pltpu.VMEM((8, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((R, Hkv, 16, d), jnp.float32),
            jax.ShapeDtypeStruct((R, Hkv, 16, d), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=R * Hkv * T * t_r * d * d * (3 + 2 * 2 * 16),
            transcendentals=0,
            bytes_accessed=2 * R * Hkv * T * t_r * d * d * S.dtype.itemsize,
        ),
        interpret=interpret,
    )(meta, unit_slots.astype(jnp.int32), unit_rows.astype(jnp.int32),
      S, z, q16, k8, vb, decay)


def _row_weight(r, n_rows: int, scale: float):
    """w_r of ops/retention.py: 1/sqrt d on the first and last feature
    row, sqrt(2/d) between."""
    return jnp.where(
        jnp.logical_or(r == 0, r == n_rows - 1), scale, scale * 2.0 ** 0.5
    ).astype(jnp.float32)


def _update_kernel_body(meta, slots, rows, s_ref, z_ref, q_ref, k_ref, vb_ref,
                        dec_ref, o_ref, zo_ref, y_ref, den_ref, qr_ref, kr_ref,
                        *, t_r: int, n_rows: int, scale: float):
    u, t = pl.program_id(0), pl.program_id(2)
    d = q_ref.shape[-1]
    f = functools.partial(
        jax.lax.dot_general, dimension_numbers=_NT,
        preferred_element_type=jnp.float32,
    )

    @pl.when(u < meta[0])
    def _live():
        @pl.when(t == 0)
        def _():
            qr_ref[...] = q_ref[...]  # rotated copies start unrotated
            kr_ref[...] = k_ref[...]
            y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)
            den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)

        dec = dec_ref[...]  # [1, d], every lane the row's decay
        vb = vb_ref[...]  # [d(v), d(c)]: v along sublanes
        q, k = q_ref[...], k_ref[...]
        high = jax.lax.broadcasted_iota(jnp.int32, (16, d), 0) < 8
        acc = jnp.zeros((16, d), jnp.float32)  # [16, d(v)]
        den = jnp.zeros((16, d), jnp.float32)
        for j in range(t_r):
            w = _row_weight(t * t_r + j, n_rows, scale)
            pk = ((k * kr_ref[...]) * w)[:1]  # [1, d]: phi(k)_r
            pq = (q * qr_ref[...]) * w  # [16, d]: phi(q)_r, twice
            s = s_ref[j].astype(jnp.float32) * dec + vb * pk
            o_ref[j] = s.astype(o_ref.dtype)
            z_new = z_ref[pl.ds(j, 1), :].astype(jnp.float32) * dec + pk
            zo_ref[pl.ds(j, 1), :] = z_new.astype(zo_ref.dtype)
            den = den + pq * z_new
            s_hi, s_lo = _split(s)
            pq_hi = pq.astype(jnp.bfloat16)
            pq_lo = (pq - pq_hi.astype(jnp.float32)).astype(jnp.bfloat16)
            a = jnp.where(high, pq_hi, pq_lo)  # rows 0-7 high parts, 8-15 low
            # (hi + lo) . s_hi on all 16 rows; hi . s_lo on the high rows
            # (the low rows of the second product, lo . lo, are dropped)
            acc = acc + f(a, s_hi) + jnp.where(high, f(a, s_lo), 0.0)
            qr_ref[...] = pltpu.roll(qr_ref[...], d - 1, 1)  # x[(c + r + 1) % d]
            kr_ref[...] = pltpu.roll(kr_ref[...], d - 1, 1)
        y_ref[...] += acc
        den_ref[...] += den

    @pl.when(meta[0] == 0)
    def _none():
        o_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)


# ----------------------------------------------------------------- chunk


def _chunk_kernel_body(meta, slots, rows, s_ref, z_ref, q_ref, qb_ref, k_ref,
                       vtw_ref, wrow_ref, bl_ref,
                       o_ref, zo_ref, y_ref, den_ref, qr_ref, kr_ref,
                       *, t_r: int, n_rows: int, scale: float):
    u, t = pl.program_id(0), pl.program_id(2)
    d = q_ref.shape[-1]

    @pl.when(u < meta[0])
    def _live():
        @pl.when(t == 0)
        def _():
            qr_ref[...] = q_ref[...]  # rotated copies start unrotated
            kr_ref[...] = k_ref[...]
            y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)
            den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)

        bl = bl_ref[...]  # [1, d], every lane b_L
        qb, k = qb_ref[...], k_ref[...]
        vtw, wrow = vtw_ref[...], wrow_ref[...]
        for j in range(t_r):
            w = _row_weight(t * t_r + j, n_rows, scale)
            q_r = (qb * qr_ref[...]) * w  # [M, d]: b_t phi(q_t)_r
            k_r = (k * kr_ref[...]) * w  # [Lc, d]: phi(k_j)_r
            s = s_ref[j].astype(jnp.float32)  # [d(v), d(c)]
            z = z_ref[pl.ds(j, 1), :]  # [1, d]
            y_ref[...] += _dot3(q_r, s, _NT)
            den_ref[...] += q_r * z
            o_ref[j] = (s * bl + _dot3(vtw, k_r, _NN)).astype(o_ref.dtype)
            zo_ref[pl.ds(j, 1), :] = (
                z * bl + _dot3(wrow, k_r, _NN)[:1]
            ).astype(zo_ref.dtype)
            qr_ref[...] = pltpu.roll(qr_ref[...], d - 1, 1)  # x[(c + r + 1) % d]
            kr_ref[...] = pltpu.roll(kr_ref[...], d - 1, 1)

    @pl.when(meta[0] == 0)
    def _none():
        o_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)


def retention_chunk_kernel(S, z, layer, unit_slots, unit_rows, n_live,
                           q, qb, k, vtw, wrow, bl, *, interpret=False):
    """S [L, NS, Hkv, T, tR, d, d], z [L, NS, Hkv, T, tR, d]; per chunk
    row and KV head: q [P, Hkv, M, d] f32 (M = Lc * group, rows (t, g)),
    qb = b_t q, k [P, Hkv, Lc, d] f32, vtw [P, Hkv, d, Lc] f32 (w_j v_j
    transposed), wrow [P, Hkv, 8, Lc] f32 (row 0 = w_j), bl [P, Hkv, 1, d].
    Returns (S', z', y_inter [P, Hkv, M, d], den_inter [P, Hkv, M, d]:
    its lane sum is the normaliser's inter-chunk part)."""
    _, _, Hkv, T, t_r, d, _ = S.shape
    P, _, M, _ = q.shape
    Lc = k.shape[2]
    state, norm, row = _unit_maps(Hkv, T)
    meta = jnp.stack([jnp.asarray(n_live, jnp.int32), jnp.asarray(layer, jnp.int32)])
    s_spec = pl.BlockSpec((None, None, None, None, t_r, d, d), state)
    z_spec = pl.BlockSpec((None, None, None, None, t_r, d), norm)
    m_spec = pl.BlockSpec((None, None, M, d), row)
    flops = P * Hkv * T * t_r * 3 * 2 * d * d * (M + Lc + 8)
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel_body, t_r=t_r, n_rows=T * t_r, scale=float(d) ** -0.5
        ),
        name="retention_chunk_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(P, Hkv, T),
            in_specs=[
                s_spec, z_spec, m_spec, m_spec,
                pl.BlockSpec((None, None, Lc, d), row),
                pl.BlockSpec((None, None, d, Lc), row),
                pl.BlockSpec((None, None, 8, Lc), row),
                pl.BlockSpec((None, None, 1, d), row),
            ],
            out_specs=[s_spec, z_spec, m_spec, m_spec],
            scratch_shapes=[
                pltpu.VMEM((M, d), jnp.float32), pltpu.VMEM((Lc, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((P, Hkv, M, d), jnp.float32),
            jax.ShapeDtypeStruct((P, Hkv, M, d), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=2 * P * Hkv * T * t_r * d * d * S.dtype.itemsize,
        ),
        interpret=interpret,
    )(meta, unit_slots.astype(jnp.int32), unit_rows.astype(jnp.int32),
      S, z, q, qb, k, vtw, wrow, bl)
    return out
