"""Pallas TPU flash kernel for MLA (DeepSeek) chunked paged prefill.

Prefill sibling of ops/pallas/mla_attention.py (decode) — same latent
trick: the compressed cache row (kv_rank + rope_dim floats) is shared by
ALL heads, so one [TQ*Hq, C] x [C, CH*BS] matmul scores a whole query
tile against a chunk of latent blocks, and pv accumulates in LATENT
space ([.., kv_rank]); W_UV is applied by the caller once per output
token (absorbed form). The gather/blockwise fallback's weakness is the
same as decode's: XLA materializes the gathered context per layer.

Structure mirrors ops/pallas/flash_prefill.py: grid (P, NT) — no head
axis, heads ride as sublane rows — double-buffered block DMA bounded by
each tile's OWN context length, online softmax, causal + ragged masking
by absolute position.

Layouts: q_lat [P, Lpad, Hq, C] (chunk-relative), the STACKED latent pool
[L, N, 1, BS, C] plus the layer index in scalar memory (a 4-D per-layer
cache is the L = 1 case), block_table [P, CB] int32, start_pos/true_len
[P] int32. Returns [P, Lpad, Hq, kv_rank]. Oracle:
ops/attention.mla_prefill_blockwise.

A query tile is TQ positions x Hq heads of rows, held to about
`rows_cap` rows: at 128 heads that is 8 positions (1024 rows of 640
lanes, 1.3 MB; scores and the accumulator [1024, 512] float32), so a
512-token chunk walks its context 64 times, 1,280 bytes a cached
position each: 0.8 ms a layer at 7680 cached tokens beside 6 ms of MXU
work (absorbed form: 2 x (640 + 512) FLOP a head, query and position).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic

NEG_INF = -1e30


def _mla_prefill_kernel(
    # scalar prefetch
    block_table_ref,  # [P, MBp] SMEM
    start_pos_ref,    # [P] SMEM
    true_len_ref,     # [P] SMEM
    layer_ref,        # [1] SMEM — which layer of the stack to read
    # inputs
    q_ref,            # [1, 1, Rp, C] VMEM (one tile's TQ*Hq rows)
    c_hbm,            # [L, N, 1, BS, C] HBM — bf16 or int8
    *rest,            # quantized: cs_hbm [L, N, 1, G, BS] f32, then
    # output
    #   o_ref         # [1, 1, Rp, KVR] VMEM
    # scratch
    #   c_buf         # [2, CH*BS, C] VMEM (cache dtype)
    #   sems          # [2, CH]
    #   (quantized)   s_buf [2, CH, G, BS] f32 + ssems [2, CH]
    block_size: int,
    chunk: int,
    tile_q: int,
    heads: int,
    scale: float,
    kv_rank: int,
    quantized: bool = False,
    scale_groups: int = 1,
):
    if quantized:
        cs_hbm, o_ref, c_buf, sems, s_buf, ssems = rest
    else:
        o_ref, c_buf, sems = rest
        cs_hbm = s_buf = ssems = None
    p = pl.program_id(0)
    t = pl.program_id(1)
    lyr = layer_ref[0]
    start = start_pos_ref[p]
    n_valid = true_len_ref[p]
    span = chunk * block_size

    tile_lo = t * tile_q
    ctx = start + jnp.minimum(tile_lo + tile_q, n_valid)
    nc = jnp.where(tile_lo < n_valid, pl.cdiv(ctx, span), 0)

    def dmas(slot, c_idx, blk):
        out = [
            mosaic.async_copy(
                    mosaic.checked_at(c_hbm, lyr, blk, 0),
                    mosaic.checked_at(c_buf, slot, pl.ds(c_idx * block_size, block_size)),
                    sems.at[slot, c_idx],
                )
        ]
        if quantized:
            # Full-extent [G, BS] scale tile (blk on the untiled dim);
            # see mla_attention._mla_common for why.
            out.append(
                mosaic.async_copy(
                    mosaic.checked_at(cs_hbm, lyr, blk, 0),
                    mosaic.checked_at(s_buf, slot, c_idx),
                    ssems.at[slot, c_idx],
                )
            )
        return out

    def start_chunk(slot, c):
        for c_idx in range(chunk):
            for d in dmas(slot, c_idx, block_table_ref[p, c * chunk + c_idx]):
                d.start()

    def wait_chunk(slot, c):
        for c_idx in range(chunk):
            for d in dmas(slot, c_idx, block_table_ref[p, c * chunk + c_idx]):
                d.wait()

    @pl.when(nc > 0)
    def _first():
        start_chunk(0, 0)

    q = q_ref[0, 0]  # [Rp, C]
    Rp = q.shape[0]
    row_off = jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // heads
    row_pos = start + tile_lo + row_off
    row_valid = tile_lo + row_off < n_valid

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        tile = c_buf[slot]  # [CH*BS, C]
        if quantized:
            from xllm_service_tpu.ops.pallas.mla_attention import (
                _dequant_tile,
            )

            tile = _dequant_tile(
                tile, s_buf[slot], chunk, block_size, scale_groups
            )
        scores = (
            jax.lax.dot_general(
                q, tile,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Rp, CH*BS]
        col_pos = c * span + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        keep = (col_pos <= row_pos) & row_valid
        scores = jnp.where(keep, scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(
            m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new)
        )
        pmat = jnp.where(
            m_new <= NEG_INF / 2, 0.0, jnp.exp(scores - m_new)
        )
        l_new = alpha * l_prev + jnp.sum(pmat, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            pmat.astype(tile.dtype), tile[:, :kv_rank],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Rp, KVR]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((Rp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Rp, 1), jnp.float32)
    a0 = jnp.zeros((Rp, kv_rank), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nc, body, (m0, l0, a0))
    o_ref[0, 0] = jnp.where(
        l > 0, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "kv_rank", "interpret", "chunk", "tile_q", "rows_cap"
    ),
)
def mla_flash_prefill_kernel(
    q_lat: jnp.ndarray,        # [P, Lpad, Hq, C]
    c_cache,                   # [L, N, 1, BS, C] stack (or one layer's 4-D)
    block_table: jnp.ndarray,  # [P, MB] int32
    start_pos: jnp.ndarray,    # [P] int32
    true_len: jnp.ndarray,     # [P] int32
    scale: float,
    kv_rank: int,
    interpret: bool = False,
    chunk: int = 4,
    tile_q: int = 128,
    layer=None,                # int32 scalar when the cache is the stack
    rows_cap: int = 1024,      # TQ * Hq rows a query tile, about
) -> jnp.ndarray:
    from xllm_service_tpu.ops.pallas.mla_attention import _mla_common

    c_data, scales, G, layer = _mla_common(c_cache, layer)
    quantized = scales is not None
    c_cache = c_data
    P, Lpad, Hq, C = q_lat.shape
    BS = c_cache.shape[-2]
    MB = block_table.shape[1]
    TQ = min(tile_q, _round_up(Lpad, 8), max(8, rows_cap // Hq // 8 * 8))
    while (TQ * Hq) % 8:
        TQ += 1
    Lp = _round_up(Lpad, TQ)
    NT = Lp // TQ
    Rp = TQ * Hq
    CH = max(1, min(chunk, MB))

    qt = q_lat
    if Lp != Lpad:
        qt = jnp.pad(qt, ((0, 0), (0, Lp - Lpad), (0, 0), (0, 0)))
    # [P, Lp, Hq, C] -> [P, NT, TQ*Hq, C]: rows position-major so
    # row // Hq is the chunk-relative query offset within the tile.
    qt = qt.reshape(P, NT, Rp, C)

    MBp = _round_up(MB, CH)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, 1, Rp, C), lambda p, t, *_: (p, t, 0, 0)),
        hbm,
    ]
    inputs = [
        bt, start_pos.astype(jnp.int32), true_len.astype(jnp.int32), layer,
        qt, c_cache,
    ]
    scratch = [
        pltpu.VMEM((2, CH * BS, C), c_cache.dtype),
        pltpu.SemaphoreType.DMA((2, CH)),
    ]
    row_bytes = C * c_cache.dtype.itemsize
    if quantized:
        in_specs.append(hbm)
        inputs.append(scales)
        scratch += [
            pltpu.VMEM((2, CH, G, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, CH)),
        ]
        row_bytes += 4 * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(P, NT),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, Rp, kv_rank), lambda p, t, *_: (p, t, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _mla_prefill_kernel, block_size=BS, chunk=CH, tile_q=TQ, heads=Hq,
        scale=scale, kv_rank=kv_rank, quantized=quantized, scale_groups=G,
    )
    out = pl.pallas_call(
        kernel,
        name="mla_prefill_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, NT, Rp, kv_rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * P * Hq * (C + kv_rank) * Lp * MB * BS // max(NT, 1),
            bytes_accessed=(
                P * Lp * Hq * C * 4
                + P * NT * MB * BS * row_bytes
            ),
            transcendentals=P * Hq * Lp * MB * BS // max(NT, 1),
        ),
        interpret=interpret,
    )(*inputs)
    return out.reshape(P, Lp, Hq, kv_rank)[:, :Lpad]
