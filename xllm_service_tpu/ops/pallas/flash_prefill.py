"""Pallas TPU flash-attention kernel for chunked paged prefill.

The prefill half of SURVEY.md §7 hard part #1 (the decode half is
ops/pallas/paged_attention.py; the reference's CUDA analogs live in its
absent engine submodule). The executor scatters a prefill chunk's K/V
rows into the paged pool FIRST (models/llama.py prefill_batch_step), so
attention here reads everything — prefix AND chunk — from the cache:
query at absolute position p attends to cache positions 0..p.

Design (flash, manual double-buffered DMA, chunked blocks — the decode
kernel's loop structure with a query-tile axis):
  * grid = (P, Hkv, NT): one program per (sequence, KV head, query tile).
    A tile is TQ consecutive chunk positions; its G = Hq//Hkv query heads
    ride along as TQ*G sublane rows, so scores are ONE
    [TQ*G, C*BS] MXU matmul per inner step.
  * the inner fori_loop streams cache blocks HBM→VMEM through a 2-slot
    buffer (C block-table entries per iteration, next chunk's DMA
    overlapped with compute). Its bound is the tile's OWN context
    length — ceil((start_pos + min((t+1)*TQ, true_len)) / (C*BS)) — so
    early tiles don't pay for late context and padded tiles run nothing.
  * causal + ragged masking by absolute position: row r (query position
    start_pos + t*TQ + r//G) keeps column c*span + j iff that cache
    position <= its own, and rows past true_len are dead (l=0 → zeros).
  * int8 caches: sub-channel scales ride pool-native as [N, Hkv, G, BS]
    f32 — one [G, BS] tile DMA per (block, head) — and tiles dequantize
    in VMEM via the shared expansion matmul (paged_attention.dequant_tile
    explains why column folding is off the table).

Layouts: q [P, Lpad, Hq, D] (chunk-relative), caches the stacked pool
[L, N, Hkv, BS, D] plus a layer index in scalar memory
(paged_attention.stack_operands; 4-D is the L = 1 case), block_table [P, MB] int32, start_pos/true_len [P] int32. Returns
[P, Lpad, Hq, D]. Parity oracle: ops/attention.prefill_attention_blockwise
(tests/test_pallas_kernels.py drives interpret mode on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic
from xllm_service_tpu.ops.pallas.paged_attention import (
    dequant_tile,
    stack_operands,
)

NEG_INF = -1e30


def _prefill_kernel(
    # scalar prefetch
    block_table_ref,  # [P, MBp] SMEM
    start_pos_ref,    # [P] SMEM
    true_len_ref,     # [P] SMEM
    layer_ref,        # [1] SMEM — which layer of the stack to read
    # inputs
    q_ref,            # [1, 1, 1, Rp, D] VMEM (one tile's TQ*G rows)
    k_hbm,            # [L, N, Hkv, BS, D] HBM
    v_hbm,            # [L, N, Hkv, BS, Dv] HBM: Dv <= D lanes
    *rest,            # has_sink: sink_ref [1, Rp, 128] f32 VMEM (each row's
    # sink logit on every lane); then
    # quantized: ks_hbm, vs_hbm [L, N, Hkv, G, BS] f32; then
    # o_ref [.., Rp, Dv] + scratch (quantized scale bufs are [2, C, G, BS] f32)
    block_size: int,
    chunk: int,
    tile_q: int,
    groups: int,
    scale: float,
    quantized: bool,
    scale_groups: int = 8,
    window: int = 0,
    has_sink: bool = False,
):
    sink_ref = None
    if has_sink:
        sink_ref, *rest = rest
    if quantized:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, sems, ks_buf, vs_buf, ssems = rest
    else:
        o_ref, k_buf, v_buf, sems = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None
    p = pl.program_id(0)
    h = pl.program_id(1)
    t = pl.program_id(2)
    lyr = layer_ref[0]
    start = start_pos_ref[p]
    n_valid = true_len_ref[p]
    span = chunk * block_size

    # This tile's context: positions 0 .. start + min((t+1)*TQ, true_len).
    tile_lo = t * tile_q  # first chunk-relative position of the tile
    ctx = start + jnp.minimum(tile_lo + tile_q, n_valid)
    nc = jnp.where(tile_lo < n_valid, pl.cdiv(ctx, span), 0)
    # Sliding window: the chunk walk starts at the first chunk holding any
    # in-window column (earliest window start across the tile's rows is
    # start + tile_lo - window + 1); earlier blocks never stream, so SWA
    # prefill bandwidth is O(L * window), not O(L * context).
    c0 = (
        jnp.maximum(start + tile_lo - window + 1, 0) // span
        if window > 0 else 0
    )

    def dmas(slot, c_idx, blk):
        off = c_idx * block_size
        out = [
            mosaic.async_copy(
                mosaic.checked_at(k_hbm, lyr, blk, h),
                mosaic.checked_at(k_buf, slot, pl.ds(off, block_size)),
                sems.at[slot, 0, c_idx],
            ),
            mosaic.async_copy(
                mosaic.checked_at(v_hbm, lyr, blk, h),
                mosaic.checked_at(v_buf, slot, pl.ds(off, block_size)),
                sems.at[slot, 1, c_idx],
            ),
        ]
        if quantized:
            # Head h's [G, BS] scale tile (layer, blk, h on untiled dims).
            out.append(
                mosaic.async_copy(
                    mosaic.checked_at(ks_hbm, lyr, blk, h),
                    mosaic.checked_at(ks_buf, slot, c_idx),
                    ssems.at[slot, 0, c_idx],
                )
            )
            out.append(
                mosaic.async_copy(
                    mosaic.checked_at(vs_hbm, lyr, blk, h),
                    mosaic.checked_at(vs_buf, slot, c_idx),
                    ssems.at[slot, 1, c_idx],
                )
            )
        return out

    def start_chunk(slot, c):
        for c_idx in range(chunk):
            blk = block_table_ref[p, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.start()

    def wait_chunk(slot, c):
        for c_idx in range(chunk):
            blk = block_table_ref[p, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.wait()

    @pl.when(nc > 0)
    def _first():
        start_chunk(jax.lax.rem(c0, 2) if window > 0 else 0, c0)

    q = q_ref[0, 0, 0]  # [Rp, D]
    Rp, D = q.shape
    # Absolute position of each query row: start + tile_lo + row // G.
    row_pos = start + tile_lo + (
        jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // groups
    )
    row_valid = tile_lo + (
        jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // groups
    ) < n_valid

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        k_tile = k_buf[slot]
        if quantized:
            k_tile = dequant_tile(
                k_tile, ks_buf[slot], chunk, block_size, scale_groups
            )
        scores = (
            jax.lax.dot_general(
                q, k_tile,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Rp, C*BS] f32
        col_pos = c * span + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        keep = (col_pos <= row_pos) & row_valid
        if window > 0:
            # HF SWA semantics: position p attends [p-window+1, p].
            keep &= col_pos > row_pos - window
        scores = jnp.where(keep, scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked-so-far rows: keep alpha/p at 0 so acc stays 0.
        alpha = jnp.where(
            m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new)
        )
        pmat = jnp.where(
            m_new <= NEG_INF / 2, 0.0, jnp.exp(scores - m_new)
        )
        l_new = alpha * l_prev + jnp.sum(pmat, axis=-1, keepdims=True)
        if quantized:
            v_tile = dequant_tile(
                v_buf[slot], vs_buf[slot], chunk, block_size, scale_groups
            )
            pv = jnp.dot(
                pmat.astype(jnp.bfloat16), v_tile,
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jnp.dot(
                pmat.astype(k_buf.dtype), v_buf[slot],
                preferred_element_type=jnp.float32,
            )
        return m_new, l_new, acc * alpha + pv

    if has_sink:
        # The sink is the softmax's first logit and has no value row.
        m0 = sink_ref[0][:, :1]
        l0 = jnp.ones((Rp, 1), jnp.float32)
    else:
        m0 = jnp.full((Rp, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((Rp, 1), jnp.float32)
    a0 = jnp.zeros((Rp, o_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(c0, nc, body, (m0, l0, a0))
    o_ref[0, 0, 0] = jnp.where(
        l > 0, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "chunk", "tile_q", "window"),
)
def flash_prefill_kernel(
    q: jnp.ndarray,            # [P, Lpad, Hq, D]
    k_cache,                   # [(L,) N, Hkv, BS, D] plain or PagedKV
    v_cache,
    block_table: jnp.ndarray,  # [P, MB] int32
    start_pos: jnp.ndarray,    # [P] int32
    true_len: jnp.ndarray,     # [P] int32
    scale: float,
    interpret: bool = False,
    chunk: int = 4,
    tile_q: int = 128,
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
    sinks=None,                # [Hq] f32: a sink logit a head, or None
) -> jnp.ndarray:
    """Returns [P, Lpad, Hq, Dv], Dv the value pool's lanes. A launch with
    a window is "window_flash_prefill_kernel" in the trace."""
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    quantized = k_cache.quantized
    k_data, v_data = k_cache.data, v_cache.data

    P, Lpad, Hq, D = q.shape
    _, N, Hkv, BS, _ = k_data.shape
    Dv = v_data.shape[-1]
    MB = block_table.shape[1]
    G = Hq // Hkv
    TQ = min(tile_q, _round_up(Lpad, 8))
    # Rows per tile must satisfy 8-sublane tiling: TQ*G padded via TQ.
    while (TQ * G) % 8:
        TQ += 1
    Lp = _round_up(Lpad, TQ)
    NT = Lp // TQ
    Rp = TQ * G
    C = max(1, min(chunk, MB))

    qt = q
    if Lp != Lpad:
        qt = jnp.pad(qt, ((0, 0), (0, Lp - Lpad), (0, 0), (0, 0)))
    # [P, Lp, Hq, D] -> [P, Hkv, NT, TQ*G, D], rows position-major so
    # row // G is the chunk-relative query offset within the tile.
    qt = qt.reshape(P, NT, TQ, Hkv, G, D)
    qt = qt.transpose(0, 3, 1, 2, 4, 5).reshape(P, Hkv, NT, Rp, D)

    MBp = _round_up(MB, C)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec(
            (1, 1, 1, Rp, D), lambda p, h, t, *_: (p, h, t, 0, 0)
        ),
        hbm,
        hbm,
    ]
    inputs = [
        bt, start_pos.astype(jnp.int32), true_len.astype(jnp.int32), layer,
        qt, k_data, v_data,
    ]
    if sinks is not None:
        # row r of a tile is query head r % G of the KV head's group
        rows = jnp.tile(sinks.astype(jnp.float32).reshape(Hkv, 1, G), (1, TQ, 1))
        in_specs.append(pl.BlockSpec((1, Rp, 128), lambda p, h, t, *_: (h, 0, 0)))
        inputs.append(jnp.broadcast_to(rows.reshape(Hkv, Rp, 1), (Hkv, Rp, 128)))
    scratch = [
        pltpu.VMEM((2, C * BS, D), k_data.dtype),
        pltpu.VMEM((2, C * BS, Dv), v_data.dtype),
        pltpu.SemaphoreType.DMA((2, 2, C)),
    ]
    SG = k_cache.scale.shape[-2] if quantized else 8  # sub-channel groups
    kv_bytes_per_row = D * k_data.dtype.itemsize
    if quantized:
        in_specs += [hbm, hbm]
        # Pool-native [L, N, Hkv, G, BS] grouped plane (see kv_cache.py).
        inputs += [
            k_cache.scale.astype(jnp.float32),
            v_cache.scale.astype(jnp.float32),
        ]
        scratch += [
            pltpu.VMEM((2, C, SG, BS), jnp.float32),
            pltpu.VMEM((2, C, SG, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ]
        # Per-block scale tile is [G, BS] f32: 4*G bytes per row.
        kv_bytes_per_row += 4 * SG

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(P, Hkv, NT),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, 1, Rp, Dv), lambda p, h, t, *_: (p, h, t, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _prefill_kernel, block_size=BS, chunk=C, tile_q=TQ, groups=G,
        scale=scale, quantized=quantized,
        scale_groups=SG, window=window, has_sink=sinks is not None,
    )
    out = pl.pallas_call(
        kernel,
        # op name in the device trace
        name=("window_" if window > 0 else "") + "flash_prefill_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, Hkv, NT, Rp, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            # ~L^2/2 causal flops per (seq, head-group); bytes dominated by
            # re-streaming the context per query tile.
            flops=2 * P * Hq * D * Lp * (Lp + 2 * MB * BS) // 2,
            bytes_accessed=(
                P * Lp * Hq * D * 4
                + P * NT * MB * BS * Hkv * kv_bytes_per_row
            ),
            transcendentals=P * Hq * Lp * MB * BS // max(NT, 1),
        ),
        interpret=interpret,
    )(*inputs)
    # [P, Hkv, NT, TQ*G, D] -> [P, Lp, Hq, D] -> slice chunk rows.
    out = out.reshape(P, Hkv, NT, TQ, G, Dv).transpose(0, 2, 3, 1, 4, 5)
    return out.reshape(P, Lp, Hq, Dv)[:, :Lpad]
