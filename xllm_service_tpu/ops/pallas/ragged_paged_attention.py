"""Pallas TPU ragged paged-attention kernel for MIXED prefill+decode batches.

The unified dispatch the Ragged Paged Attention paper (arxiv 2604.15464)
argues for, and ISSUE 9's tentpole: ONE kernel launch serves a batch
mixing chunked-prefill rows (arbitrary query length, prefix-aware start
offsets, causal + ragged masking by absolute position) and decode rows
(query length 1) over the same paged KV pool — replacing the separate
decode-kernel + flash-prefill launches and the prefill/decode batch split
in the engine hot loop (runtime/engine.py mixed step).

Contract (shared with ops.attention.ragged_attention_blockwise, the
CPU/parity oracle):

  * queries ride FLATTENED: q [T, Hq, D], the concatenation of every
    row's query-token segment. Per-row segment CAPACITIES `seg_lens`
    (static tuple, sum == T) fix each row's offset q_lo[b] at trace
    time; the dynamic `q_len[b] <= seg_lens[b]` marks the valid prefix
    (0 = dead row — inactive decode slot or padded prefill lane).
  * `pos0[b]` is the ABSOLUTE position of row b's first query token, so
    token j of row b sits at position pos0[b]+j and attends cache
    positions 0..pos0[b]+j within block_tables[b] (prefix-cache hits
    simply raise pos0; decode rows are seg 1 with pos0 = seq_len-1).

Design (the decode/flash kernels' manual double-buffered DMA structure
with a RAGGED query-tile axis):

  * grid = (NT, Hkv): one program per (flattened query tile, KV head).
    A tile is TQ consecutive flattened tokens — tiles freely CROSS row
    boundaries (a 128-token tile can hold 128 decode rows, one prefill
    chunk's slab, or a mix), which is what makes the launch count
    independent of batch composition.
  * per tile, the kernel loops over the rows overlapping it (row ranges
    are static per tile — segment offsets are static — and ride scalar
    prefetch), and per row streams that row's context blocks HBM→VMEM
    through the 2-slot buffer, C block-table entries per inner step.
    Scores for the whole [TQ*G, C*BS] tile are ONE MXU matmul per step;
    rows not owned by the current row-iteration mask to NEG_INF and
    fall out of the online-softmax merge exactly (their alpha is 1 and
    p is 0), so the flash accumulator needs no per-row state.
  * TPU grid programs execute sequentially per core, so serializing a
    tile's rows costs nothing vs the old per-row grid — total DMA and
    MXU work is identical; what the fusion buys is one launch, shared
    weight-stage scheduling in the surrounding step, and no
    prefill-vs-decode step alternation.
  * the chunk walk per (row, tile) is context-bounded: it covers only
    cache positions the row's tokens IN THIS TILE can see
    (ceil((pos0 + last_local_token + 1) / span)), and sliding-window
    rows skip blocks wholly below the window.
  * int8 caches stream pool-native [N, Hkv, G, BS] grouped scale tiles
    and dequantize in VMEM via the shared expansion matmul
    (paged_attention.dequant_tile) — the unified grouped scale contract
    from BASELINE.md round 3.

Layouts: q [T, Hq_packed, D] (GQA head packing via the
kernel_io_for/pack_queries contract happens in the ops.attention
dispatcher), caches the stacked pool [L, N, Hkv, BS, D] plus a layer
index in scalar memory (paged_attention.stack_operands; 4-D is the
L = 1 case), block_tables [B, MB] int32,
q_len/pos0 [B] int32. Returns [T, Hq, D]; dead rows emit zeros.
Chip validation: scripts/validate_kernel_tpu.py ragged-* cases (opt-in
XLLM_RAGGED_ATTENTION_KERNEL=1 until PARITY OK per the repo convention);
chip_smoke.py serves llama3-3b through it on a v5e and checks every
served token against the dense forward.
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


def _ragged_kernel(
    # scalar prefetch
    tile_start_ref,   # [NT] SMEM — first row overlapping each tile
    tile_cnt_ref,     # [NT] SMEM — rows overlapping each tile
    q_lo_ref,         # [B] SMEM — static segment offsets (flat tokens)
    q_len_ref,        # [B] SMEM — dynamic valid tokens per row
    pos0_ref,         # [B] SMEM — absolute position of first query token
    bt_ref,           # [B, MBp] SMEM block tables (padded to C multiple)
    layer_ref,        # [1] SMEM — which layer of the stack to read
    # inputs
    q_ref,            # [1, 1, TQ*G, D] VMEM — one tile's query rows
    k_hbm,            # [L, N, Hkv, BS, D] HBM
    v_hbm,            # [L, N, Hkv, BS, D] HBM
    *rest,            # quantized: ks_hbm, vs_hbm [L, N, Hkv, G, BS] f32; then
    # o_ref + scratch (k_buf/v_buf [2, C*BS, D], sems; quantized adds
    # [2, C, G, BS] f32 scale bufs + ssems)
    block_size: int,
    chunk: int,
    tile_q: int,
    groups: int,
    scale: float,
    quantized: bool,
    scale_groups: int = 8,
    window: int = 0,
):
    if quantized:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, sems, ks_buf, vs_buf, ssems = rest
    else:
        o_ref, k_buf, v_buf, sems = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None
    t = pl.program_id(0)
    h = pl.program_id(1)
    lyr = layer_ref[0]
    span = chunk * block_size
    tile_lo = t * tile_q  # first flattened token index of this tile

    q = q_ref[0, 0]  # [TQ*G, D]
    Rp, D = q.shape
    # Flattened-token index of each q-tile row (rows are token-major,
    # G head-group rows per token).
    tok_local = jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0) // groups

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

    def start_chunk(b, slot, c):
        for c_idx in range(chunk):  # static, small
            blk = bt_ref[b, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.start()

    def wait_chunk(b, slot, c):
        for c_idx in range(chunk):
            blk = bt_ref[b, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.wait()

    def row_body(bi, carry):
        b = tile_start_ref[t] + bi
        lo = q_lo_ref[b]
        ln = q_len_ref[b]
        p0 = pos0_ref[b]
        # Overlap of row b's VALID tokens with this tile, in flat coords.
        s = jnp.maximum(lo, tile_lo)
        e = jnp.minimum(lo + ln, tile_lo + tile_q)
        # Context the overlap's LAST token sees: pos0 + (e-1-lo) + 1 cols.
        ctx = p0 + (e - lo)
        nc = jnp.where(e > s, pl.cdiv(ctx, span), 0)
        # Sliding window: the FIRST overlapping token's window start
        # bounds the chunk walk from below (later tokens see later
        # windows); blocks wholly below it never stream.
        c_lo = (
            jnp.maximum(p0 + (s - lo) - window + 1, 0) // span
            if window > 0 else 0
        )

        @pl.when(nc > c_lo)
        def _first():
            start_chunk(b, jax.lax.rem(c_lo, 2), c_lo)

        # Absolute position of each q-tile row FOR THIS ROW-ITERATION
        # (only rows owned by b keep their scores).
        row_pos = p0 + (tile_lo + tok_local - lo)
        owned = (tok_local >= s - tile_lo) & (tok_local < e - tile_lo)

        def chunk_body(c, carry):
            m_prev, l_prev, acc = carry
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < nc)
            def _prefetch():
                start_chunk(b, jax.lax.rem(c + 1, 2), c + 1)

            wait_chunk(b, slot, c)
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
            keep = owned & (col_pos <= row_pos)
            if window > 0:
                keep &= col_pos > row_pos - window
            scores = jnp.where(keep, scores, NEG_INF)

            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            # Untouched rows (m == NEG_INF) keep alpha/p at 0 so their
            # accumulator stays 0; rows owned by EARLIER iterations see
            # all-NEG_INF scores here, making alpha 1 and p 0 — an exact
            # no-op on their finished state.
            alpha = jnp.where(
                m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new)
            )
            pmat = jnp.where(
                m_new <= NEG_INF / 2, 0.0, jnp.exp(scores - m_new)
            )
            l_new = alpha * l_prev + jnp.sum(pmat, axis=-1, keepdims=True)
            if quantized:
                v_tile = dequant_tile(
                    v_buf[slot], vs_buf[slot], chunk, block_size,
                    scale_groups,
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

        return jax.lax.fori_loop(c_lo, nc, chunk_body, carry)

    m0 = jnp.full((Rp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Rp, 1), jnp.float32)
    a0 = jnp.zeros((Rp, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, tile_cnt_ref[t], row_body, (m0, l0, a0)
    )
    o_ref[0, 0] = jnp.where(
        l > 0, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _tile_row_ranges(seg_lens, tile_q: int, n_tiles: int):
    """Static per-tile (first_row, row_count) over the segment layout.
    Segments are contiguous and ordered, so overlapping rows form a
    contiguous range; tiles past the last token carry (0, 0)."""
    q_lo = []
    off = 0
    for s in seg_lens:
        q_lo.append(off)
        off += s
    starts, counts = [], []
    for t in range(n_tiles):
        lo_t, hi_t = t * tile_q, (t + 1) * tile_q
        rows = [
            b for b, s in enumerate(seg_lens)
            if q_lo[b] < hi_t and q_lo[b] + s > lo_t
        ]
        starts.append(rows[0] if rows else 0)
        counts.append(len(rows))
    return q_lo, starts, counts


@functools.partial(
    jax.jit,
    static_argnames=(
        "seg_lens", "scale", "interpret", "chunk", "tile_q", "window",
    ),
)
def ragged_paged_attention_kernel(
    q: jnp.ndarray,            # [T, Hq, D] — flattened ragged queries
    k_cache,                   # [(L,) N, Hkv, BS, D] plain or PagedKV
    v_cache,
    block_tables: jnp.ndarray,  # [B, MB] int32
    q_len: jnp.ndarray,        # [B] int32 (dynamic; <= seg_lens[b])
    pos0: jnp.ndarray,         # [B] int32
    seg_lens: tuple,           # static per-row segment capacities
    scale: float,
    interpret: bool = False,
    chunk: int = 4,
    tile_q: int = 128,
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
) -> jnp.ndarray:
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    quantized = k_cache.quantized
    k_data, v_data = k_cache.data, v_cache.data

    T, Hq, D = q.shape
    _, N, Hkv, BS, _ = k_data.shape
    B, MB = block_tables.shape
    assert sum(seg_lens) == T and len(seg_lens) == B, (
        f"seg_lens {seg_lens} inconsistent with q [T={T}] / tables [B={B}]"
    )
    G = Hq // Hkv
    TQ = max(8, min(tile_q, _round_up(T, 8)))
    Tp = _round_up(T, TQ)
    NT = Tp // TQ
    Rp = TQ * G  # q-tile rows; TQ % 8 == 0 keeps sublane tiling legal
    C = max(1, min(chunk, MB))

    q_lo, tile_start, tile_cnt = _tile_row_ranges(seg_lens, TQ, NT)

    qt = q
    if Tp != T:
        qt = jnp.pad(qt, ((0, Tp - T), (0, 0), (0, 0)))
    # [Tp, Hq, D] -> [Hkv, NT, TQ*G, D], rows token-major so row // G is
    # the tile-local token index.
    qt = qt.reshape(Tp, Hkv, G, D).transpose(1, 0, 2, 3)
    qt = qt.reshape(Hkv, NT, Rp, D)

    MBp = _round_up(MB, C)
    bt = block_tables.astype(jnp.int32)
    if MBp != MB:
        # Chunk-tail entries point at the reserved garbage block 0; their
        # columns are masked out by position anyway.
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, 1, Rp, D), lambda t, h, *_: (h, t, 0, 0)),
        hbm,
        hbm,
    ]
    inputs = [
        jnp.asarray(tile_start, jnp.int32),
        jnp.asarray(tile_cnt, jnp.int32),
        jnp.asarray(q_lo, jnp.int32),
        q_len.astype(jnp.int32),
        pos0.astype(jnp.int32),
        bt,
        layer,
        qt, k_data, v_data,
    ]
    scratch = [
        pltpu.VMEM((2, C * BS, D), k_data.dtype),
        pltpu.VMEM((2, C * BS, D), v_data.dtype),
        pltpu.SemaphoreType.DMA((2, 2, C)),
    ]
    SG = k_cache.scale.shape[-2] if quantized else 8  # sub-channel groups
    kv_bytes_per_row = D * k_data.dtype.itemsize
    if quantized:
        in_specs += [hbm, hbm]
        # Pool-native [L, N, Hkv, G, BS] grouped plane (kv_cache.py) — no
        # per-call relayout, tile-legal on every tp shard.
        inputs += [
            k_cache.scale.astype(jnp.float32),
            v_cache.scale.astype(jnp.float32),
        ]
        scratch += [
            pltpu.VMEM((2, C, SG, BS), jnp.float32),
            pltpu.VMEM((2, C, SG, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ]
        kv_bytes_per_row += 4 * SG

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NT, Hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Rp, D), lambda t, h, *_: (h, t, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=BS, chunk=C, tile_q=TQ, groups=G,
        scale=scale, quantized=quantized, scale_groups=SG, window=window,
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, NT, Rp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            # Each row streams its context once per tile it spans.
            flops=4 * Tp * Hq * D * MB * BS // max(1, len(seg_lens)),
            bytes_accessed=(
                Tp * Hq * D * 4 + NT * MB * BS * Hkv * kv_bytes_per_row
            ),
            transcendentals=Tp * Hq * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)
    # [Hkv, NT, TQ*G, D] -> [Tp, Hq, D] -> drop padding.
    out = out.reshape(Hkv, Tp, G, D).transpose(1, 0, 2, 3)
    return out.reshape(Tp, Hq, D)[:T]
