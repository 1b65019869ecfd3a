"""Pallas TPU flash kernels for MLA (DeepSeek) chunked paged prefill, in
the two forms of the one algebra (`q W_UK . c` = `q . W_UK^T c`):

  * ABSORBED (`mla_flash_prefill_kernel`, below): queries projected into
    the latent space outside, scores and context in latent space;
  * MATERIALISED (`mla_materialised_prefill_kernel`, at the end of the
    file): each head's keys and values made from the latent blocks in
    VMEM, 0.56x the absorbed form's FLOP at a 512-row chunk.

ops.attention.attention_routes picks one by the rows of a chunk.

The absorbed kernel is the prefill sibling of ops/pallas/mla_attention.py (decode) — same latent
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


# ------------------------------------------------ the materialised form
# A head and (query, cached position) pair costs the absorbed kernel
# 2 x (640 + 512) executed FLOP. Here it costs 2 x (256 + 128) for the
# scores (dn + the lane-padded rope part) and p.v, plus the up-projection
# of the cached position for that head, 2 x kv_rank x (dn + dv), shared
# by every query row of the tile: 512 a pair at 512 rows. The crossover
# is near 170 rows a chunk, which is why decode and the verify shapes
# keep the absorbed form.
#
# A query tile is MANY positions of FEW heads (the opposite of the
# absorbed kernel's 8 positions x 128 heads): grid (P, Hq / G, NT); a
# grid step holds G heads' W_UK / W_UV (2 x G x 128 KB) and walks the
# tile's context ONCE, so a chunk walks it Hq / G times. For each
# double-buffered group of latent blocks and each head of the group:
# k_nope = c W_UK_h and v = c W_UV_h (float32 accumulation, rounded to
# the pool's dtype as the published kv_b_proj gives them), scores
# [q_nope | q_pe] . [k_nope | k_pe], online softmax in float32,
# acc += p v. The softmax scale rides the queries (one multiply a tile
# and head, not one a score).

# Heads whose code makes ONE block of the loop over a group's heads: the
# scheduler then runs one head's matmuls under the other's softmax (on a
# v5e 4.29 ms against 5.13 at 7168 cached tokens; four a block no better).
HEADS_A_BLOCK = 2


def _mla_materialised_prefill_kernel(
    # scalar prefetch
    block_table_ref,  # [P, MBp] SMEM
    start_pos_ref,    # [P] SMEM
    true_len_ref,     # [P] SMEM
    layer_ref,        # [1] SMEM — which layer of the stack to read
    w_layer_ref,      # [1] SMEM — which layer of the W_UK / W_UV stacks (the block specs read it)
    # inputs
    q_ref,            # [1, TQ, G*(dn+dr)] VMEM: a head's [q_nope | rope part, NOT roped]
    qpe_ref,          # [1, TQ, G*dr] VMEM: a head's roped q_pe
    wuk_ref,          # [G, kv_rank, dn] VMEM
    wuv_ref,          # [G, kv_rank, dv] VMEM
    c_hbm,            # [L, N, 1, BS, C] HBM
    # output
    o_ref,            # [1, TQ, G*dv] VMEM
    # scratch
    c_buf,            # [2, CH*BS, C] VMEM (pool dtype)
    sems,             # [2, CH]
    q_scr,            # [G, TQ, DQ] VMEM: [q_nope | q_pe | 0] head-major, scaled
    bias_scr,         # [TQ, CH*BS] f32: 0 where a row may see a column
    m_scr,            # [G, TQ, 1] f32
    l_scr,            # [G, TQ, 1] f32
    acc_scr,          # [G, TQ, dv] f32
    *,
    block_size: int,
    chunk: int,
    tile_q: int,
    heads: int,
    scale: float,
    kv_rank: int,
    unroll: int,
):
    p = pl.program_id(0)
    t = pl.program_id(2)
    lyr = layer_ref[0]
    start = start_pos_ref[p]
    n_valid = true_len_ref[p]
    span = chunk * block_size
    DQ = q_scr.shape[-1]
    dn = wuk_ref.shape[-1]
    dv = wuv_ref.shape[-1]

    tile_lo = t * tile_q
    ctx = start + jnp.minimum(tile_lo + tile_q, n_valid)
    nc = jnp.where(tile_lo < n_valid, pl.cdiv(ctx, span), 0)

    def dma(slot, c_idx, blk):
        return mosaic.async_copy(
            mosaic.checked_at(c_hbm, lyr, blk, 0),
            mosaic.checked_at(c_buf, slot, pl.ds(c_idx * block_size, block_size)),
            sems.at[slot, c_idx],
        )

    def start_chunk(slot, c):
        for c_idx in range(chunk):
            dma(slot, c_idx, block_table_ref[p, c * chunk + c_idx]).start()

    def wait_chunk(slot, c):
        for c_idx in range(chunk):
            dma(slot, c_idx, block_table_ref[p, c * chunk + c_idx]).wait()

    @pl.when(nc > 0)
    def _first():
        start_chunk(0, 0)

    # Position-major in, as the projection wrote them (a head's dn + dr
    # lanes side by side, whatever their alignment); head-major, roped,
    # scaled and as wide as [k_nope | the pool's rope lanes] here.
    dr = qpe_ref.shape[-1] // heads

    def scaled(x):
        return (x.astype(jnp.float32) * scale).astype(q_scr.dtype)

    for h in range(heads):
        q_scr[h, :, :dn] = scaled(q_ref[0, :, h * (dn + dr):h * (dn + dr) + dn])
        q_scr[h, :, dn:dn + dr] = scaled(qpe_ref[0, :, h * dr:(h + 1) * dr])
        if DQ > dn + dr:
            q_scr[h, :, dn + dr:] = jnp.zeros(
                (tile_q, DQ - dn - dr), q_scr.dtype
            )
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # A row sees the columns at or before its own position. Rows past
    # true_len are not masked here (every row then has column 0 to see,
    # so no running maximum stays at NEG_INF): they are zeroed at the end.
    row_off = jax.lax.broadcasted_iota(jnp.int32, (tile_q, 1), 0)
    ahead = jax.lax.broadcasted_iota(jnp.int32, (tile_q, span), 1) - row_off

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        bias_scr[...] = jnp.where(
            ahead <= start + tile_lo - c * span, 0.0, NEG_INF
        )
        wait_chunk(slot, c)

        def head(h):
            tile = c_buf[slot]  # [CH*BS, C]
            lat = tile[:, :kv_rank]
            k_nope = jax.lax.dot_general(
                lat, wuk_ref[h],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(tile.dtype)  # [CH*BS, dn]
            v = jax.lax.dot_general(
                lat, wuv_ref[h],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(tile.dtype)  # [CH*BS, dv]
            k = jnp.concatenate([k_nope, tile[:, kv_rank:]], axis=1)
            scores = jax.lax.dot_general(
                q_scr[h], k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + bias_scr[...]  # [TQ, CH*BS]
            m_prev = m_scr[h]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            pmat = jnp.exp(scores - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(
                pmat, axis=-1, keepdims=True
            )
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                pmat.astype(tile.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        def some_heads(i, carry):
            for j in range(unroll):  # one block of code: their work interleaves
                head(i * unroll + j)
            return carry

        return jax.lax.fori_loop(0, heads // unroll, some_heads, carry)

    jax.lax.fori_loop(0, nc, body, 0)
    row_valid = tile_lo + row_off < n_valid
    for h in range(heads):
        l = l_scr[h]
        o_ref[0, :, h * dv:(h + 1) * dv] = jnp.where(
            row_valid & (l > 0), acc_scr[h] / jnp.maximum(l, 1e-30), 0.0
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "kv_rank", "interpret", "chunk", "tile_q", "head_group"
    ),
)
def mla_materialised_prefill_kernel(
    q: jnp.ndarray,            # [P, Lpad, Hq, dn + dr]: the projection's heads
    q_pe: jnp.ndarray,         # [P, Lpad, Hq, dr]: their rope part, roped
    w_uk: jnp.ndarray,         # [Hq, kv_rank, dn], or the layers' stack [n, Hq, ..]
    w_uv: jnp.ndarray,         # [Hq, kv_rank, dv], or the layers' stack
    c_cache,                   # [L, N, 1, BS, C] stack (or one layer's 4-D)
    block_table: jnp.ndarray,  # [P, MB] int32
    start_pos: jnp.ndarray,    # [P] int32
    true_len: jnp.ndarray,     # [P] int32
    scale: float,
    kv_rank: int,
    interpret: bool = False,
    chunk: int = 4,
    tile_q: int = 512,
    head_group: int = 8,
    layer=None,                # int32 scalar when the cache is the stack
    w_layer=None,              # int32 scalar when w_uk / w_uv are the stacks
) -> jnp.ndarray:
    """MLA chunked-prefill attention in the MATERIALISED form over a bf16
    (unquantized) latent pool. `q` is the query projection's result as it
    lies, a head's [q_nope | rope part] (the rope part NOT roped: only
    its first dn lanes a head are read), so that nothing is sliced out or
    re-laid before the launch; `q_pe` is the roped rope part. W_UK and
    W_UV may be the layers' STACKS with `w_layer`, as the pool is: a grid
    step then fetches its heads of that layer from where the stack lies
    (a layer sliced out of a scan's operands would be copied for the
    custom call, 2 x 16 MB a layer at the published widths). Returns
    [P, Lpad, Hq, dv]: the heads' VALUE-space outputs (the caller applies
    W_O only). Oracle: ops/attention.mla_prefill_blockwise followed by
    W_UV."""
    from xllm_service_tpu.ops.pallas.mla_attention import _mla_common

    c_cache, scales, _, layer = _mla_common(c_cache, layer)
    if scales is not None:
        raise ValueError("the materialised form takes an unquantized pool")
    if w_uk.ndim == 3:
        w_uk, w_uv, w_layer = w_uk[None], w_uv[None], 0
    w_layer = jnp.asarray(w_layer, jnp.int32).reshape(1)
    P, Lpad, Hq, dr = q_pe.shape
    dn, dv = q.shape[-1] - dr, w_uv.shape[-1]
    BS, C = c_cache.shape[-2:]
    MB = block_table.shape[1]
    G = min(head_group, Hq)
    while Hq % G:
        G -= 1
    unroll = HEADS_A_BLOCK if G % HEADS_A_BLOCK == 0 else 1
    TQ = min(tile_q, _round_up(Lpad, 8))
    Lp = _round_up(Lpad, TQ)
    NT = Lp // TQ
    CH = max(1, min(chunk, MB))
    DQ = dn + C - kv_rank  # the rope part as wide as the pool's, zeros after dr

    # One row a position, every head's lanes side by side (a bitcast):
    # a grid step reads G heads of it.
    if Lp != Lpad:
        q, q_pe = (
            jnp.pad(a, ((0, 0), (0, Lp - Lpad), (0, 0), (0, 0)))
            for a in (q, q_pe)
        )
    q = q.reshape(P, Lp, Hq * (dn + dr))
    q_pe = q_pe.reshape(P, Lp, Hq * dr)

    MBp = _round_up(MB, CH)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(P, Hq // G, NT),
        in_specs=[
            pl.BlockSpec((1, TQ, G * (dn + dr)), lambda p, g, t, *_: (p, t, g)),
            pl.BlockSpec((1, TQ, G * dr), lambda p, g, t, *_: (p, t, g)),
            pl.BlockSpec(
                (None, G, kv_rank, dn), lambda p, g, t, *s: (s[4][0], g, 0, 0)
            ),
            pl.BlockSpec(
                (None, G, kv_rank, dv), lambda p, g, t, *s: (s[4][0], g, 0, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_specs=pl.BlockSpec(
            (1, TQ, G * dv), lambda p, g, t, *_: (p, t, g)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, CH * BS, C), c_cache.dtype),
            pltpu.SemaphoreType.DMA((2, CH)),
            pltpu.VMEM((G, TQ, DQ), c_cache.dtype),
            pltpu.VMEM((TQ, CH * BS), jnp.float32),
            pltpu.VMEM((G, TQ, 1), jnp.float32),
            pltpu.VMEM((G, TQ, 1), jnp.float32),
            pltpu.VMEM((G, TQ, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_materialised_prefill_kernel, block_size=BS, chunk=CH,
        tile_q=TQ, heads=G, scale=scale, kv_rank=kv_rank, unroll=unroll,
    )
    pairs = P * Hq * Lp * MB * BS // max(NT, 1)
    out = pl.pallas_call(
        kernel,
        # The op's name in the device trace: NOT a name that starts with
        # the absorbed kernel's, whose readers count that form's FLOP.
        name="mla_materialised_prefill_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, Lp, Hq * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=(
                2 * pairs * (DQ + dv)
                + 2 * P * Hq * NT * MB * BS * kv_rank * (dn + dv)
            ),
            bytes_accessed=(
                P * Lp * Hq * (dn + 2 * dr + dv) * q.dtype.itemsize
                + P * (Hq // G) * NT * MB * BS * C * c_cache.dtype.itemsize
            ),
            transcendentals=pairs,
        ),
        interpret=interpret,
    )(
        bt, start_pos.astype(jnp.int32), true_len.astype(jnp.int32), layer,
        w_layer, q, q_pe, w_uk, w_uv, c_cache,
    )
    return out.reshape(P, Lp, Hq, dv)[:, :Lpad]
