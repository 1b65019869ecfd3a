"""Pallas TPU paged-attention decode kernel.

The engine's hottest op (SURVEY.md §7 hard part #1; the reference's CUDA
analog lives in the absent engine submodule). One query token per running
sequence attends to that sequence's paged KV context.

Design (flash-decode, manual double-buffered DMA, chunked blocks):
  * grid = (R, Hkv): one program per (sequence, KV head). The K/V caches
    stay in HBM (`pl.ANY`); the kernel streams this sequence's blocks
    through a 2-slot VMEM buffer with `make_async_copy`, overlapping the
    next chunk's DMA with the current chunk's compute.
  * each inner iteration processes a CHUNK of `C` consecutive block-table
    entries as one [C*BS, D] tile -> a single [Gp, C*BS] score matmul.
    Shape search on real hardware: one-block-per-grid-step (4096 programs)
    and one-block-per-iteration (16 iters of ~10 ns MXU work) are both
    loop-latency-bound (~300 ns/step floor), and an 8x head-unrolled body
    stalls the Mosaic compiler; C=4 chunking cuts iteration count 4x with
    no code-size growth.
  * the block table and sequence lengths ride in scalar-prefetch SMEM; the
    inner `fori_loop` bound is the sequence's true chunk count, so no
    bandwidth is spent on other sequences' blocks. Padding entries within
    a live chunk DMA the reserved garbage block and are masked out of the
    softmax by column index.
  * GQA: the G = Hq//Hkv query heads of one KV head are processed together,
    zero-padded to Gp = roundup(G, 8) sublanes to satisfy TPU tiling;
    scores are bf16-in/f32-accum on the MXU (the fast path).

Cache operand: the STACKED pool k/v `[L, num_blocks, Hkv, BS, D]` in HBM
plus the layer index in scalar memory (`stack_operands`; a 4-D per-layer
cache is the L = 1 case) — block DMAs address `[layer, blk, h]`, so the
serving steps never slice a layer out of the pool. q `[R, Hq, D]`;
block_table `[R, MB]` int32; seq_lens `[R]` int32 (context length
INCLUDING the current token). Returns `[R, Hq, D]`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic

NEG_INF = -1e30


def dequant_tile(tile, s_buf, chunk, block_size, scale_groups):
    """VMEM dequant of an int8 cache tile [CH*BS, D] with sub-channel
    scales [CH, G, BS] (the pool's [.., H, G, BS] plane, one head's [G,
    BS] tile DMA'd per block): expand the scales to the D lanes via a
    constant 0/1 matmul (E[g, d] = 1 iff lane d's group is g) contracting
    the G axis — no lane reshapes or sublane-dynamic slices, which Mosaic
    rejects. HBM already moved int8 bytes; this is VPU/MXU work on
    resident data. Shared by every int8 kernel path (GQA + MLA, decode +
    prefill + multi-query).

    Why scales aren't folded into score/probability columns anymore (the
    round-2 scheme): column folding needs ONE scale per cache row, but a
    per-row scale plane cannot be tiled legally on every tp shard —
    Mosaic DMA slices must be (8, 128)-tile multiples and tp slices Hkv
    to 1 on production llama shards. Grouped [G % 8 == 0, BS] tiles are
    shard-invariant, and sub-channel grouping buys precision."""
    D = tile.shape[-1]
    gsz = D // scale_groups
    E = (
        jax.lax.broadcasted_iota(jnp.int32, (scale_groups, D), 1) // gsz
        == jax.lax.broadcasted_iota(jnp.int32, (scale_groups, D), 0)
    ).astype(jnp.float32)
    s_exp = jax.lax.dot_general(
        s_buf, E,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [CH, BS, D]
    s_exp = s_exp.reshape(chunk * block_size, D)
    return (tile.astype(jnp.float32) * s_exp).astype(jnp.bfloat16)


def stack_operands(k_cache, v_cache, layer):
    """(k, v, layer[1]) as every GQA kernel takes them: the caches as
    STACKED pools [L, N, Hkv, BS, D] (int8: plus [L, N, Hkv, G, BS] scale
    planes) and the layer as an int32 scalar-prefetch operand. The layer
    scans of models/llama.py carry the stack and hand it over whole, so
    no layer is ever sliced out of it; a per-layer 4-D cache is the
    L = 1 case (`cache[None]`, a bitcast, layer 0) of the same kernel."""
    from xllm_service_tpu.ops import kv_cache as kvc

    k_cache, v_cache = kvc.as_paged(k_cache), kvc.as_paged(v_cache)
    if k_cache.data.ndim == 4:
        k_cache, v_cache = (
            kvc.PagedKV(*(a if a is None else a[None] for a in c))
            for c in (k_cache, v_cache)
        )
        layer = 0
    return k_cache, v_cache, jnp.asarray(layer, jnp.int32).reshape(1)


def _decode_kernel(
    # scalar prefetch
    block_table_ref,  # [R, MBp] SMEM (padded to a multiple of C with 0s)
    seq_lens_ref,     # [R]      SMEM
    layer_ref,        # [1]      SMEM — which layer of the stack to read
    # inputs
    q_ref,            # [1, 1, Gp, D] VMEM
    k_hbm,            # [L, N, Hkv, BS, D] HBM (pl.ANY) — bf16 or int8
    v_hbm,            # [L, N, Hkv, BS, D] HBM (pl.ANY)
    *rest,            # quantized: ks_hbm, vs_hbm [L, N, Hkv, G, BS] f32, then
    # output
    #   o_ref         # [1, 1, Gp, D] VMEM
    # scratch
    #   k_buf, v_buf  # [2, C*BS, D] VMEM (cache dtype)
    #   sems          # [2, 2, C] DMA semaphores
    #   (quantized)   ks_buf, vs_buf [2, C, G, BS] f32 + ssems [2, 2, C]
    block_size: int,
    chunk: int,
    scale: float,
    quantized: bool,
    s_rows: int = 1,
    gp: int = 0,
    scale_groups: int = 8,
    window: int = 0,
):
    if quantized:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, sems, ks_buf, vs_buf, ssems = rest
    else:
        o_ref, k_buf, v_buf, sems = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None
    r = pl.program_id(0)
    h = pl.program_id(1)
    lyr = layer_ref[0]
    seq_len = seq_lens_ref[r]
    span = chunk * block_size
    # Sliding-window attention: the chunk walk starts at the first chunk
    # holding any in-window position (earliest window start across the
    # s_rows queries is seq_len - window) — blocks wholly below it never
    # stream, so SWA decode bandwidth is O(window), not O(context).
    c_lo = (
        jnp.maximum(seq_len - window, 0) // span if window > 0 else 0
    )
    if s_rows == 1:
        nc = pl.cdiv(seq_len, span)  # chunks to process
    else:
        # Multi-query (speculative verify): query row s attends to context
        # seq_len + s, so the chunk walk must cover the LAST row's context;
        # inactive slots (seq_len = 0) still process no chunks. Clamp to
        # the table width: near max_seq_len the caller may have sized the
        # table for fewer than S extra rows (true_len < S) — rows past
        # that bound are garbage the sampler never emits, and walking
        # beyond the table would read out-of-bounds SMEM block ids.
        nc = jnp.minimum(
            jnp.where(seq_len == 0, 0, pl.cdiv(seq_len + s_rows - 1, span)),
            block_table_ref.shape[1] // chunk,
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
        for c_idx in range(chunk):  # static, small
            blk = block_table_ref[r, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.start()

    def wait_chunk(slot, c):
        for c_idx in range(chunk):
            blk = block_table_ref[r, c * chunk + c_idx]
            for d in dmas(slot, c_idx, blk):
                d.wait()

    # Inactive decode slots carry seq_len = 0: issue no DMAs (their
    # semaphores would never be awaited and could satisfy a later grid
    # step's wait early) and emit zeros.
    @pl.when(nc > c_lo)
    def _first():
        start_chunk(jax.lax.rem(c_lo, 2), c_lo)

    q = q_ref[0, 0]  # [Gp, D], model dtype (bf16 on TPU)

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
        )  # [Gp, C*BS] f32
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if s_rows == 1:
            valid = c * span + col < seq_len
            if window > 0:
                valid &= c * span + col >= seq_len - window
        else:
            # q tile rows are [S, Gp] flattened: row // gp is the query's
            # offset from the first fed position (causal within the step).
            row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = c * span + col < seq_len + row // gp
            if window > 0:
                valid &= c * span + col >= seq_len + row // gp - window
        scores = jnp.where(valid, scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            v_tile = dequant_tile(
                v_buf[slot], vs_buf[slot], chunk, block_size, scale_groups
            )
            pv = jnp.dot(
                p.astype(jnp.bfloat16), v_tile,
                preferred_element_type=jnp.float32,
            )  # [Gp, D] f32
        else:
            pv = jnp.dot(
                p.astype(k_buf.dtype), v_buf[slot],
                preferred_element_type=jnp.float32,
            )
        return m_new, l_new, acc * alpha + pv

    Gp, D = q_ref.shape[2], q_ref.shape[3]
    m0 = jnp.full((Gp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Gp, 1), jnp.float32)
    a0 = jnp.zeros((Gp, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(c_lo, nc, body, (m0, l0, a0))
    # an active slot always has seq_len >= 1 (l > 0); inactive slots get 0
    o_ref[0, 0] = jnp.where(
        nc > c_lo, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "chunk", "window")
)
def paged_attention_kernel(
    q: jnp.ndarray,            # [R, Hq, D]
    k_cache,                   # [(L,) N, Hkv, BS, D] plain or PagedKV
    v_cache,
    block_table: jnp.ndarray,  # [R, MB] int32
    seq_lens: jnp.ndarray,     # [R] int32
    scale: float,
    interpret: bool = False,
    chunk: int = 4,
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
) -> jnp.ndarray:
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    quantized = k_cache.quantized
    k_data, v_data = k_cache.data, v_cache.data

    R, Hq, D = q.shape
    _, N, Hkv, BS, _ = k_data.shape
    MB = block_table.shape[1]
    G = Hq // Hkv
    Gp = _round_up(G, 8)
    C = max(1, min(chunk, MB))

    qr = q.reshape(R, Hkv, G, D)
    if Gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    MBp = _round_up(MB, C)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        # Chunk-tail entries point at the reserved garbage block 0; their
        # columns are masked out by seq_len anyway.
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    # Pin the caches to HBM explicitly: under pl.ANY the compiler may place
    # a small cache in VMEM, where the [BS, D] per-block slice is illegal
    # for D < 128 (lane-padded tiling); HBM DMA slices are contiguous.
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, 1, Gp, D), lambda r, h, *_: (r, h, 0, 0)),
        hbm,
        hbm,
    ]
    inputs = [bt, seq_lens.astype(jnp.int32), layer, qr, k_data, v_data]
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
        # Per-block scale tile is [G, BS] f32: 4*G bytes per row.
        kv_bytes_per_row += 4 * SG

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, Hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, Gp, D), lambda r, h, *_: (r, h, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _decode_kernel, block_size=BS, chunk=C, scale=scale,
        quantized=quantized,
        scale_groups=SG, window=window,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hkv, Gp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * R * Hkv * Gp * D * MB * BS,  # qk + pv
            bytes_accessed=(
                R * Hq * D * 4 + 2 * R * MB * BS * Hkv * kv_bytes_per_row
            ),
            transcendentals=R * Hkv * Gp * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)
    return out[:, :, :G, :].reshape(R, Hq, D)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "chunk", "window")
)
def multiquery_paged_attention_kernel(
    q: jnp.ndarray,            # [R, S, Hq, D] — S consecutive query tokens
    k_cache,                   # [(L,) N, Hkv, BS, D] plain or PagedKV
    v_cache,
    block_table: jnp.ndarray,  # [R, MB] int32
    seq_lens: jnp.ndarray,     # [R] int32 — context INCLUDING the FIRST
    # query token; row s of a sequence attends to seq_lens + s rows
    scale: float,
    interpret: bool = False,
    chunk: int = 4,
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
) -> jnp.ndarray:
    """Speculative-verify attention: the decode kernel with S query rows
    per sequence. Same HBM traffic as one decode step (each KV row streams
    once), S times the MXU work — the shape speculative decoding wants.
    The S*G query heads of one KV head ride one [S*Gp, D] tile; causal
    masking within the step is by tile-row // Gp. Returns [R, S, Hq, D]."""
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    quantized = k_cache.quantized
    k_data, v_data = k_cache.data, v_cache.data

    R, S, Hq, D = q.shape
    _, N, Hkv, BS, _ = k_data.shape
    MB = block_table.shape[1]
    G = Hq // Hkv
    Gp = _round_up(G, 8)
    C = max(1, min(chunk, MB))

    # [R, S, Hkv, G, D] -> [R, Hkv, S, Gp, D] -> [R, Hkv, S*Gp, D]
    qr = jnp.swapaxes(q.reshape(R, S, Hkv, G, D), 1, 2)
    if Gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    qr = qr.reshape(R, Hkv, S * Gp, D)
    MBp = _round_up(MB, C)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, 1, S * Gp, D), lambda r, h, *_: (r, h, 0, 0)),
        hbm,
        hbm,
    ]
    inputs = [bt, seq_lens.astype(jnp.int32), layer, qr, k_data, v_data]
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
        # Per-block scale tile is [G, BS] f32: 4*G bytes per row.
        kv_bytes_per_row += 4 * SG

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, Hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, S * Gp, D), lambda r, h, *_: (r, h, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _decode_kernel, block_size=BS, chunk=C, scale=scale,
        quantized=quantized, s_rows=S, gp=Gp,
        scale_groups=SG, window=window,
    )
    out = pl.pallas_call(
        kernel,
        name="multiquery_paged_attention_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hkv, S * Gp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * R * Hkv * S * Gp * D * MB * BS,
            bytes_accessed=(
                R * S * Hq * D * 4 + 2 * R * MB * BS * Hkv * kv_bytes_per_row
            ),
            transcendentals=R * Hkv * S * Gp * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)
    # [R, Hkv, S*Gp, D] -> [R, Hkv, S, Gp, D] -> [R, S, Hq, D]
    out = out.reshape(R, Hkv, S, Gp, D)[:, :, :, :G, :]
    return jnp.swapaxes(out, 1, 2).reshape(R, S, Hq, D)
