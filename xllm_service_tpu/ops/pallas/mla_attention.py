"""Pallas TPU decode kernel for Multi-head Latent Attention (DeepSeek).

The MLA decode op (ops/attention.mla_paged_attention_gather) is, like GQA
decode, HBM-bandwidth-bound — but its traffic is the compressed latent
cache (kv_rank + rope_dim floats/token, shared by ALL heads), so the
gather fallback's weakness is different: XLA materializes the gathered
context [R, MB*BS, C] per layer in HBM before the einsum. This kernel
streams the sequence's latent blocks HBM→VMEM once and fuses scores +
online softmax + latent-context accumulation, never materializing the
gathered context.

Design (one program per SEQUENCE — no head axis in the grid):
  * the latents are shared across heads, so all Hq heads' scores for a
    chunk come from ONE [Hqp, C] x [C, T] matmul — MXU-shaped (Hq is 128
    for DeepSeek-V3); the grid is just (R,).
  * double-buffered chunk DMA with scalar-prefetched block tables, same
    scheme as the GQA kernel (ops/pallas/paged_attention.py).
  * pv accumulates in LATENT space ([Hqp, kv_rank]) — W_UV is applied by
    the caller once per token, outside the kernel, exactly like the
    absorbed gather path.

Cache layout: the STACKED latent pool [L, N, 1, BS, C] plus the layer
index in scalar memory: block DMAs address `[layer, blk, 0]`, so the
serving steps never slice a layer out of the pool (a 4-D per-layer cache
is the L = 1 case, as in ops/pallas/paged_attention.py). q_lat
[R, Hq, C]; block_table [R, MB]; seq_lens [R]. Returns [R, Hq, kv_rank].
C is the lane-padded row (640 for the 576 of DeepSeek-V2/V3:
ModelConfig.mla_cache_dim). At the published widths a row of the grid is
128 heads x 640 lanes against a one-head latent row: 218 FLOP a cached
byte beside a v5e's ridge of 240, bound by neither side alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic

NEG_INF = -1e30


from xllm_service_tpu.ops.pallas.paged_attention import dequant_tile

_dequant_tile = dequant_tile  # shared with mla_prefill (historical name)


def _mla_kernel(
    # scalar prefetch
    block_table_ref,  # [R, MBp] SMEM
    seq_lens_ref,     # [R] SMEM
    layer_ref,        # [1] SMEM — which layer of the stack to read
    # inputs
    q_ref,            # [1, Hqp, C] VMEM
    c_hbm,            # [L, N, 1, BS, C] HBM — bf16 or int8
    *rest,            # quantized: cs_hbm [L, N, 1, G, BS] f32, then
    # output
    #   o_ref         # [1, Hqp, KVR] VMEM
    # scratch
    #   c_buf         # [2, CH*BS, C] VMEM (cache dtype)
    #   sems          # [2, CH] DMA semaphores
    #   (quantized)   s_buf [2, CH, G, BS] f32 + ssems [2, CH]
    block_size: int,
    chunk: int,
    scale: float,
    kv_rank: int,
    s_rows: int = 1,
    hqp: int = 0,
    quantized: bool = False,
    scale_groups: int = 1,
):
    if quantized:
        cs_hbm, o_ref, c_buf, sems, s_buf, ssems = rest
    else:
        o_ref, c_buf, sems = rest
        cs_hbm = s_buf = ssems = None
    r = pl.program_id(0)
    lyr = layer_ref[0]
    seq_len = seq_lens_ref[r]
    span = chunk * block_size
    if s_rows == 1:
        nc = pl.cdiv(seq_len, span)
    else:
        # Multi-query (speculative verify): row s attends to seq_len + s
        # context rows; clamp to the table width (true_len < S near
        # max_seq_len) and keep inactive slots at zero chunks.
        nc = jnp.minimum(
            jnp.where(seq_len == 0, 0, pl.cdiv(seq_len + s_rows - 1, span)),
            block_table_ref.shape[1] // chunk,
        )

    def dmas(slot, c_idx, blk):
        out = [
            mosaic.async_copy(
                    mosaic.checked_at(c_hbm, lyr, blk, 0),
                    mosaic.checked_at(c_buf, slot, pl.ds(c_idx * block_size, block_size)),
                    sems.at[slot, c_idx],
                )
        ]
        if quantized:
            # Full-extent [G, BS] scale tile (blk on the untiled dim).
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
            for d in dmas(slot, c_idx, block_table_ref[r, c * chunk + c_idx]):
                d.start()

    def wait_chunk(slot, c):
        for c_idx in range(chunk):
            for d in dmas(slot, c_idx, block_table_ref[r, c * chunk + c_idx]):
                d.wait()

    @pl.when(nc > 0)
    def _first():
        start_chunk(0, 0)

    q = q_ref[0]  # [Hqp, C]

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nc)
        def _prefetch():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        tile = c_buf[slot]  # [CH*BS, C]
        if quantized:
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
        )  # [Hqp, CH*BS]
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if s_rows == 1:
            valid = c * span + col < seq_len
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            valid = c * span + col < seq_len + row // hqp
        scores = jnp.where(valid, scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(tile.dtype), tile[:, :kv_rank],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Hqp, KVR]
        return m_new, l_new, acc * alpha + pv

    Hqp = q_ref.shape[1]
    m0 = jnp.full((Hqp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hqp, 1), jnp.float32)
    a0 = jnp.zeros((Hqp, kv_rank), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nc, body, (m0, l0, a0))
    o_ref[0] = jnp.where(
        nc > 0, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _mla_common(c_cache, layer=None):
    """Split a plain-or-PagedKV latent cache into (data, scales, groups,
    layer[1]): data and scales as STACKS ([L, N, 1, BS, C] and
    [L, N, 1, G, BS]; a per-layer 4-D cache becomes the L = 1 stack, a
    bitcast, layer 0) and the layer as the int32 scalar-prefetch operand.

    Scales stay in their pool-native [N, 1, G, BS] layout (G groups on
    sublanes, BS on lanes, G a multiple of 8 — kv_cache.mla_scale_groups
    guarantees it): each block's DMA is then a full-extent [G, BS] tile
    with the dynamic block id on the untiled leading dim. Mosaic accepts
    only full (8,128)-tile-aligned extents on the last two dims of a DMA
    slice (chip finding, round 3) — both the old flat [N, BS*G] plane
    (1-sublane row slices) and a [.., BS, G] layout (G non-128 lanes)
    fail to compile on real hardware."""
    from xllm_service_tpu.ops import kv_cache as kvc

    c_cache = kvc.as_paged(c_cache)
    data, sc = c_cache.data, c_cache.scale
    if data.ndim == 4:
        data, sc, layer = data[None], None if sc is None else sc[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if not c_cache.quantized:
        return data, None, 1, layer
    if sc.ndim != data.ndim or sc.shape[-2] % 8:
        raise ValueError(
            f"int8 MLA caches need grouped [N, 1, G, BS] scales with "
            f"G % 8 == 0 (got scale shape {sc.shape}); allocate via "
            f"kv_cache.alloc_cache with kv_cache.mla_scale_groups"
        )
    return data, sc.astype(jnp.float32), sc.shape[-2], layer


@functools.partial(
    jax.jit, static_argnames=("scale", "kv_rank", "interpret", "chunk")
)
def mla_attention_kernel(
    q_lat: jnp.ndarray,        # [R, Hq, C]
    c_cache,                   # [L, N, 1, BS, C] stack (or one layer's 4-D)
    block_table: jnp.ndarray,  # [R, MB] int32
    seq_lens: jnp.ndarray,     # [R] int32
    scale: float,
    kv_rank: int,
    interpret: bool = False,
    chunk: int = 4,
    layer=None,                # int32 scalar when the cache is the stack
) -> jnp.ndarray:
    data, scales, G, layer = _mla_common(c_cache, layer)
    quantized = scales is not None
    R, Hq, C = q_lat.shape
    BS = data.shape[-2]
    MB = block_table.shape[1]
    Hqp = _round_up(Hq, 8)
    CH = max(1, min(chunk, MB))

    qr = q_lat
    if Hqp != Hq:
        qr = jnp.pad(qr, ((0, 0), (0, Hqp - Hq), (0, 0)))
    MBp = _round_up(MB, CH)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, Hqp, C), lambda r, *_: (r, 0, 0)),
        hbm,
    ]
    inputs = [bt, seq_lens.astype(jnp.int32), layer, qr, data]
    scratch = [
        pltpu.VMEM((2, CH * BS, C), data.dtype),
        pltpu.SemaphoreType.DMA((2, CH)),
    ]
    row_bytes = C * data.dtype.itemsize
    if quantized:
        in_specs.append(hbm)
        inputs.append(scales)
        scratch += [
            pltpu.VMEM((2, CH, G, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, CH)),
        ]
        row_bytes += 4 * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hqp, kv_rank), lambda r, *_: (r, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _mla_kernel, block_size=BS, chunk=CH, scale=scale, kv_rank=kv_rank,
        quantized=quantized, scale_groups=G,
    )
    out = pl.pallas_call(
        kernel,
        name="mla_paged_attention_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hqp, kv_rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * Hqp * C * MB * BS + 2 * R * Hqp * kv_rank * MB * BS,
            bytes_accessed=R * MB * BS * row_bytes,
            transcendentals=R * Hqp * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)
    return out[:, :Hq, :]


@functools.partial(
    jax.jit, static_argnames=("scale", "kv_rank", "interpret", "chunk")
)
def mla_multiquery_attention_kernel(
    q_lat: jnp.ndarray,        # [R, S, Hq, C] — S consecutive query tokens
    c_cache,                   # [L, N, 1, BS, C] stack (or one layer's 4-D)
    block_table: jnp.ndarray,  # [R, MB] int32
    seq_lens: jnp.ndarray,     # [R] int32 — context INCLUDING the FIRST
    # query token; row s attends to seq_lens + s rows
    scale: float,
    kv_rank: int,
    interpret: bool = False,
    chunk: int = 4,
    layer=None,                # int32 scalar when the cache is the stack
) -> jnp.ndarray:
    """Speculative-verify MLA attention: the decode kernel with S query
    rows per sequence riding one [S*Hqp, C] tile — same latent-cache HBM
    traffic as one decode step, S times the MXU work. Causal masking
    within the step is by tile-row // Hqp. Returns [R, S, Hq, kv_rank]."""
    data, scales, G, layer = _mla_common(c_cache, layer)
    quantized = scales is not None
    R, S, Hq, C = q_lat.shape
    BS = data.shape[-2]
    MB = block_table.shape[1]
    Hqp = _round_up(Hq, 8)
    CH = max(1, min(chunk, MB))

    qr = q_lat
    if Hqp != Hq:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Hqp - Hq), (0, 0)))
    qr = qr.reshape(R, S * Hqp, C)
    MBp = _round_up(MB, CH)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    in_specs = [
        pl.BlockSpec((1, S * Hqp, C), lambda r, *_: (r, 0, 0)),
        hbm,
    ]
    inputs = [bt, seq_lens.astype(jnp.int32), layer, qr, data]
    scratch = [
        pltpu.VMEM((2, CH * BS, C), data.dtype),
        pltpu.SemaphoreType.DMA((2, CH)),
    ]
    row_bytes = C * data.dtype.itemsize
    if quantized:
        in_specs.append(hbm)
        inputs.append(scales)
        scratch += [
            pltpu.VMEM((2, CH, G, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, CH)),
        ]
        row_bytes += 4 * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, S * Hqp, kv_rank), lambda r, *_: (r, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _mla_kernel, block_size=BS, chunk=CH, scale=scale, kv_rank=kv_rank,
        s_rows=S, hqp=Hqp, quantized=quantized, scale_groups=G,
    )
    out = pl.pallas_call(
        kernel,
        name="mla_multiquery_attention_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, S * Hqp, kv_rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * S * Hqp * (C + kv_rank) * MB * BS,
            bytes_accessed=R * MB * BS * row_bytes,
            transcendentals=R * S * Hqp * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)
    return out.reshape(R, S, Hqp, kv_rank)[:, :, :Hq, :]
