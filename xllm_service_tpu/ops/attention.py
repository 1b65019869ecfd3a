"""Paged attention over a block-structured KV cache.

Engine-tier hot op (the reference's paged-attention CUDA kernel lives in the
absent submodule; the service-visible contract is only the 128-token block
size + chained hashing — SURVEY.md §2.3). Two implementations:

  * `paged_attention_gather` — pure-jnp reference: gathers each sequence's
    blocks via its block table and runs masked SDPA. Exact; used on CPU
    (tests) and as the correctness oracle for the Pallas kernel.
  * `ops/pallas/paged_attention.py` — TPU Pallas kernel that streams KV
    blocks HBM→VMEM per (sequence, kv-head) program with the block table in
    scalar memory. Selected on TPU via `ops.attention.paged_attention`.

Cache layout (one layer): k_cache, v_cache `[num_blocks, num_kv_heads,
block_size, head_dim]` — KV-head-major within a block so the Pallas kernel
DMAs a [block_size, head_dim] tile per (block, head) with TPU-legal tiling;
the KV-head axis shards over the `tp` mesh axis.

Every GQA entry point here also takes the STACKED pool `[L, num_blocks,
...]` with `layer=` (an int32 scalar, traced inside the layer scan): the
kernels address `[layer, blk, head]` and the fallbacks gather
`cache[layer, table]`, so no path ever materializes one layer of the pool
(models/llama.py carries the stack through its scans; docs/KV_CACHE.md).
Without `layer` the cache is one layer's 4-D array, as the MLA paths and
the kernel tests pass it.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kv_cache as kvc

NEG_INF = -1e30


# ------------------------------------------------- sharded kernel dispatch
# Pallas kernels are opaque custom calls to XLA's GSPMD partitioner: under
# a tp>1 mesh it cannot partition them, so a kernel launched from inside
# the jitted step would silently run replicated over a gathered cache —
# exactly the degradation the per-shard tier exists to kill. The serving
# dispatchers below therefore wrap every kernel launch in `shard_map`
# over the tp axis when a shard context is declared: each shard runs ONE
# kernel over its own contiguous slice of query heads and KV heads
# (attention is head-independent, so no collectives are needed), the GQA
# packing/eligibility trio evaluates against the PER-SHARD cache
# geometry inside the mapped body, and the fused mixed/spec steps stay
# one-launch-per-shard. XLLM_SHARDED_KERNELS=0 is the escape hatch back
# to the pre-shard_map GSPMD behavior (docs/SHARDING.md).
#
# The context is per-thread (each engine thread serves one executor) and
# read at TRACE time — the same lifetime every other kernel hatch here
# has (the jitted steps bake the decision in at first trace).

_SHARD_TLS = threading.local()


def sharded_kernels_enabled() -> bool:
    import os

    return os.environ.get("XLLM_SHARDED_KERNELS") != "0"


def set_shard_context(mesh, axis: str = "tp") -> None:
    """Declare the mesh the current thread's kernel dispatches run under
    (runtime/executor.py sets it before every jitted step family so the
    trace captures the right mesh; None clears). Ignored for meshes
    without a >1 `axis` extent."""
    if mesh is not None and mesh.shape.get(axis, 1) > 1:
        _SHARD_TLS.ctx = (mesh, axis)
    else:
        _SHARD_TLS.ctx = None


def shard_context():
    """(mesh, axis) when per-shard kernel dispatch applies, else None."""
    ctx = getattr(_SHARD_TLS, "ctx", None)
    if ctx is None or not sharded_kernels_enabled():
        return None
    return ctx


def declared_shard_context():
    """The raw (mesh, axis) the executor declared for this thread,
    ignoring the XLLM_SHARDED_KERNELS gate — that hatch escapes KERNEL
    dispatch to GSPMD; consumers with their own hatch (the overlap
    collectives tier, ops/collective_matmul.py) still need the mesh."""
    return getattr(_SHARD_TLS, "ctx", None)


def _cache_shard_spec(cache, axis: str):
    """shard_map spec pytree for a cache operand: data [(L,) N, Hc, BS, D]
    and int8 scale [(L,) N, Hc, G, BS] both carry the head axis third
    from last."""
    from jax.sharding import PartitionSpec as P

    lead = kvc.raw(cache).ndim - 3
    spec = P(*(None,) * lead, axis, None, None)
    if isinstance(cache, kvc.PagedKV):
        return kvc.PagedKV(spec, spec if cache.scale is not None else None)
    return spec


def _shardable(q: jnp.ndarray, k_cache, ctx) -> bool:
    """Whether this (query, cache) pair can shard over ctx's axis: the
    query heads and the per-shard cache geometry must divide evenly —
    gqa_kernel_eligible re-checks the cache side per shard."""
    if ctx is None:
        return False
    n = ctx[0].shape[ctx[1]]
    return q.shape[-2] % n == 0 and kvc.raw(k_cache).shape[-3] % n == 0


def _kernel_call(body, ctx, q_spec_ndim: int, q, k_cache, v_cache,
                 *rep_args, layer=None):
    """Run `body(q, k, v, *rep_args, layer)`: directly, or once per tp
    shard via shard_map under a shard context.

    `body` receives PER-SHARD operands (Hq/tp query heads, Hc/tp cache
    rows) and must do its own packing (kernel_io_for inside the body sees
    the per-shard geometry). Tables/lengths/positions and the layer index
    replicate; the output's head axis is at `q_spec_ndim - 1` == ndim-2
    of q."""
    from jax.sharding import PartitionSpec as P

    # One operand list for both routes: a 4-D cache reads "layer 0" of
    # its own L = 1 stack (pallas/paged_attention.stack_operands).
    rep_args += (jnp.asarray(0 if layer is None else layer, jnp.int32),)
    if ctx is None:
        return body(q, k_cache, v_cache, *rep_args)
    mesh, axis = ctx
    head_ax = q_spec_ndim - 2
    q_spec = P(*(
        axis if i == head_ax else None for i in range(q_spec_ndim)
    ))
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            q_spec,
            _cache_shard_spec(k_cache, axis),
            _cache_shard_spec(v_cache, axis),
        ) + (P(),) * len(rep_args),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, *rep_args)


def _pack_ratio(cache, q_head_dim: int) -> int:
    """Heads packed per cache row (kv_cache.kv_pack_factor layouts):
    1 for ordinary caches, cache_row_dim / head_dim for packed ones."""
    return kvc.raw(cache).shape[-1] // q_head_dim


def _pack_lanes(heads: int, pack: int, groups: int) -> jnp.ndarray:
    """[Hq, pack] one-hot of which packed lane-block each query head's
    kv head occupies (query head h -> kv head h // groups)."""
    i = (jnp.arange(heads, dtype=jnp.int32) // groups) % pack
    return jax.nn.one_hot(i, pack, dtype=jnp.float32)


def kernel_io_for(cache, q: jnp.ndarray):
    """(pack, kv_heads, packed_q) for a kernel call against `cache` —
    the one place the pack/derive trio lives (review r3)."""
    pack = _pack_ratio(cache, q.shape[-1])
    kv_heads = kvc.raw(cache).shape[-3] * pack
    return pack, kv_heads, pack_queries(q, pack, kv_heads)


def _packed_kernel_allowed(pack: int) -> bool:
    """Packed-pair shapes are a NEW on-chip shape class validated only in
    interpret mode so far; per the repo's opt-in-until-chip-validated
    convention they ride the kernels only under XLLM_PACKED_KV_KERNEL=1
    (scripts/validate_kernel_tpu.py carries the packed cases; flip the
    default once they report PARITY OK on silicon)."""
    import os

    return pack == 1 or os.environ.get("XLLM_PACKED_KV_KERNEL") == "1"


def pack_queries(q: jnp.ndarray, pack: int, kv_heads: int) -> jnp.ndarray:
    """Embed queries block-diagonally for a packed cache: [..., Hq, D] ->
    [..., Hq, pack*D] with head h's vector in its kv head's lane block and
    zeros elsewhere — zeros keep q·k scores exact against packed K rows,
    and the pv garbage lanes are discarded by unpack_outputs."""
    if pack == 1:
        return q
    *lead, hq, d = q.shape
    oh = _pack_lanes(hq, pack, hq // kv_heads).astype(q.dtype)
    return jnp.einsum("...hd,hp->...hpd", q, oh).reshape(*lead, hq, pack * d)


def unpack_outputs(o: jnp.ndarray, pack: int, kv_heads: int) -> jnp.ndarray:
    """Select each query head's own lane block from packed attention
    output: [..., Hq, pack*D] -> [..., Hq, D]."""
    if pack == 1:
        return o
    *lead, hq, dp = o.shape
    oh = _pack_lanes(hq, pack, hq // kv_heads).astype(o.dtype)
    o = o.reshape(*lead, hq, pack, dp // pack)
    return jnp.einsum("...hpd,hp->...hd", o, oh)


def gather_context(
    k_cache,  # [num_blocks, Hkv, block_size, D] (plain or PagedKV)
    v_cache,
    block_table: jnp.ndarray,  # [R, max_blocks] int32
    unpack: int = 1,
    layer=None,
):
    """Gather each sequence's context as [R, max_blocks*block_size, Hkv, D].
    Quantized (int8) caches are dequantized after the gather — only the
    sequence's own blocks pay the dequant, not the whole pool. `unpack`
    undoes packed-pair rows (head_dim < 128 layouts) on the gathered
    slice only. `layer` indexes a stacked pool."""
    k_ctx = kvc.unpack_rows(
        kvc.gather_blocks(k_cache, block_table, layer=layer), unpack
    )
    v_ctx = kvc.unpack_rows(
        kvc.gather_blocks(v_cache, block_table, layer=layer), unpack
    )
    k_ctx = jnp.swapaxes(k_ctx, 2, 3)
    v_ctx = jnp.swapaxes(v_ctx, 2, 3)
    R, MB, BS, H, D = k_ctx.shape
    return (
        k_ctx.reshape(R, MB * BS, H, D),
        v_ctx.reshape(R, MB * BS, H, v_ctx.shape[-1]),  # value rows may be narrower
    )


def _sdpa(
    q: jnp.ndarray,  # [R, Lq, Hq, D]
    k: jnp.ndarray,  # [R, Lk, Hkv, D]
    v: jnp.ndarray,  # [R, Lk, Hkv, D]
    mask: jnp.ndarray,  # [R, Lq, Lk] bool (True = attend)
    scale: float,
    sinks: jnp.ndarray | None = None,  # [Hq] f32: a logit more a head
) -> jnp.ndarray:
    """Value rows may be narrower than key rows (v [.., Dv]). `sinks`
    adds exp(sink) to each head's softmax denominator and nothing to
    its output: a sink's mass is dropped."""
    R, Lq, Hq, D = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(R, Lq, Hkv, groups, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [R, Hkv, groups, Lq, Lk]
    scores = jnp.einsum("rqhgd,rkhd->rhgqk", qf, kf) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    if sinks is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        sink = sinks.astype(jnp.float32).reshape(1, Hkv, groups, 1, 1)
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
        e = jnp.exp(scores - m)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))
    out = jnp.einsum("rhgqk,rkhd->rqhgd", probs, vf)
    return out.reshape(R, Lq, Hq, vf.shape[-1]).astype(q.dtype)


def paged_attention_gather(
    q: jnp.ndarray,  # [R, Hq, D] — one query token per sequence (decode)
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_table: jnp.ndarray,  # [R, max_blocks]
    seq_lens: jnp.ndarray,  # [R] context length INCLUDING current token
    scale: float,
    window: int = 0,
    layer=None,
    sinks=None,
) -> jnp.ndarray:
    """Decode-step attention: each query attends to its first seq_lens cache
    rows — the LAST `window` of them when sliding-window attention is on
    (window > 0, HF semantics: positions [pos-window+1, pos]). Returns
    [R, Hq, D]."""
    k_ctx, v_ctx = gather_context(
        k_cache, v_cache, block_table,
        unpack=_pack_ratio(k_cache, q.shape[-1]), layer=layer,
    )
    Lk = k_ctx.shape[1]
    cols = jnp.arange(Lk, dtype=jnp.int32)[None, :]  # [1, Lk]
    mask = cols < seq_lens[:, None]  # [R, Lk]
    if window > 0:
        mask = mask & (cols >= seq_lens[:, None] - window)
    out = _sdpa(q[:, None], k_ctx, v_ctx, mask[:, None, :], scale, sinks)
    return out[:, 0]


def prefill_attention_gather(
    q: jnp.ndarray,  # [L, Hq, D] — chunk of new tokens for ONE sequence
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_table: jnp.ndarray,  # [max_blocks]
    start_pos: jnp.ndarray,  # scalar int32: tokens already in cache (prefix hit)
    true_len: jnp.ndarray,  # scalar int32: valid tokens in this chunk
    scale: float,
    window: int = 0,
    layer=None,
    sinks=None,
) -> jnp.ndarray:
    """Chunked-prefill attention for one sequence: rows are chunk positions
    start_pos..start_pos+L, columns the sequence's cache rows (which already
    contain this chunk's K/V — caller scatters before attending). Causal;
    window > 0 restricts each row to its last `window` positions.
    Reference oracle — materializes the full [L, Lk] score matrix; the
    serving path uses prefill_attention_blockwise. Returns [L, Hq, D]."""
    k_ctx, v_ctx = gather_context(
        k_cache, v_cache, block_table[None],
        unpack=_pack_ratio(k_cache, q.shape[-1]), layer=layer,
    )
    L = q.shape[0]
    Lk = k_ctx.shape[1]
    rows = start_pos + jnp.arange(L, dtype=jnp.int32)  # absolute positions
    cols = jnp.arange(Lk, dtype=jnp.int32)
    causal = cols[None, :] <= rows[:, None]
    if window > 0:
        causal = causal & (cols[None, :] > rows[:, None] - window)
    valid_row = jnp.arange(L, dtype=jnp.int32) < true_len
    mask = causal & valid_row[:, None]
    out = _sdpa(q[None], k_ctx, v_ctx, mask[None], scale, sinks)
    return out[0]


def prefill_attention_blockwise(
    q: jnp.ndarray,  # [L, Hq, D]
    k_cache: jnp.ndarray,  # [(L,) num_blocks, Hkv, BS, D]
    v_cache: jnp.ndarray,
    block_table: jnp.ndarray,  # [CB] — sliced to the context bound
    start_pos: jnp.ndarray,  # scalar int32
    true_len: jnp.ndarray,  # scalar int32
    scale: float,
    window: int = 0,
    layer=None,
    sinks=None,  # [Hq] f32: a logit more a head, its mass dropped
) -> jnp.ndarray:
    """Flash-style prefill: lax.scan over KV blocks with online-softmax
    accumulation. Peak memory is O(L * BS) per step instead of the dense
    O(L * CB*BS) score matrix — a full 8K x 8K bf16 prefill's f32 scores
    (~8.5 GB for 32 heads) would not fit v5e HBM. Exact (log-sum-exp
    merge), parity-tested against prefill_attention_gather."""
    L, Hq, D = q.shape
    pack = _pack_ratio(k_cache, D)
    Hkv = kvc.raw(k_cache).shape[-3] * pack
    BS = kvc.raw(k_cache).shape[-2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(L, Hkv, G, D)
    rows = start_pos + jnp.arange(L, dtype=jnp.int32)  # absolute positions
    valid_row = jnp.arange(L, dtype=jnp.int32) < true_len

    # One [L, Hkv, G, *] layout throughout the carry.
    Dv = kvc.raw(v_cache).shape[-1] // pack  # value rows may be narrower
    m0 = jnp.full((L, Hkv, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((L, Hkv, G, 1), jnp.float32)
    if sinks is not None:
        # The sink is the softmax's first logit, with no value row.
        m0 = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(Hkv, G, 1), m0.shape)
        l0 = jnp.ones_like(l0)
    a0 = jnp.zeros((L, Hkv, G, Dv), jnp.float32)

    def body(carry, inputs):
        m_prev, l_prev, acc = carry
        blk_idx, blk_id = inputs
        k_blk = kvc.unpack_rows(
            kvc.gather_block(k_cache, blk_id, jnp.float32, layer), pack
        )  # [Hkv, BS, D]
        v_blk = kvc.unpack_rows(
            kvc.gather_block(v_cache, blk_id, jnp.float32, layer), pack
        )
        cols = blk_idx * BS + jnp.arange(BS, dtype=jnp.int32)
        scores = (
            jnp.einsum("qhgd,hkd->qhgk", qf, k_blk) * scale
        )  # [L, Hkv, G, BS]
        mask = (cols[None, :] <= rows[:, None]) & valid_row[:, None]
        if window > 0:
            mask = mask & (cols[None, :] > rows[:, None] - window)
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)  # >= m_prev by construction
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        p = jnp.exp(scores - m_new)
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("qhgk,hkd->qhgd", p, v_blk)
        return (m_new, l_new, acc), None

    CB = block_table.shape[0]
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.arange(CB, dtype=jnp.int32), block_table.astype(jnp.int32)),
    )
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(L, Hq, Dv).astype(q.dtype)



def _kernel_tile_ok(cache, lane_dim: int, on: bool) -> bool:
    """Mosaic tile-legality gate for every Pallas kernel path (chip
    findings, round 3): DMA slice dims must be tile MULTIPLES on the
    last two dims. `lane_dim` is the per-row lane width (head_dim D for
    GQA, the lane-padded latent dim C for MLA) and must be a 128
    multiple; BS sits on sublanes of the [BS, lane_dim] data slice (16
    bf16; int8's stricter bound is subsumed below); int8 additionally
    streams [G, BS] scale tiles with BS on LANES, so quantized caches
    need BS % 128."""
    BS = kvc.raw(cache).shape[-2]
    cq = isinstance(cache, kvc.PagedKV) and cache.quantized
    return (
        on
        and lane_dim % 128 == 0
        and (BS % 128 == 0 if cq else BS % 16 == 0)
    )


def _gqa_kernel_ok(k_cache, on: bool) -> bool:
    # Gate on the CACHE row width: packed head_dim<128 layouts carry
    # 128-lane rows and are kernel-eligible; unpacked narrow rows are not.
    return _kernel_tile_ok(k_cache, kvc.raw(k_cache).shape[-1], on)


def gqa_kernel_eligible(
    k_cache, q_head_dim: int, on: bool, shards: int = 1
) -> bool:
    """THE tile/lane/packing eligibility gate for every GQA Pallas path
    (decode, flash prefill, multi-query verify, ragged mixed) — one
    predicate instead of a per-dispatcher copy of the `_kernel_tile_ok`
    + `_packed_kernel_allowed` pair (ISSUE 9 satellite). `on` is the
    platform gate (_on_tpu() or interpret). `shards` > 1 evaluates the
    PER-SHARD cache geometry of the shard_map'd dispatch: the (possibly
    packed) cache-head axis must split evenly over tp or the per-shard
    kernel is declined (the caller then serves the GSPMD path; the
    config-level resolve_kv_packing fallback normally prevents this, but
    the gate must hold for hand-built caches too)."""
    if shards > 1 and kvc.raw(k_cache).shape[-3] % shards:
        return False
    return _gqa_kernel_ok(k_cache, on) and _packed_kernel_allowed(
        _pack_ratio(k_cache, q_head_dim)
    )


def cache_kernel_route(cache, interpret: bool = False):
    """(eligible, ctx) for a Pallas launch over the pool `cache` itself,
    not through a dispatcher here (the in-place write, ops/kv_write.py):
    the GQA tile gate on the attached platform and, on a tp mesh, the
    shard context to launch per shard under (`ctx`, as _kernel_call takes
    it; None on one device). Not eligible under the XLLM_SHARDED_KERNELS=0
    hatch, which sends everything back to GSPMD, nor when the cache heads
    do not split over the shards."""
    ctx = shard_context()
    ok = (
        _gqa_kernel_ok(cache, _on_tpu() or interpret)
        and (ctx is not None or declared_shard_context() is None)
        and (ctx is None or kvc.raw(cache).shape[-3] % ctx[0].shape[ctx[1]] == 0)
    )
    return ok, ctx


def _mla_kernel_ok(c_cache, on: bool) -> bool:
    return _kernel_tile_ok(c_cache, kvc.raw(c_cache).shape[-1], on)


@region("attn")
def prefill_attention(
    q: jnp.ndarray,  # [P, Lpad, Hq, D] — the batched chunk's queries
    k_cache,
    v_cache,
    block_tables: jnp.ndarray,  # [P, CB]
    start_pos: jnp.ndarray,  # [P]
    true_len: jnp.ndarray,  # [P]
    scale: float,
    use_kernel: bool | None = None,
    interpret: bool = False,
    window: int = 0,
    layer=None,
    sinks=None,  # [Hq] f32: a logit more a head (not on the verify shapes)
) -> jnp.ndarray:
    """Batched chunked-prefill attention over the paged cache; Pallas
    flash kernel (ops/pallas/flash_prefill.py) on TPU, vmapped blockwise
    scan elsewhere. window > 0 = sliding-window attention (each position
    attends its last `window` positions; kernels also skip blocks wholly
    below the window). Same eligibility rules as the decode kernel (D a
    lane multiple; int8 additionally needs BS scale rows 128-wide); env
    override XLLM_PREFILL_ATTENTION_KERNEL=0/1 forces the path, and
    `interpret` lets CI drive the kernel branch on CPU."""
    import os

    # One eligibility predicate for BOTH Pallas paths (flash prefill and
    # the multi-query verify kernel). Under a shard context (tp>1) each
    # kernel launches per-shard via shard_map and the packing trio
    # (kernel_io_for) evaluates the per-shard cache geometry inside the
    # mapped body.
    # Packed-pair caches (head_dim < 128): queries embed block-diagonally
    # into the 128-lane rows; outputs slice back (pack_queries docstring).
    ctx = shard_context() if _shardable(q, k_cache, shard_context()) else None
    shards = ctx[0].shape[ctx[1]] if ctx is not None else 1
    kernel_ok = gqa_kernel_eligible(
        k_cache, q.shape[-1], _on_tpu() or interpret, shards=shards
    )

    # Speculative-verify shapes (a handful of query rows per sequence):
    # the multi-query decode kernel streams each KV row ONCE like a decode
    # step — the flash-prefill kernel would pad S~4 rows to a 128-row
    # query tile. Default ON for bf16 since the mq-bf16 case validated on
    # a real v5e chip (round 3, scripts/validate_kernel_tpu.py); int8
    # stays opt-in (XLLM_MQ_ATTENTION_KERNEL=1) until mq-int8 validates
    # on the grouped scale layout. =0 disables outright.
    S = q.shape[1]
    mq_env = os.environ.get("XLLM_MQ_ATTENTION_KERNEL")
    kq_mq = isinstance(k_cache, kvc.PagedKV) and k_cache.quantized
    if (
        use_kernel is None
        and S <= 8
        and sinks is None
        and kernel_ok
        and (mq_env == "1" if kq_mq else mq_env != "0")
        # The function-wide kill switch keeps covering EVERY kernel path
        # here: =0 forces the blockwise reference even for mq shapes.
        and os.environ.get("XLLM_PREFILL_ATTENTION_KERNEL") != "0"
    ):
        from xllm_service_tpu.ops.pallas.paged_attention import (
            multiquery_paged_attention_kernel,
        )

        seq_lens = jnp.where(true_len > 0, start_pos + 1, 0)

        def mq_body(qq, kk, vv, bt, sl, lyr):
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                multiquery_paged_attention_kernel(
                    q_packed, kk, vv, bt, sl, scale,
                    interpret=interpret, window=window, layer=lyr,
                ),
                pack, kv_heads,
            )

        return _kernel_call(
            mq_body, ctx, 4, q, k_cache, v_cache, block_tables, seq_lens,
            layer=layer,
        )

    env = os.environ.get("XLLM_PREFILL_ATTENTION_KERNEL")
    if use_kernel is None:
        use_kernel = (env != "0") if kernel_ok else (env == "1")
    if use_kernel:
        from xllm_service_tpu.ops.pallas.flash_prefill import (
            flash_prefill_kernel,
        )

        def flash_body(qq, kk, vv, bt, sp, tl, lyr):
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                flash_prefill_kernel(
                    q_packed, kk, vv, bt, sp, tl, scale,
                    interpret=interpret, window=window, layer=lyr,
                    sinks=sinks,
                ),
                pack, kv_heads,
            )

        return _kernel_call(
            flash_body, ctx, 4, q, k_cache, v_cache, block_tables,
            start_pos, true_len, layer=layer,
        )
    return jax.vmap(
        lambda qi, ti, sp, tl: prefill_attention_blockwise(
            qi, k_cache, v_cache, ti, sp, tl, scale, window=window,
            layer=layer, sinks=sinks,
        )
    )(q, block_tables, start_pos, true_len)


# ----------------------------------------------------------------- MLA
# Multi-head Latent Attention (DeepSeek-V2/V3): the paged cache stores ONE
# compressed row per token — concat(c_kv [kv_rank], k_pe [rope_dim]) — and
# decode runs in ABSORBED form: queries are projected into the latent space
# (q_nope @ W_UK per head) so scores and the attention-weighted context are
# computed directly against cache rows, with the per-head V up-projection
# applied once to the [kv_rank] context vector. This is what makes the
# ~3.5x-smaller cache also a bandwidth win: no per-head K/V is ever
# materialized for cached tokens.


def mla_paged_attention_gather(
    q_lat: jnp.ndarray,  # [R, Hq, C] — concat(absorbed q_nope, roped q_pe)
    c_cache,  # [N, 1, BS, C] plain or PagedKV (C = kv_rank + rope_dim)
    block_table: jnp.ndarray,  # [R, MB] int32
    seq_lens: jnp.ndarray,  # [R] int32 (INCLUDING current token)
    scale: float,
    kv_rank: int,
    layer=None,  # int32 scalar: c_cache is the STACK [L, N, 1, BS, C]
) -> jnp.ndarray:
    """Decode-step MLA attention. Returns the attention-weighted LATENT
    context [R, Hq, kv_rank] (caller applies W_UV per head)."""
    ctx = kvc.gather_blocks(c_cache, block_table, jnp.float32, layer=layer)
    R, MB, _, BS, C = ctx.shape
    ctx = ctx.reshape(R, MB * BS, C)
    scores = (
        jnp.einsum("rhc,rtc->rht", q_lat.astype(jnp.float32), ctx) * scale
    )
    cols = jnp.arange(MB * BS, dtype=jnp.int32)[None, None, :]
    scores = jnp.where(cols < seq_lens[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("rht,rtk->rhk", p, ctx[:, :, :kv_rank])
    return out.astype(q_lat.dtype)


@region("attn")
def mla_paged_attention(
    q_lat, c_cache, block_table, seq_lens, scale, kv_rank,
    use_kernel: bool | None = None, interpret: bool = False, layer=None,
):
    """Decode MLA attention over the latent pool (the stack plus `layer`,
    or one layer's 4-D cache): the Pallas kernel on TPU
    where the tiles are eligible (XLLM_MLA_ATTENTION_KERNEL=0 is the
    hatch back to the gather path; nothing forces the kernel where the
    shapes decline it), the gather elsewhere. Int8 latent caches ride the kernel too (sub-channel
    scales stream in a separate plane and dequantize in VMEM); `interpret`
    lets CI drive the kernel branch on CPU."""
    import os

    if use_kernel is None:
        use_kernel = (
            _mla_kernel_ok(c_cache, _on_tpu() or interpret)
            and os.environ.get("XLLM_MLA_ATTENTION_KERNEL") != "0"
        )
    if use_kernel:
        from xllm_service_tpu.ops.pallas.mla_attention import (
            mla_attention_kernel,
        )

        return mla_attention_kernel(
            q_lat, c_cache, block_table, seq_lens, scale, kv_rank,
            interpret=interpret, layer=layer,
        )
    return mla_paged_attention_gather(
        q_lat, c_cache, block_table, seq_lens, scale, kv_rank, layer=layer
    )


@region("attn")
def mla_prefill_attention(
    q_lat: jnp.ndarray,  # [P, Lpad, Hq, C] — the batched chunk's queries
    c_cache,
    block_tables: jnp.ndarray,  # [P, CB]
    start_pos: jnp.ndarray,  # [P]
    true_len: jnp.ndarray,  # [P]
    scale: float,
    kv_rank: int,
    use_kernel: bool | None = None,
    interpret: bool = False,
    layer=None,  # int32 scalar: c_cache is the STACK [L, N, 1, BS, C]
) -> jnp.ndarray:
    """Batched MLA chunked-prefill attention in ABSORBED form; Pallas
    flash kernel (ops/pallas/mla_prefill.py) on TPU, vmapped blockwise
    scan elsewhere. Int8 latent caches ride both kernel branches
    (sub-channel scales stream in their own plane, VMEM dequant);
    XLLM_MLA_PREFILL_KERNEL=0/1 forces the flash path, `interpret` drives
    the kernel branches in CI."""
    import os

    quantized = isinstance(c_cache, kvc.PagedKV) and c_cache.quantized
    # Speculative-verify shapes: the multi-query MLA decode kernel streams
    # each latent row once (see the GQA analog in prefill_attention);
    # int8 latent caches dequantize in VMEM inside the kernel.
    # Opt-in via XLLM_MQ_ATTENTION_KERNEL=1 until chip-validated.
    S = q_lat.shape[1]
    if (
        use_kernel is None
        and S <= 8
        and _mla_kernel_ok(c_cache, _on_tpu() or interpret)
        and os.environ.get("XLLM_MQ_ATTENTION_KERNEL") == "1"
    ):
        from xllm_service_tpu.ops.pallas.mla_attention import (
            mla_multiquery_attention_kernel,
        )

        seq_lens = jnp.where(true_len > 0, start_pos + 1, 0)
        return mla_multiquery_attention_kernel(
            q_lat, c_cache, block_tables, seq_lens, scale,
            kv_rank, interpret=interpret, layer=layer,
        )
    if use_kernel is None:
        env = os.environ.get("XLLM_MLA_PREFILL_KERNEL")
        # int8 stays OPT-IN (env == "1") until the mla-prefill-int8 chip
        # case validates — the convention for every unvalidated kernel
        # path; bf16 keeps its existing default.
        kernel_ok = (
            _mla_kernel_ok(c_cache, _on_tpu() or interpret)
            and not quantized
        )
        use_kernel = (env != "0") if kernel_ok else (env == "1")
    if use_kernel:
        from xllm_service_tpu.ops.pallas.mla_prefill import (
            mla_flash_prefill_kernel,
        )

        return mla_flash_prefill_kernel(
            q_lat, c_cache, block_tables, start_pos, true_len,
            scale, kv_rank, interpret=interpret, layer=layer,
        )
    return jax.vmap(
        lambda qi, ti, sp, tl: mla_prefill_blockwise(
            qi, c_cache, ti, sp, tl, scale, kv_rank, layer=layer
        )
    )(q_lat, block_tables, start_pos, true_len)


def mla_prefill_blockwise(
    q_lat: jnp.ndarray,  # [Lq, Hq, C] for ONE sequence's chunk
    c_cache,  # [N, 1, BS, C]
    block_table: jnp.ndarray,  # [CB] — sliced to the context bound
    start_pos: jnp.ndarray,  # scalar int32
    true_len: jnp.ndarray,  # scalar int32
    scale: float,
    kv_rank: int,
    layer=None,  # int32 scalar: c_cache is the STACK [L, N, 1, BS, C]
) -> jnp.ndarray:
    """Flash-style causal MLA prefill over latent blocks (online softmax,
    O(Lq * BS) peak score memory). Returns [Lq, Hq, kv_rank]."""
    Lq, Hq, C = q_lat.shape
    BS = kvc.raw(c_cache).shape[-2]
    qf = q_lat.astype(jnp.float32)
    rows = start_pos + jnp.arange(Lq, dtype=jnp.int32)
    valid_row = jnp.arange(Lq, dtype=jnp.int32) < true_len

    m0 = jnp.full((Lq, Hq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Lq, Hq, 1), jnp.float32)
    a0 = jnp.zeros((Lq, Hq, kv_rank), jnp.float32)

    def body(carry, inputs):
        m_prev, l_prev, acc = carry
        blk_idx, blk_id = inputs
        blk = kvc.gather_block(
            c_cache, blk_id, jnp.float32, layer=layer
        )[0]  # [BS, C]
        cols = blk_idx * BS + jnp.arange(BS, dtype=jnp.int32)
        scores = jnp.einsum("qhc,kc->qhk", qf, blk) * scale  # [Lq, Hq, BS]
        mask = (cols[None, :] <= rows[:, None]) & valid_row[:, None]
        scores = jnp.where(mask[:, None, :], scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        p = jnp.exp(scores - m_new)
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("qhk,kc->qhc", p, blk[:, :kv_rank])
        return (m_new, l_new, acc), None

    CB = block_table.shape[0]
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.arange(CB, dtype=jnp.int32), block_table.astype(jnp.int32)),
    )
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q_lat.dtype)


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # A backend that fails to initialise raises here: an engine meant for
    # the chip must not be routed to the gather/blockwise reference.
    return jax.devices()[0].platform == "tpu"


@region("attn")
def paged_attention(
    q, k_cache, v_cache, block_table, seq_lens, scale,
    use_kernel: bool | None = None, window: int = 0,
    interpret: bool = False, layer=None, sinks=None,
):
    """Decode paged attention; Pallas kernel on TPU, gather fallback elsewhere.
    `sinks` [Hq] f32 is a logit more a head in the softmax's denominator
    (its mass dropped); value rows may be narrower than key rows.

    The kernel is the DEFAULT on TPU since round 2: validated on a real v5e
    chip (scripts/validate_kernel_tpu.py — max |err| vs the gather oracle
    0.002 in bf16, 2.5-8x faster across llama-8B/70B-class decode shapes).
    Set XLLM_PAGED_ATTENTION_KERNEL=0 to force the gather path, =1 to force
    the kernel even where the default heuristics decline it. Under a
    declared shard context (set_shard_context; tp>1 meshes) the kernel
    launches per-shard through shard_map — one launch per tp shard over
    its own head slice — instead of degrading to a GSPMD-replicated
    custom call.

    head_dim < 128 models ride the kernel through the packed-pair cache
    layout (kv_cache.kv_pack_factor: a bare [BS, 64] block slice is below
    one 128-lane Mosaic tile — observed on-chip as a tpu.memref_slice
    verification failure — so P heads pack per 128-lane row and queries
    embed block-diagonally, see pack_queries)."""
    import os

    ctx = shard_context() if _shardable(q, k_cache, shard_context()) else None
    shards = ctx[0].shape[ctx[1]] if ctx is not None else 1
    env = os.environ.get("XLLM_PAGED_ATTENTION_KERNEL")
    if use_kernel is None:
        kernel_ok = gqa_kernel_eligible(
            k_cache, q.shape[-1], _on_tpu() or interpret, shards=shards
        )
        use_kernel = (env != "0") if kernel_ok else (env == "1")
    if use_kernel:
        try:
            from xllm_service_tpu.ops.pallas.paged_attention import (
                paged_attention_kernel,
            )
        except ImportError:
            use_kernel = False
        else:
            def body(qq, kk, vv, bt, sl, lyr):
                # Per-shard packing: kernel_io_for reads the (per-shard,
                # under shard_map) cache geometry.
                pack, kv_heads, q_packed = kernel_io_for(kk, qq)
                return unpack_outputs(
                    paged_attention_kernel(
                        q_packed, kk, vv, bt, sl, scale,
                        window=window, interpret=interpret, layer=lyr,
                        sinks=sinks,
                    ),
                    pack, kv_heads,
                )

            return _kernel_call(
                body, ctx, 3, q, k_cache, v_cache, block_table, seq_lens,
                layer=layer,
            )
    return paged_attention_gather(
        q, k_cache, v_cache, block_table, seq_lens, scale, window=window,
        layer=layer, sinks=sinks,
    )


# ------------------------------------------------ ragged mixed batches
# One attention call for a batch mixing chunked-prefill rows (arbitrary
# query length, prefix-aware start offsets) and decode rows (query length
# 1) over the same paged KV — the Ragged Paged Attention shape (arxiv
# 2604.15464; docs/KERNELS.md). The flattened-query contract:
#
#   q        [T, Hq, D]   — all rows' query tokens, segment-concatenated
#   seg_lens tuple (static) — per-row segment CAPACITY; sum == T. A row's
#                             tokens live at [q_lo[b], q_lo[b]+q_len[b])
#                             with q_lo = exclusive prefix sum of seg_lens
#   q_len    [B] int32    — valid tokens per row (<= seg_lens[b]; 0 = dead)
#   pos0     [B] int32    — ABSOLUTE position of the row's first query
#                             token (prefix hits / decode context offset)
#   tables   [B, CB]      — per-row block table
#
# Row b's token j sits at absolute position pos0[b]+j and attends cache
# positions 0..pos0[b]+j (causal; `window` restricts to the trailing
# window). Decode rows are seg_lens[b] == 1 with pos0 = seq_len - 1.


def ragged_attention_blockwise(
    q: jnp.ndarray,  # [T, Hq, D] flattened ragged queries
    k_cache,
    v_cache,
    block_tables: jnp.ndarray,  # [B, CB]
    q_len: jnp.ndarray,  # [B] int32
    pos0: jnp.ndarray,  # [B] int32
    seg_lens: tuple,  # static per-row segment capacities
    scale: float,
    window: int = 0,
    layer=None,
    sinks=None,
) -> jnp.ndarray:
    """Blockwise oracle for the ragged mixed contract: each row runs the
    chunked-prefill blockwise scan (prefill_attention_blockwise handles
    query length 1 — a decode row — exactly like the decode gather, and
    arbitrary ragged lengths with prefix offsets). Exact; the CPU/parity
    reference for ops/pallas/ragged_paged_attention.py. Returns
    [T, Hq, D] with dead rows (q_len 0) zeroed."""
    outs = []
    off = 0
    for b, seg in enumerate(seg_lens):
        out_b = prefill_attention_blockwise(
            q[off:off + seg], k_cache, v_cache, block_tables[b],
            pos0[b], q_len[b], scale, window=window, layer=layer,
            sinks=sinks,
        )
        # Blockwise emits acc/l with l=0 rows zeroed already; mask the
        # padded tail explicitly so dead segments are deterministic.
        valid = (
            jnp.arange(seg, dtype=jnp.int32)[:, None, None] < q_len[b]
        )
        outs.append(jnp.where(valid, out_b, 0).astype(q.dtype))
        off += seg
    return jnp.concatenate(outs, axis=0)


def _ragged_serves(k_cache, v_cache, sinks) -> bool:
    """The ragged kernel has no sink logit and takes key and value rows
    of one width: a launch with either goes to the decode and the flash
    kernel side by side (or to the blockwise oracle), which have both."""
    return sinks is None and (
        kvc.raw(k_cache).shape[-1] == kvc.raw(v_cache).shape[-1]
    )


def ragged_kernel_enabled(
    k_cache, q_head_dim: int, use_kernel: bool | None = None,
    interpret: bool = False, shards: int = 1,
) -> bool:
    """Dispatch decision for the ragged mixed kernel. Follows the repo's
    opt-in-until-chip-validated convention: the kernel is NEW silicon
    surface (queued in scripts/validate_kernel_tpu.py as ragged-*), so
    the default is OFF even on TPU until parity lands —
    XLLM_RAGGED_ATTENTION_KERNEL=1 opts in, =0 forces the reference
    path, and `interpret` (the XLLM_RAGGED_INTERPRET CI hook) opts in
    on its own — the hook exists to DRIVE the kernel branch on CPU, so
    it must select it, not merely flavor it (=0 still wins).
    Tile/lane/packing eligibility via the shared gate."""
    import os

    if use_kernel is not None:
        return use_kernel and gqa_kernel_eligible(
            k_cache, q_head_dim, _on_tpu() or interpret, shards=shards
        )
    env = os.environ.get("XLLM_RAGGED_ATTENTION_KERNEL")
    if env == "0":
        return False
    return (env == "1" or interpret) and gqa_kernel_eligible(
        k_cache, q_head_dim, _on_tpu() or interpret, shards=shards
    )


def ragged_paged_attention(
    q: jnp.ndarray,  # [T, Hq, D]
    k_cache,
    v_cache,
    block_tables: jnp.ndarray,  # [B, CB]
    q_len: jnp.ndarray,  # [B]
    pos0: jnp.ndarray,  # [B]
    seg_lens: tuple,
    scale: float,
    use_kernel: bool | None = None,
    interpret: bool = False,
    window: int = 0,
    layer=None,
    sinks=None,
) -> jnp.ndarray:
    """Ragged mixed-batch paged attention: ONE Pallas dispatch over
    prefill + decode rows when the kernel is enabled
    (ragged_kernel_enabled), blockwise oracle otherwise — ONE dispatch
    PER TP SHARD under a shard context (the fused mixed/spec engine
    steps stay one-launch-per-shard on multi-chip meshes). GQA head
    packing rides the kernel_io_for/pack_queries contract like every
    other GQA kernel path; int8 caches stream pool-native grouped
    scales."""
    ctx = shard_context() if _shardable(q, k_cache, shard_context()) else None
    shards = ctx[0].shape[ctx[1]] if ctx is not None else 1
    if _ragged_serves(k_cache, v_cache, sinks) and ragged_kernel_enabled(
        k_cache, q.shape[-1], use_kernel, interpret, shards=shards
    ):
        from xllm_service_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention_kernel,
        )

        def body(qq, kk, vv, bt, ql, p0, lyr):
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                ragged_paged_attention_kernel(
                    q_packed, kk, vv, bt, ql, p0, seg_lens, scale,
                    interpret=interpret, window=window, layer=lyr,
                ),
                pack, kv_heads,
            )

        return _kernel_call(
            body, ctx, 3, q, k_cache, v_cache, block_tables, q_len, pos0,
            layer=layer,
        )
    return ragged_attention_blockwise(
        q, k_cache, v_cache, block_tables, q_len, pos0, seg_lens, scale,
        window=window, layer=layer, sinks=sinks,
    )


@region("attn")
def mixed_attention(
    q_dec: jnp.ndarray,  # [R, Hq, D] — decode slots (some inactive)
    q_pf: jnp.ndarray,  # [P, Lpad, Hq, D] — prefill chunk rows
    k_cache,
    v_cache,
    dec_tables: jnp.ndarray,  # [R, CBd]
    dec_seq_lens: jnp.ndarray,  # [R] context INCLUDING this token; 0 = off
    pf_tables: jnp.ndarray,  # [P, CBp]
    pf_start: jnp.ndarray,  # [P]
    pf_len: jnp.ndarray,  # [P]
    scale: float,
    use_ragged: bool | None = None,
    interpret: bool = False,
    window: int = 0,
    layer=None,
    sinks=None,
):
    """Attention for one MIXED engine step (models.llama.mixed_step):
    decode slots and chunked-prefill rows against the same paged KV.

    Ragged kernel on: the whole batch flattens into ONE Pallas dispatch
    (seg_lens = R decode singletons + P Lpad segments). Otherwise the
    reference path runs each half through its own serving dispatcher —
    the Pallas decode kernel + flash prefill on TPU, gather + blockwise
    on CPU — so mixed-step outputs match the split engine's byte for
    byte while still fusing the rest of the step into one dispatch.
    The halves may carry different context-bucket table widths (the
    executor buckets each exactly like its split program); the ragged
    flatten pads the narrower table with garbage-block-0 entries, which
    the kernel's context bound never walks."""
    R = q_dec.shape[0]
    P, Lpad = q_pf.shape[0], q_pf.shape[1]
    if _ragged_serves(k_cache, v_cache, sinks) and ragged_kernel_enabled(
        k_cache, q_dec.shape[-1], use_ragged, interpret
    ):
        seg_lens = (1,) * R + (Lpad,) * P
        q_flat = jnp.concatenate(
            [q_dec, q_pf.reshape(P * Lpad, *q_pf.shape[2:])], axis=0
        )
        CB = max(dec_tables.shape[1], pf_tables.shape[1])
        dt = jnp.pad(dec_tables, ((0, 0), (0, CB - dec_tables.shape[1])))
        pt = jnp.pad(pf_tables, ((0, 0), (0, CB - pf_tables.shape[1])))
        tables = jnp.concatenate([dt, pt], axis=0)
        q_len = jnp.concatenate(
            [jnp.minimum(dec_seq_lens, 1), pf_len]
        ).astype(jnp.int32)
        pos0 = jnp.concatenate(
            [jnp.maximum(dec_seq_lens - 1, 0), pf_start]
        ).astype(jnp.int32)
        out = ragged_paged_attention(
            q_flat, k_cache, v_cache, tables, q_len, pos0, seg_lens,
            scale, use_kernel=True, interpret=interpret, window=window,
            layer=layer, sinks=sinks,
        )
        return out[:R], out[R:].reshape(*q_pf.shape[:-1], out.shape[-1])
    # Reference pair: EXACTLY the split engine's dispatchers. interpret
    # is deliberately NOT forwarded — it is the ragged-branch CI hook,
    # and leaking it here would flip the prefill half onto the
    # interpret-mode flash kernel while split-step engines run
    # blockwise, breaking the mixed ≡ split byte-parity contract.
    dec_out = paged_attention(
        q_dec, k_cache, v_cache, dec_tables, dec_seq_lens, scale,
        window=window, layer=layer, sinks=sinks,
    )
    pf_out = prefill_attention(
        q_pf, k_cache, v_cache, pf_tables, pf_start, pf_len, scale,
        window=window, layer=layer, sinks=sinks,
    )
    return dec_out, pf_out


@region("attn")
def mixed_prefill_attention(
    q_a: jnp.ndarray,  # [A, La, Hq, D] — speculative verify rows (q_len<=La)
    q_b: jnp.ndarray,  # [B, Lb, Hq, D] — chunked-prefill rows
    k_cache,
    v_cache,
    a_tables: jnp.ndarray,  # [A, CBa]
    a_start: jnp.ndarray,  # [A]
    a_len: jnp.ndarray,  # [A] (0 = inactive row)
    b_tables: jnp.ndarray,  # [B, CBb]
    b_start: jnp.ndarray,  # [B]
    b_len: jnp.ndarray,  # [B]
    scale: float,
    use_ragged: bool | None = None,
    interpret: bool = False,
    window: int = 0,
    layer=None,
):
    """Attention for one fused speculative MIXED step
    (models.llama.mixed_verify_step): TWO prefill-shaped halves — the
    multi-query verify rows [A, S] and the chunked-prefill rows
    [B, Lpad] — against the same paged KV.

    Ragged kernel on: the whole heterogeneous batch flattens into ONE
    Pallas dispatch (seg_lens = A S-segments + B Lpad-segments — a
    verify row is just a ragged row with q_len = k+1, which the kernel
    already serves; docs/KERNELS.md). Otherwise each half runs the exact
    split serving dispatcher (prefill_attention — the program the sync
    verify and split prefill paths use), so composed-step outputs match
    sync+split byte for byte. `interpret` is the ragged-branch CI hook
    only and is deliberately not forwarded to the reference pair, same
    as mixed_attention."""
    A, La = q_a.shape[0], q_a.shape[1]
    B, Lb = q_b.shape[0], q_b.shape[1]
    if ragged_kernel_enabled(
        k_cache, q_a.shape[-1], use_ragged, interpret
    ):
        seg_lens = (La,) * A + (Lb,) * B
        q_flat = jnp.concatenate(
            [
                q_a.reshape(A * La, *q_a.shape[2:]),
                q_b.reshape(B * Lb, *q_b.shape[2:]),
            ],
            axis=0,
        )
        CB = max(a_tables.shape[1], b_tables.shape[1])
        at = jnp.pad(a_tables, ((0, 0), (0, CB - a_tables.shape[1])))
        bt = jnp.pad(b_tables, ((0, 0), (0, CB - b_tables.shape[1])))
        tables = jnp.concatenate([at, bt], axis=0)
        q_len = jnp.concatenate([a_len, b_len]).astype(jnp.int32)
        pos0 = jnp.concatenate([a_start, b_start]).astype(jnp.int32)
        out = ragged_paged_attention(
            q_flat, k_cache, v_cache, tables, q_len, pos0, seg_lens,
            scale, use_kernel=True, interpret=interpret, window=window,
            layer=layer,
        )
        return (
            out[: A * La].reshape(q_a.shape),
            out[A * La:].reshape(q_b.shape),
        )
    return (
        prefill_attention(
            q_a, k_cache, v_cache, a_tables, a_start, a_len, scale,
            window=window, layer=layer,
        ),
        prefill_attention(
            q_b, k_cache, v_cache, b_tables, b_start, b_len, scale,
            window=window, layer=layer,
        ),
    )


def resolved_kernel_report(
    k_cache, q_head_dim: int, ragged_interpret: bool = False,
    shards: int = 1,
) -> dict:
    """The dispatch decisions the serving paths would take RIGHT NOW for
    this cache/geometry — what actually runs, not which env var is set
    (bench.py reports these; ISSUE 9 satellite: `attention_kernel:
    default` told the record nothing). Values name the winning
    implementation; a path whose env hatch forces it off reports the
    fallback with a ` (forced-off)` marker. `shards` > 1 resolves the
    per-shard (shard_map) dispatch of a tp mesh: the report's `shards`
    key is how many kernel launches one engine dispatch fans into —
    asserted (not assumed) by the virtual-mesh differential suite."""
    import os

    # The interpret hook drives only the RAGGED branch on CPU (the
    # decode/prefill serving dispatchers never see it from the engine),
    # so the platform gate for those stays _on_tpu().
    on = _on_tpu()
    eligible = gqa_kernel_eligible(k_cache, q_head_dim, on, shards=shards)

    def resolve(env_name: str, kernel: str, fallback: str) -> str:
        env = os.environ.get(env_name)
        if env == "0":
            return f"{fallback} (forced-off)"
        if env == "1":
            return kernel
        return kernel if eligible else fallback

    dec = resolve("XLLM_PAGED_ATTENTION_KERNEL", "paged", "gather")
    pf = resolve("XLLM_PREFILL_ATTENTION_KERNEL", "flash", "blockwise")
    ragged = (
        "ragged"
        if ragged_kernel_enabled(
            k_cache, q_head_dim, interpret=ragged_interpret, shards=shards
        )
        else (
            "split (forced-off)"
            if os.environ.get("XLLM_RAGGED_ATTENTION_KERNEL") == "0"
            else "split"
        )
    )
    kq = isinstance(k_cache, kvc.PagedKV) and k_cache.quantized
    mq_env = os.environ.get("XLLM_MQ_ATTENTION_KERNEL")
    # The prefill dispatcher's function-wide kill switch covers its mq
    # branch too (prefill_attention requires != "0"), so the report must
    # mirror it — mq never runs with the prefill kernels forced off.
    mq_on = (
        eligible
        and os.environ.get("XLLM_PREFILL_ATTENTION_KERNEL") != "0"
        and (mq_env == "1" if kq else mq_env != "0")
    )
    return {
        "decode": dec,
        "prefill": pf,
        "mixed": ragged,
        "mq": "mq" if mq_on else "blockwise",
        # Kernel launches one engine dispatch fans into: tp under the
        # shard_map tier, 1 on single-device meshes (or with the
        # XLLM_SHARDED_KERNELS=0 escape hatch back to GSPMD).
        "shards": shards,
    }


def resolved_mla_kernel_report(c_cache) -> dict:
    """MLA counterpart of resolved_kernel_report: mirrors the actual
    dispatch decisions of mla_paged_attention / mla_prefill_attention —
    including the _mla_kernel_ok tile/platform gate those dispatchers
    apply — not just the env vars. A mixed step of the family runs the
    decode and the prefill op side by side (no ragged latent kernel)."""
    import os

    ok = _mla_kernel_ok(c_cache, _on_tpu())
    quantized = isinstance(c_cache, kvc.PagedKV) and c_cache.quantized
    dec_env = os.environ.get("XLLM_MLA_ATTENTION_KERNEL")
    pf_env = os.environ.get("XLLM_MLA_PREFILL_KERNEL")
    mq_env = os.environ.get("XLLM_MQ_ATTENTION_KERNEL")
    # mla_paged_attention: on where tile-eligible ("0" is the hatch).
    dec = "mla" if ok and dec_env != "0" else "gather"
    # mla_prefill_attention: default-on for eligible bf16 latents
    # (kernel_ok = ok and not quantized); env == "1" forces, "0" kills.
    pf_ok = ok and not quantized
    if (pf_env != "0") if pf_ok else (pf_env == "1"):
        pf = "mla-flash"
    elif pf_ok and pf_env == "0":
        pf = "blockwise (forced-off)"
    else:
        pf = "blockwise"
    return {
        "decode": dec,
        "prefill": pf,
        "mixed": f"{dec}+{pf}",
        "mq": "mla-mq" if (ok and mq_env == "1") else "blockwise",
        # MLA's latent cache has no KV-head axis to shard — the kernels
        # stay single-launch (docs/SHARDING.md).
        "shards": 1,
    }
