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
import os
import threading
from typing import NamedTuple

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



def _kernel_tile_ok(cache, on: bool) -> bool:
    """Mosaic tile-legality gate for every Pallas kernel path (chip
    findings, round 3): DMA slice dims must be tile MULTIPLES on the
    last two dims. The pool's row width (head_dim D for GQA, where packed
    head_dim<128 layouts carry 128-lane rows and unpacked narrow rows are
    not eligible; the lane-padded latent dim C for MLA) must be a 128
    multiple; BS sits on sublanes of the [BS, lanes] data slice (16
    bf16; int8's stricter bound is subsumed below); int8 additionally
    streams [G, BS] scale tiles with BS on LANES, so quantized caches
    need BS % 128."""
    *_, BS, lanes = kvc.raw(cache).shape
    cq = isinstance(cache, kvc.PagedKV) and cache.quantized
    return (
        on
        and lanes % 128 == 0
        and (BS % 128 == 0 if cq else BS % 16 == 0)
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
        _kernel_tile_ok(cache, _on_tpu() or interpret)
        and (ctx is not None or declared_shard_context() is None)
        and (ctx is None or kvc.raw(cache).shape[-3] % ctx[0].shape[ctx[1]] == 0)
    )
    return ok, ctx


# ------------------------------------------------- which launch runs as what
# ONE function turns what can be observed (the platform, the pool's tile
# geometry, packing and quantization, the declared mesh, the hatches that
# remain) into the route of every attention launch over a pool. The
# dispatchers below take their branch from it and the executor's
# kernel_report() prints it, so the two cannot disagree.

MQ_MAX_ROWS = 8  # verify shapes: at most this many query rows a sequence
# An MLA prefill launch of at least this many rows a chunk makes each
# head's keys and values from the latent blocks (the materialised form);
# below it the absorbed form is the cheaper one. The up-projection of a
# cached position is shared by the rows of the chunk: the two forms cost
# the same at about 170 rows (ops/pallas/mla_prefill.py).
MLA_MATERIALISE_ROWS = 256


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # A backend that fails to initialise raises here: an engine meant for
    # the chip must not be routed to the gather/blockwise reference.
    return jax.devices()[0].platform == "tpu"


def _interpret() -> bool:
    """The seam beside _on_tpu that a CPU test patches to True: every
    attention launch then passes the platform gate and runs its Pallas
    kernel in interpret mode, inside whatever step program traces it."""
    return False


class Routes(NamedTuple):
    """What the attention launches over ONE pool run as (attention_routes)."""

    latent: bool  # an MLA latent pool (else GQA K/V pools)
    decode: bool  # the Pallas decode kernel (else the gather)
    prefill: bool  # the Pallas flash kernel (else the blockwise scan)
    verify: bool  # verify shapes take the multi-query kernel (else as `prefill`)
    shards: int  # kernel launches a dispatch fans into (shard_map over tp)
    interpret: bool  # the kernels run in interpret mode
    forced_off: tuple = ()  # launch kinds a hatch sent to the fallback
    # the prefill launch is the MATERIALISED MLA flash kernel (else, where
    # `prefill`, the absorbed one): decided by the rows of a chunk
    materialised: bool = False

    @property
    def bounded_by_context(self) -> bool:
        """Whether every launch walks a row's context and not its table
        (`nb = cdiv(seq_len, block_size)`): the kernels do, the gather and
        the blockwise scan read every column they are given. Verify
        shapes ride the multi-query kernel or go as `prefill` does."""
        return self.decode and self.prefill

    def report(self) -> dict:
        """The names the records carry (bench rows, the engine's dispatch
        counter, docs/OBSERVABILITY.md): the winning implementation of
        each launch kind, a fallback that a hatch forced marked
        ` (forced-off)`; `mixed` is the pair a fused step launches side by
        side, `mq` what the verify shapes run as; `mla-flash-mat` is the
        materialised form of a latent pool's flash kernel, for the rows
        the routes were asked about."""
        decode, prefill, mq = (
            ("mla", "mla-flash", "mla-mq") if self.latent
            else ("paged", "flash", "mq")
        )
        if not self.decode:
            decode = "gather" + " (forced-off)" * ("decode" in self.forced_off)
        if not self.prefill:
            prefill = "blockwise" + " (forced-off)" * ("prefill" in self.forced_off)
        if not self.verify:
            mq = prefill  # a few rows a sequence: never the materialised form
        if self.materialised:
            prefill = "mla-flash-mat"
        return {
            "decode": decode,
            "prefill": prefill,
            "mixed": f"{decode}+{prefill}",
            "mq": mq,
            "shards": self.shards,
        }


def attention_routes(
    cache,
    q_heads: int = 1,
    q_head_dim: int | None = None,
    *,
    latent: bool = False,
    tp: int = 1,
    use_kernel: bool | None = None,
    interpret: bool = False,
    sinks: bool = False,
    prefill_rows: int = 0,
) -> Routes:
    """THE dispatch decision for the attention launches over `cache` (a
    K pool, or the latent pool with `latent`): which of them run as
    Pallas kernels, per how many shards, in which mode.

    The platform gate is _on_tpu() (or interpret mode: the argument, or
    the _interpret seam). The Mosaic tile gate (_kernel_tile_ok) reads
    the pool's row width and block size; a packed-pair pool (head_dim <
    128) rides the kernels only under XLLM_PACKED_KV_KERNEL=1. On a
    declared tp mesh (`tp` its extent; XLLM_SHARDED_KERNELS=0 escapes to
    GSPMD) a GQA launch fans into one per shard where the query heads
    and the cache heads split evenly; a latent pool has no head axis to
    split. XLLM_PAGED_ATTENTION_KERNEL / XLLM_PREFILL_ATTENTION_KERNEL
    =0/1 force a GQA launch off or on (the second covers the multi-query
    kernel too); XLLM_MQ_ATTENTION_KERNEL=0/1 gates the multi-query
    kernel, which is on for bf16 GQA pools (mq-bf16 validated on a v5e)
    and opt-in for int8 and latent pools, and has no sink logit: a
    launch with `sinks` goes as `prefill`. An int8 latent pool's flash
    kernel is not validated on a chip: it takes the blockwise scan.
    `use_kernel` True / False (a caller's own switch) forces the decode
    and the flash kernel on / off whatever the gates say. A latent
    pool's flash kernel has two forms, and `prefill_rows` (the rows of a
    chunk of the launch asked about: static at trace time, so a step
    program holds one of the two) picks one: the materialised kernel
    from MLA_MATERIALISE_ROWS rows on, the absorbed one below, where the
    verify shapes are."""
    interp = interpret or _interpret()
    on = _on_tpu() or interp
    raw = kvc.raw(cache)
    quantized = isinstance(cache, kvc.PagedKV) and cache.quantized
    ok = _kernel_tile_ok(cache, on)
    mq_env = os.environ.get("XLLM_MQ_ATTENTION_KERNEL")
    shards, forced = 1, ()
    if latent:
        decode, prefill = ok, ok and not quantized
        verify = ok and mq_env == "1"
    else:
        if (
            tp > 1 and sharded_kernels_enabled()
            and q_heads % tp == 0 and raw.shape[-3] % tp == 0
        ):
            shards = tp
        ok = ok and _packed_kernel_allowed(_pack_ratio(cache, q_head_dim))
        envs = {
            "decode": os.environ.get("XLLM_PAGED_ATTENTION_KERNEL"),
            "prefill": os.environ.get("XLLM_PREFILL_ATTENTION_KERNEL"),
        }
        decode, prefill = (
            (env != "0") if ok else (env == "1") for env in envs.values()
        )
        forced = tuple(kind for kind, env in envs.items() if env == "0")
        verify = (
            ok and not sinks and envs["prefill"] != "0"
            and (mq_env == "1" if quantized else mq_env != "0")
        )
    if use_kernel is not None:
        decode = prefill = bool(use_kernel)
        verify, forced = False, ()
    materialised = (
        latent and prefill and not quantized
        and prefill_rows >= MLA_MATERIALISE_ROWS
    )
    return Routes(
        latent, decode, prefill, verify, shards, interp, forced, materialised
    )


def _gqa_routes(q, k_cache, use_kernel, interpret, sinks=None):
    """(routes, ctx) of a GQA dispatcher's launch: the decision for the
    calling thread's declared mesh, and the shard context to launch
    under where it fans out (None: one launch)."""
    ctx = declared_shard_context()
    routes = attention_routes(
        k_cache, q.shape[-2], q.shape[-1],
        tp=ctx[0].shape[ctx[1]] if ctx is not None else 1,
        use_kernel=use_kernel, interpret=interpret, sinks=sinks is not None,
    )
    return routes, ctx if routes.shards > 1 else None


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
    below the window). Which launch runs is attention_routes' decision
    (`use_kernel` forces the flash kernel on or off, `interpret` lets CI
    drive the kernel branch on CPU). Under a shard context (tp>1) each
    kernel launches per-shard via shard_map and the packing trio
    (kernel_io_for) evaluates the per-shard cache geometry inside the
    mapped body. Packed-pair caches (head_dim < 128): queries embed
    block-diagonally into the 128-lane rows; outputs slice back
    (pack_queries docstring)."""
    routes, ctx = _gqa_routes(q, k_cache, use_kernel, interpret, sinks)

    # Speculative-verify shapes (a handful of query rows per sequence):
    # the multi-query decode kernel streams each KV row ONCE like a decode
    # step — the flash-prefill kernel would pad S~4 rows to a 128-row
    # query tile.
    if routes.verify and q.shape[1] <= MQ_MAX_ROWS:
        from xllm_service_tpu.ops.pallas.paged_attention import (
            multiquery_paged_attention_kernel,
        )

        seq_lens = jnp.where(true_len > 0, start_pos + 1, 0)

        def mq_body(qq, kk, vv, bt, sl, lyr):
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                multiquery_paged_attention_kernel(
                    q_packed, kk, vv, bt, sl, scale,
                    interpret=routes.interpret, window=window, layer=lyr,
                ),
                pack, kv_heads,
            )

        return _kernel_call(
            mq_body, ctx, 4, q, k_cache, v_cache, block_tables, seq_lens,
            layer=layer,
        )

    if routes.prefill:
        from xllm_service_tpu.ops.pallas.flash_prefill import (
            flash_prefill_kernel,
        )

        def flash_body(qq, kk, vv, bt, sp, tl, lyr):
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                flash_prefill_kernel(
                    q_packed, kk, vv, bt, sp, tl, scale,
                    interpret=routes.interpret, window=window, layer=lyr,
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
    or one layer's 4-D cache): the Pallas kernel on TPU where the tiles
    are eligible (attention_routes; `use_kernel` forces either way), the
    gather elsewhere. Int8 latent caches ride the kernel too (sub-channel
    scales stream in a separate plane and dequantize in VMEM); `interpret`
    lets CI drive the kernel branch on CPU."""
    routes = attention_routes(
        c_cache, latent=True, use_kernel=use_kernel, interpret=interpret
    )
    if routes.decode:
        from xllm_service_tpu.ops.pallas.mla_attention import (
            mla_attention_kernel,
        )

        return mla_attention_kernel(
            q_lat, c_cache, block_table, seq_lens, scale, kv_rank,
            interpret=routes.interpret, layer=layer,
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
    """Batched MLA chunked-prefill attention in ABSORBED form (a launch
    whose Routes say `materialised`, a chunk of many rows on the chip,
    goes to mla_materialised_prefill_attention instead); Pallas
    flash kernel (ops/pallas/mla_prefill.py) on TPU, vmapped blockwise
    scan elsewhere (attention_routes decides; `use_kernel` forces the
    flash kernel either way, `interpret` drives the kernel branches in
    CI). Int8 latent caches ride both kernels (sub-channel scales stream
    in their own plane, VMEM dequant). Speculative-verify shapes take the
    multi-query MLA decode kernel, which streams each latent row once
    (see the GQA analog in prefill_attention), where it is opted in."""
    routes = attention_routes(
        c_cache, latent=True, use_kernel=use_kernel, interpret=interpret
    )
    if routes.verify and q_lat.shape[1] <= MQ_MAX_ROWS:
        from xllm_service_tpu.ops.pallas.mla_attention import (
            mla_multiquery_attention_kernel,
        )

        seq_lens = jnp.where(true_len > 0, start_pos + 1, 0)
        return mla_multiquery_attention_kernel(
            q_lat, c_cache, block_tables, seq_lens, scale,
            kv_rank, interpret=routes.interpret, layer=layer,
        )
    if routes.prefill:
        from xllm_service_tpu.ops.pallas.mla_prefill import (
            mla_flash_prefill_kernel,
        )

        return mla_flash_prefill_kernel(
            q_lat, c_cache, block_tables, start_pos, true_len,
            scale, kv_rank, interpret=routes.interpret, layer=layer,
        )
    return jax.vmap(
        lambda qi, ti, sp, tl: mla_prefill_blockwise(
            qi, c_cache, ti, sp, tl, scale, kv_rank, layer=layer
        )
    )(q_lat, block_tables, start_pos, true_len)


@region("attn")
def mla_materialised_prefill_attention(
    q: jnp.ndarray,  # [P, Lpad, Hq, dn + dr] — the chunk's query heads, not roped
    q_pe: jnp.ndarray,  # [P, Lpad, Hq, dr] — their rope part, roped
    w_uk: jnp.ndarray,  # [Hq, kv_rank, dn], or the layers' stack [n, Hq, ..]
    w_uv: jnp.ndarray,  # [Hq, kv_rank, dv], or the layers' stack
    c_cache,
    block_tables: jnp.ndarray,  # [P, CB]
    start_pos: jnp.ndarray,  # [P]
    true_len: jnp.ndarray,  # [P]
    scale: float,
    kv_rank: int,
    interpret: bool = False,
    layer=None,  # int32 scalar: c_cache is the STACK [L, N, 1, BS, C]
    w_layer=None,  # int32 scalar: w_uk / w_uv are the layers' stacks
) -> jnp.ndarray:
    """Batched MLA chunked-prefill attention in the MATERIALISED form, for
    a launch whose Routes say `materialised` (attention_routes with the
    chunk's rows as `prefill_rows`): ONE Pallas flash kernel that makes
    each head's keys and values from the latent blocks in VMEM
    (ops/pallas/mla_prefill.py). Takes the query heads as the projection
    wrote them (a head's [q_nope | rope part]: the kernel reads the first
    dn lanes a head and takes the roped part from `q_pe`, so nothing is
    sliced out or re-laid before the launch) and returns the heads'
    VALUE-space outputs [P, Lpad, Hq, dv]: no absorbed q_lat before it
    and no W_UV product after it. The other routes of a chunk are
    mla_prefill_attention's, whose blockwise scan is this kernel's
    oracle."""
    from xllm_service_tpu.ops.pallas.mla_prefill import (
        mla_materialised_prefill_kernel,
    )

    return mla_materialised_prefill_kernel(
        q, q_pe, w_uk, w_uv, c_cache, block_tables, start_pos,
        true_len, scale, kv_rank, interpret=interpret, layer=layer,
        w_layer=w_layer,
    )


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
    attention_routes decides (XLLM_PAGED_ATTENTION_KERNEL=0 forces the
    gather path, =1 the kernel even where the gates decline it, as
    `use_kernel` does for one call). Under a declared shard context
    (set_shard_context; tp>1 meshes) the kernel launches per-shard through
    shard_map — one launch per tp shard over its own head slice — instead
    of degrading to a GSPMD-replicated custom call.

    head_dim < 128 models ride the kernel through the packed-pair cache
    layout (kv_cache.kv_pack_factor: a bare [BS, 64] block slice is below
    one 128-lane Mosaic tile — observed on-chip as a tpu.memref_slice
    verification failure — so P heads pack per 128-lane row and queries
    embed block-diagonally, see pack_queries)."""
    routes, ctx = _gqa_routes(q, k_cache, use_kernel, interpret, sinks)
    if routes.decode:
        from xllm_service_tpu.ops.pallas.paged_attention import (
            paged_attention_kernel,
        )

        def body(qq, kk, vv, bt, sl, lyr):
            # Per-shard packing: kernel_io_for reads the (per-shard,
            # under shard_map) cache geometry.
            pack, kv_heads, q_packed = kernel_io_for(kk, qq)
            return unpack_outputs(
                paged_attention_kernel(
                    q_packed, kk, vv, bt, sl, scale,
                    window=window, interpret=routes.interpret, layer=lyr,
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
    use_kernel: bool | None = None,
    window: int = 0,
    layer=None,
    sinks=None,
):
    """Attention for one MIXED engine step (models.llama.mixed_step):
    decode slots and chunked-prefill rows against the same paged KV, each
    half through its own serving dispatcher — the Pallas decode kernel +
    flash prefill on TPU, gather + blockwise on CPU — so mixed-step
    outputs match the split engine's byte for byte while the rest of the
    step fuses into one dispatch (`use_kernel` forces both halves'
    route, as it does each dispatcher's). The halves may carry different
    context-bucket table widths (the executor buckets each exactly like
    its split program)."""
    dec_out = paged_attention(
        q_dec, k_cache, v_cache, dec_tables, dec_seq_lens, scale,
        use_kernel=use_kernel, window=window, layer=layer, sinks=sinks,
    )
    pf_out = prefill_attention(
        q_pf, k_cache, v_cache, pf_tables, pf_start, pf_len, scale,
        use_kernel=use_kernel, window=window, layer=layer, sinks=sinks,
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
    window: int = 0,
    layer=None,
):
    """Attention for one fused speculative MIXED step
    (models.llama.mixed_verify_step): TWO prefill-shaped halves — the
    multi-query verify rows [A, S] and the chunked-prefill rows
    [B, Lpad] — against the same paged KV, each through the split
    serving dispatcher (prefill_attention — the program the sync verify
    and split prefill paths use), so composed-step outputs match
    sync+split byte for byte."""
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
