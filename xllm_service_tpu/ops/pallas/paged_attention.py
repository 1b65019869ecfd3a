"""Pallas TPU paged-attention decode kernel.

The engine's hottest op (SURVEY.md §7 hard part #1; the reference's CUDA
analog lives in the absent engine submodule). One query token per running
sequence attends to that sequence's paged KV context.

Design (flash-decode; the DMA schedule is the kernel, PR 40):
  * grid = (steps, Hkv / HF), both axes "arbitrary": one program per LIVE
    sequence (the row axis walks a scalar-prefetch list of the step's
    live rows under a dynamic bound, `_visits`: a slot that holds no
    sequence costs no grid step)
    and HF KV heads (two where the heads pair up, else one; never more:
    an 8x head-unrolled body stalls the Mosaic compiler). The heads'
    matmul -> reduce -> exp -> matmul chains are independent and hide each
    other's latencies, and a block's rows for both heads come in ONE
    descriptor ([L, N, :, BS, D] is contiguous over the heads). The K/V
    caches stay in HBM (`pl.ANY`); the kernel streams a sequence's blocks
    through a 2-slot VMEM buffer with `make_async_copy`.
  * each inner iteration processes a CHUNK of `C` consecutive block-table
    entries as one [C*BS, D] tile a head -> a single [Gp, C*BS] score
    matmul. An iteration costs about 0.4 us of issue, wait and reduce
    latency whatever its width (chip readings, docs/KERNELS.md), so C is
    8 for a plain pool and 4 where the tile is dequantized or a window
    masks most of it, and never wider than the table.
  * LIVE BLOCKS ONLY. The block table, sequence lengths and `next_live`
    ride in scalar-prefetch SMEM. A chunk starts and awaits a DMA only
    for the table entries in [first in-window block, cdiv(context, BS)),
    one loop over the same bounds for both (`for_live_blocks`; a slot is
    [C, HF, BS, D], so the block of a chunk rides an untiled dim and needs
    no unrolling): a semaphore that is signalled and not consumed would
    satisfy a later wait early. The garbage block, table tails and blocks
    below a window never move.
  * THE PIPELINE CROSSES GRID STEPS. While a step computes its last chunk
    it starts the first chunk of the next LIVE step (the row's next heads,
    else row `next_live[r]`, a reversed running minimum over seq_lens made
    in the wrapper, so dead rows cost no search) into the other slot; that
    step begins with a wait on DMAs that have been in flight for a whole
    chunk's compute. A 2-word SMEM scratch carries the hand-over: the slot
    the next live step starts in, and whether its first chunk is already
    in flight (0 for the first live step of a launch, which fetches for
    itself). The last live step prefetches nothing, so nothing outlives a
    launch. This is why the axes are "arbitrary": the steps must run in
    order on one core (a v5e has one TensorCore; a tp mesh launches once
    a shard through shard_map).
  * STALE ROWS. Columns of a chunk that were not fetched hold whatever the
    slot held. Their scores are masked to NEG_INF by column index (p is
    exactly 0), but 0 * NaN is NaN in p @ v: the V slots (and an int8
    pool's V scale slots) are ZEROED ONCE at a launch's first grid step,
    after which an unfetched row holds zeros or an earlier live block's
    finite rows. Zeroing once costs 0.1 us a launch; selecting V rows to
    zero would cost a [C*BS, D] select a chunk.
  * the q and o tiles of RB rows (all their heads) are ONE VMEM block:
    fetched once, written back once, so a grid step moves nothing but
    its own K and V. Dead rows are not visited and read exact zeros all
    the same: a block's first row always is, and its step zeroes the
    whole o block before the live steps write their rows.
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
    next_live_ref,    # [R]      SMEM — next row after r with seq_len > 0, R if none
    visit_ref,        # [R]      SMEM — the row of each step of the grid's row
    #                   axis (index maps too): see _visits
    # inputs
    q_ref,            # [RB, Hkv, Gp, D] VMEM: the tiles of RB rows, fetched
    #                   once for their RB * Hkv / HF grid steps
    k_hbm,            # [L, N, Hkv, BS, D] HBM (pl.ANY) — bf16 or int8
    v_hbm,            # [L, N, Hkv, BS, Dv] HBM (pl.ANY): Dv <= D lanes
    *rest,            # has_sink: sink_ref [Hkv, Gp, 128] f32 VMEM (a head's
    #                 #   sink logit on every lane), fetched once; then
    #                 # quantized: ks_hbm, vs_hbm [L, N, Hkv, G, BS] f32, then
    # output
    #   o_ref         # [RB, Hkv, Gp, Dv] VMEM, written back once a row block
    # scratch
    #   k_buf, v_buf  # [2, C, HF, BS, D | Dv] VMEM (cache dtype): the block
    #                 #   of a chunk rides an untiled dim, so one loop serves them
    #   sems          # [2, 2, C] DMA semaphores
    #   handover      # [2] SMEM int32: (slot of the next live step's first
    #                 #   chunk, 1 if that chunk is already in flight)
    #   (quantized)   ks_buf, vs_buf [2, C, HF, G, BS] f32 + ssems [2, 2, C]
    block_size: int,
    chunk: int,
    scale: float,
    quantized: bool,
    table_blocks: int,
    s_rows: int = 1,
    gp: int = 0,
    scale_groups: int = 8,
    window: int = 0,
    has_sink: bool = False,
):
    sink_ref = None
    if has_sink:
        sink_ref, *rest = rest
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, sems, handover,
         ks_buf, vs_buf, ssems) = rest
    else:
        o_ref, k_buf, v_buf, sems, handover = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None
    step = pl.program_id(0)
    hb = pl.program_id(1)
    r = visit_ref[step]
    rows, head_blocks = seq_lens_ref.shape[0], pl.num_programs(1)
    fold = k_buf.shape[2]  # HF: the KV heads of one grid step
    lyr = layer_ref[0]
    span = chunk * block_size

    def walk(seq_len):
        """(first chunk, chunk bound, first live block, live block bound)
        of a row: the blocks [b_lo, nb) hold every position some query of
        the row's grid steps can see, and nothing else is fetched."""
        # Multi-query (speculative verify): query row s attends to context
        # seq_len + s, so the walk covers the LAST row's context; inactive
        # slots (seq_len = 0) have no block. Clamp to the table's true
        # width: near max_seq_len the caller may have sized the table for
        # fewer than S extra rows (true_len < S) — rows past that bound
        # are garbage the sampler never emits, and walking beyond the
        # table would read out-of-bounds SMEM block ids.
        nb = jnp.where(
            seq_len == 0, 0,
            jnp.minimum(pl.cdiv(seq_len + s_rows - 1, block_size), table_blocks),
        )
        # Sliding-window attention: the walk starts at the first block
        # holding any in-window position (earliest window start across the
        # s_rows queries is seq_len - window) — blocks wholly below it never
        # stream, so SWA decode bandwidth is O(window), not O(context).
        b_lo = (
            jnp.maximum(seq_len - window, 0) // block_size if window > 0 else 0
        )
        return b_lo // chunk, pl.cdiv(nb, chunk), b_lo, nb

    def dmas(slot, c_idx, blk, h0):
        # One descriptor takes a block's rows for all HF heads of the step:
        # [L, N, :, BS, D] is contiguous over the heads (layer, blk and the
        # heads ride untiled dims). An int8 pool adds the heads' [G, BS]
        # scale tiles.
        planes = [(k_hbm, k_buf, sems, 0), (v_hbm, v_buf, sems, 1)]
        if quantized:
            planes += [(ks_hbm, ks_buf, ssems, 0), (vs_hbm, vs_buf, ssems, 1)]
        return [
            mosaic.async_copy(
                mosaic.checked_at(src, lyr, blk, pl.ds(h0, fold)),
                mosaic.checked_at(buf, slot, c_idx),
                sem.at[slot, kv, c_idx],
            )
            for src, buf, sem, kv in planes
        ]

    def for_live_blocks(c, b_lo, nb, fn):
        """fn(c_idx, table column) for the chunk's entries in [b_lo, nb).
        A chunk is STARTED and AWAITED through these same bounds, so every
        semaphore that is signalled is consumed: one left over would satisfy
        a later grid step's (or launch's) wait early."""
        first = c * chunk
        lo = jnp.maximum(first, b_lo) if window > 0 else first
        hi = jnp.minimum(first + chunk, nb)

        def each(j, _):
            fn(j - first, j)

        jax.lax.fori_loop(lo, hi, each, None)

    def start_chunk(slot, c, row, h0, b_lo, nb):
        def start(c_idx, j):
            for d in dmas(slot, c_idx, block_table_ref[row, j], h0):
                d.start()

        for_live_blocks(c, b_lo, nb, start)

    def wait_chunk(slot, c, b_lo, nb):
        def wait(c_idx, j):
            # A wait reads its descriptor's size and semaphore alone.
            for d in dmas(slot, c_idx, 0, 0):
                d.wait()

        for_live_blocks(c, b_lo, nb, wait)

    @pl.when((step == 0) & (hb == 0))
    def _launch_begins():
        handover[0] = 0
        handover[1] = 0
        # Rows of a slot that no DMA of this launch has written yet are
        # masked to p = 0, and 0 * NaN is NaN in p @ v: give V (and its
        # scales) finite rows once. After that a row that was not fetched
        # holds zeros or an earlier live block's rows.
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            vs_buf[...] = jnp.zeros_like(vs_buf)

    seq_len = seq_lens_ref[r]
    r_in = jax.lax.rem(r, q_ref.shape[0])  # this row within its q / o block
    h0 = hb * fold

    # Inactive decode slots carry seq_len = 0 and emit zeros, yet the grid
    # does not visit them: the first row of a block of rows is always
    # visited, live or not, and its step zeroes the whole o block before
    # the live steps write their rows over that. (A dead first row does
    # this alone: it starts nothing and touches neither the slots nor the
    # hand-over.)
    @pl.when((r_in == 0) & (hb == 0))
    def _block_begins():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(seq_len > 0)
    def _live():
        c_lo, nc, b_lo, nb = walk(seq_len)
        slot0 = handover[0]

        # The first live step of a launch fetches for itself; every other
        # one finds its first chunk started by the live step before it.
        @pl.when(handover[1] == 0)
        def _first():
            start_chunk(slot0, c_lo, r, h0, b_lo, nb)

        # The live grid step after this one: the row's next KV heads, else
        # the next live row's first (the dead rows between are skipped).
        wraps = hb + 1 == head_blocks
        r_nx = jnp.where(wraps, next_live_ref[r], r)
        h0_nx = jnp.where(wraps, 0, h0 + fold)
        has_nx = r_nx < rows
        c_lo_nx, _, b_lo_nx, nb_nx = walk(
            seq_lens_ref[jnp.minimum(r_nx, rows - 1)]
        )

        # [HF, Gp, D], model dtype (bf16 on TPU)
        qs = [q_ref[r_in, h0 + i] for i in range(fold)]

        def body(c, carry):
            slot = jax.lax.rem(slot0 + c - c_lo, 2)

            # Behind this chunk's compute goes this step's next chunk or,
            # with the last chunk, the first chunk of the next live step.
            more = c + 1 < nc
            pick = lambda mine, theirs: jnp.where(more, mine, theirs)

            @pl.when(more | has_nx)
            def _prefetch():
                start_chunk(
                    1 - slot, pick(c + 1, c_lo_nx), pick(r, r_nx),
                    pick(h0, h0_nx), pick(b_lo, b_lo_nx), pick(nb, nb_nx),
                )

            wait_chunk(slot, c, b_lo, nb)
            shape = (qs[0].shape[0], span)
            col = c * span + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            if s_rows == 1:
                valid = col < seq_len
                if window > 0:
                    valid &= col >= seq_len - window
            else:
                # q tile rows are [S, Gp] flattened: row // gp is the query's
                # offset from the first fed position (causal within the step).
                row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // gp
                valid = col < seq_len + row
                if window > 0:
                    valid &= col >= seq_len + row - window
            # The heads' chains are independent: written side by side so
            # that one's MXU and reduce latencies hide behind the other's.
            return tuple(
                _online_softmax_step(i, slot, valid, *carry[i])
                for i in range(fold)
            )

        def _online_softmax_step(i, slot, valid, m_prev, l_prev, acc):
            k_tile = k_buf[slot, :, i].reshape(span, -1)  # [C*BS, D]
            if quantized:
                k_tile = dequant_tile(
                    k_tile, ks_buf[slot, :, i], chunk, block_size, scale_groups
                )
            scores = (
                jax.lax.dot_general(
                    qs[i], k_tile,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [Gp, C*BS] f32
            # Columns of blocks that were not fetched hold stale K rows:
            # never valid, so they leave here as NEG_INF whatever they scored.
            scores = jnp.where(valid, scores, NEG_INF)

            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                v_tile = dequant_tile(
                    v_buf[slot, :, i].reshape(span, -1), vs_buf[slot, :, i],
                    chunk, block_size, scale_groups,
                )
                pv = jnp.dot(
                    p.astype(jnp.bfloat16), v_tile,
                    preferred_element_type=jnp.float32,
                )  # [Gp, D] f32
            else:
                pv = jnp.dot(
                    p.astype(k_buf.dtype), v_buf[slot, :, i].reshape(span, -1),
                    preferred_element_type=jnp.float32,
                )
            return m_new, l_new, acc * alpha + pv

        Gp, Dv = o_ref.shape[2], o_ref.shape[3]
        a0 = jnp.zeros((Gp, Dv), jnp.float32)
        if has_sink:
            # The sink is the softmax's first logit and has no value row:
            # the running maximum starts at it and the running sum at 1.
            l0 = jnp.ones((Gp, 1), jnp.float32)
            init = tuple((sink_ref[h0 + i][:, :1], l0, a0) for i in range(fold))
        else:
            m0 = jnp.full((Gp, 1), NEG_INF, jnp.float32)
            init = ((m0, jnp.zeros((Gp, 1), jnp.float32), a0),) * fold
        out = jax.lax.fori_loop(c_lo, nc, body, init)

        handover[0] = jax.lax.rem(slot0 + nc - c_lo, 2)
        handover[1] = has_nx.astype(jnp.int32)
        # a live row has seq_len >= 1, so l > 0 for every real query row
        for i, (_, l, acc) in enumerate(out):
            o_ref[r_in, h0 + i] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _row_block(rows: int, row_bytes: int) -> int:
    """Rows a q / o block: the largest divisor of `rows` within 512 KB."""
    cap = max(1, min(rows, 512 * 1024 // row_bytes))
    return next(rb for rb in range(cap, 0, -1) if rows % rb == 0)


def _next_live(seq_lens):
    """next_live[r]: the first row after r with seq_len > 0, R if none (a
    reversed running minimum): the kernel's successor without a search."""
    R = seq_lens.shape[0]
    ids = jnp.where(seq_lens > 0, jnp.arange(R, dtype=jnp.int32), R)
    after = jax.lax.cummin(ids, reverse=True)[1:]
    return jnp.concatenate([after, jnp.full((1,), R, jnp.int32)])


def _visits(seq_lens, row_block: int):
    """(steps, visit): the rows the grid's row axis walks, in order, and
    how many: the live rows, and the FIRST row of every block of
    `row_block` rows whether it lives or not, whose step zeroes the
    block's output (so a block with no live row has its zeros, and there
    is always a step). Past `steps` the list repeats its last row, so an
    index map that looks ahead finds a block that is there.

    A stable partition by one sort, as the state kernels' unit order is
    made (ops/mamba.py `_units`). In a step program's compiled text XLA
    moves the sort out of the layer scan and keeps the making of the
    scalar `steps`, whatever it is made of, in the scan's body: so the
    count is one reduce over a mask that is elementwise in `seq_lens`
    (tests/test_tpu_compile.py holds the body to that)."""
    R = seq_lens.shape[0]
    rows = jnp.arange(R, dtype=jnp.int32)
    walked = (seq_lens > 0) | (rows % row_block == 0)
    steps = jnp.sum(walked, dtype=jnp.int32)
    order = jnp.argsort(~walked, stable=True).astype(jnp.int32)
    return steps, order[jnp.minimum(rows, steps - 1)]


def _launch(name, qr, k_cache, v_cache, layer, block_table, seq_lens, *,
            scale, chunk, window, interpret, s_rows, gp, sinks=None):
    """One `pallas_call` of `_decode_kernel` over q tiles [R, Hkv, T, D]
    (T = s_rows * gp query rows a KV head); returns [R, Hkv, T, Dv], Dv
    the value pool's lanes. `sinks` [Hkv, T] f32: a logit more a query
    row in the softmax's denominator. A launch with a window carries a
    name of its own in the trace ("window_" + name)."""
    quantized = k_cache.quantized
    k_data, v_data = k_cache.data, v_cache.data
    R, Hkv, T, D = qr.shape
    Dv = v_data.shape[-1]
    if window > 0:
        name = "window_" + name
    BS = k_data.shape[3]
    # KV heads a grid step: two where the heads pair up. Their chains of
    # matmul, reduce and exp are independent and hide each other's
    # latencies, and a block's rows come in one descriptor for both. Never
    # more: an 8x head-unrolled body stalls the Mosaic compiler.
    HF = 2 if Hkv % 2 == 0 else 1
    MB = block_table.shape[1]
    if chunk is None:
        # A chunk pays about 0.4 us of issue, wait and reduce latency
        # whatever its width, so a plain pool takes the table in chunks of
        # 8 blocks; dequantizing or window-masking a wider tile costs more
        # than the iterations it saves (docs/KERNELS.md has the readings).
        chunk = 4 if quantized or window > 0 else 8
    C = max(1, min(chunk, MB))
    MBp = _round_up(MB, C)
    bt = block_table.astype(jnp.int32)
    if MBp != MB:
        # Chunk-tail entries are never fetched (the walk stops at MB);
        # they only keep the table's SMEM reads in bounds.
        bt = jnp.pad(bt, ((0, 0), (0, MBp - MB)))
    seq_lens = seq_lens.astype(jnp.int32)

    # Pin the caches to HBM explicitly: under pl.ANY the compiler may place
    # a small cache in VMEM, where the [BS, D] per-block slice is illegal
    # for D < 128 (lane-padded tiling); HBM DMA slices are contiguous.
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    # The q and o tiles of RB rows ride ONE block: a block whose index does
    # not change between grid steps is neither fetched nor written back, so
    # a grid step costs no DMA of the block pipeline's, only its own K and V.
    RB = _row_block(R, Hkv * T * D * qr.dtype.itemsize)
    # The row axis of the grid walks the step's live rows (a dynamic
    # bound): a slot that holds no sequence costs no grid step.
    steps, visit = _visits(seq_lens, RB)
    if RB == R:
        block_of = lambda i, h, *pre: (0, 0, 0, 0)
    else:
        block_of = lambda i, h, *pre: (pre[-1][i] // RB, 0, 0, 0)
    tile = pl.BlockSpec((RB, Hkv, T, D), block_of)
    o_tile = pl.BlockSpec((RB, Hkv, T, Dv), block_of)
    in_specs = [tile, hbm, hbm]
    inputs = [bt, seq_lens, layer, _next_live(seq_lens), visit, qr, k_data,
              v_data]
    if sinks is not None:
        in_specs.append(pl.BlockSpec((Hkv, T, 128), lambda i, h, *_: (0, 0, 0)))
        inputs.append(jnp.broadcast_to(
            sinks.astype(jnp.float32)[:, :, None], (Hkv, T, 128)
        ))
    scratch = [
        pltpu.VMEM((2, C, HF, BS, D), k_data.dtype),
        pltpu.VMEM((2, C, HF, BS, Dv), v_data.dtype),
        pltpu.SemaphoreType.DMA((2, 2, C)),
        pltpu.SMEM((2,), jnp.int32),
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
            pltpu.VMEM((2, C, HF, SG, BS), jnp.float32),
            pltpu.VMEM((2, C, HF, SG, BS), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, C)),
        ]
        # Per-block scale tile is [G, BS] f32: 4*G bytes per row.
        kv_bytes_per_row += 4 * SG

    kernel = functools.partial(
        _decode_kernel, block_size=BS, chunk=C, scale=scale,
        quantized=quantized, table_blocks=MB, s_rows=s_rows, gp=gp,
        scale_groups=SG, window=window, has_sink=sinks is not None,
    )
    return pl.pallas_call(
        kernel,
        name=name,  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps, Hkv // HF),
            in_specs=in_specs,
            out_specs=o_tile,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((R, Hkv, T, Dv), qr.dtype),
        compiler_params=pltpu.CompilerParams(
            # The DMA pipeline crosses grid steps (slot parity and the
            # chunk in flight ride SMEM from one step to the next), so the
            # steps run in order on one core. A v5e has one TensorCore; a
            # tp mesh launches once a shard through shard_map.
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * Hkv * T * (D + Dv) * MB * BS,  # qk + pv
            bytes_accessed=(
                R * Hkv * T * D * 4
                + R * MB * BS * Hkv * kv_bytes_per_row * (D + Dv) // D
            ),
            transcendentals=R * Hkv * T * MB * BS,
        ),
        interpret=interpret,
    )(*inputs)


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
    chunk: int | None = None,  # blocks a chunk; None: by pool and window
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
    sinks=None,                # [Hq] f32: a sink logit a head, or None
) -> jnp.ndarray:
    """Returns [R, Hq, Dv], Dv the value pool's lanes (D where the pools
    are alike)."""
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    R, Hq, D = q.shape
    Hkv = k_cache.data.shape[2]
    G = Hq // Hkv
    Gp = _round_up(G, 8)

    qr = q.reshape(R, Hkv, G, D)
    if Gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    if sinks is not None:
        sinks = jnp.pad(sinks.reshape(Hkv, G), ((0, 0), (0, Gp - G)))
    out = _launch(
        "paged_attention_kernel", qr, k_cache, v_cache, layer, block_table,
        seq_lens, scale=scale, chunk=chunk, window=window,
        interpret=interpret, s_rows=1, gp=Gp, sinks=sinks,
    )
    return out[:, :, :G, :].reshape(R, Hq, out.shape[-1])


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
    chunk: int | None = None,  # blocks a chunk; None: by pool and window
    window: int = 0,
    layer=None,                # int32 scalar when the caches are stacks
) -> jnp.ndarray:
    """Speculative-verify attention: the decode kernel with S query rows
    per sequence. Same HBM traffic as one decode step (each KV row streams
    once), S times the MXU work — the shape speculative decoding wants.
    The S*G query heads of one KV head ride one [S*Gp, D] tile; causal
    masking within the step is by tile-row // Gp. Returns [R, S, Hq, D]."""
    k_cache, v_cache, layer = stack_operands(k_cache, v_cache, layer)
    R, S, Hq, D = q.shape
    Hkv = k_cache.data.shape[2]
    G = Hq // Hkv
    Gp = _round_up(G, 8)

    # [R, S, Hkv, G, D] -> [R, Hkv, S, Gp, D] -> [R, Hkv, S*Gp, D]
    qr = jnp.swapaxes(q.reshape(R, S, Hkv, G, D), 1, 2)
    if Gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    out = _launch(
        "multiquery_paged_attention_kernel", qr.reshape(R, Hkv, S * Gp, D),
        k_cache, v_cache, layer, block_table, seq_lens, scale=scale,
        chunk=chunk, window=window, interpret=interpret, s_rows=S, gp=Gp,
    )
    # [R, Hkv, S*Gp, D] -> [R, Hkv, S, Gp, D] -> [R, S, Hq, D]
    Dv = out.shape[-1]
    out = out.reshape(R, Hkv, S, Gp, Dv)[:, :, :, :G, :]
    return jnp.swapaxes(out, 1, 2).reshape(R, S, Hq, Dv)
