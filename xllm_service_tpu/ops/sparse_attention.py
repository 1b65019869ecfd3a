"""Block-sparse attention whose pages the QUERY chooses (InfLLM-V2,
arXiv:2509.24663; the `minicpm4` layer of MiniCPM-SALA) over the paged
K/V pool: a pool of compressed keys beside K, stage 1 (which blocks a
query group reads) and stage 2 (attention over those blocks alone).

For the query at position p (context p + 1), one KV head and its G query
heads, with blocks of `block` tokens (= the pool's page), compressed keys
`c_j = mean(k[stride j : stride j + kernel])`:

    p + 1 <= dense_len:  causal softmax attention over the whole context
    else:  a_j  = softmax_j(q . c_j * scale) over the VISIBLE j
                  (stride j + kernel - 1 <= p), a head at a time, exact;
           A_j  = sum of a_j over the group's G heads
           s_b  = max(A_j : c_j overlaps block b)         (j in [b cpb - span + 1,
                  b cpb + cpb - 1], cpb = block / stride, span = kernel / stride)
           the first `init_blocks` blocks and the `local_blocks` blocks
           that end at p's own are always read; the best-scoring others
           fill the selection to `topk` blocks
           causal softmax attention over the tokens of the selected blocks

The switch is per QUERY POSITION, so a token's output does not depend on
how its request was cut into chunks (models/granite.py).

**The compressed-key pool** `CK [La, N, Hkv cpb, D]` float32 (a page's keys
of every KV head one whole (8, 128) tile at 2 heads of 4 keys: with the
heads and the keys as dimensions of their own XLA re-laid the whole pool
between the write and the read, twice a step program) rides the
stack's fourth cache slot (the convolution pool's place: this family has
no convolution): block n holds the compressed keys that START in it, under
the same block table as K and V, so it is allocated, freed and preempted
with the page. A key whose tokens span a page boundary (the last of a
block at kernel = 2 stride) lies in the block it starts in and is written
when its last token is (`write_compressed`, from the K rows the step has
just written: the pool's own rows, so a key half made of an earlier
chunk's tokens needs no carried sum). A compressed key not yet whole is
not VISIBLE, and what a block's entry held before (another sequence's) is
never read.

**Stage 2 needs no kernel of its own.** The layers have no positional
signal, so attention over the selected blocks is paged attention over a
shorter VIRTUAL sequence: the selected blocks in ascending order (the
query's own block is the last), at a context of `(topk - 1) block + p %
block + 1`. The pool `[La, N, Hkv, BS, D]` read as `[La, N Hkv, 1, BS, D]`
(the same bytes) makes a KV head a row of the launch, so each of a row's
KV heads brings a table of its own: row (t, h) reads entries `n Hkv + h`.
A row at or under `dense_len` rides the same launch with its own table.
Decode rows and a prefill chunk's selected rows (each a row of the decode
launch, in tiles of ROW_TILE rows: a chunk's rows share no table) take
it; a chunk's rows under `dense_len` take the flash kernel as before.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops.attention import paged_attention

_HI = jax.lax.Precision.HIGHEST
ROW_TILE = 256  # query rows of a prefill chunk that select and attend at once
FORCED = 1e9  # the score of a block that is always read (a sum of G softmaxes is <= G)


class Selection(NamedTuple):
    """The selection's constants (ModelConfig's `sparse_*`), in blocks."""

    block: int  # tokens of a block: the pool's page
    topk: int  # blocks a query group reads past dense_len
    kernel: int  # tokens of a compressed key
    stride: int  # ... and between two
    init_blocks: int
    local_blocks: int
    dense_len: int

    @property
    def per_block(self) -> int:  # compressed keys that start in a block
        return self.block // self.stride

    @property
    def span(self) -> int:  # stride groups of a compressed key
        return self.kernel // self.stride

    @property
    def dense_blocks(self) -> int:  # table columns of a row under dense_len
        return -(-self.dense_len // self.block)


def selection_of(cfg) -> Selection:
    sel = Selection(
        cfg.sparse_block_size, cfg.sparse_topk, cfg.sparse_kernel_size,
        cfg.sparse_kernel_stride, cfg.sparse_init_blocks,
        cfg.sparse_window // max(cfg.sparse_block_size, 1), cfg.sparse_dense_len,
    )
    if (sel.block % sel.stride or sel.kernel % sel.stride or sel.kernel > sel.block
            or sel.init_blocks + sel.local_blocks > sel.topk
            or sel.topk > sel.dense_blocks):
        raise ValueError(
            f"sparse attention: {sel}: the stride divides the kernel and the block, a "
            f"compressed key is at most a block, the forced blocks fit the selection and "
            f"the selection fits under dense_len"
        )
    return sel


def pool_shape(cfg, blocks: int):
    """The compressed-key pool of `blocks` pages."""
    return (cfg.num_sparse_layers, blocks,
            cfg.num_kv_heads * cfg.sparse_keys_per_block, cfg.head_dim)


@region("cache_write")
def write_compressed(CK, K, layer, tables, start, length, width: int, sel: Selection):
    """The compressed keys that the tokens [start, start + length) of each
    row COMPLETE, out of the K rows already in the pool. CK the
    compressed-key pool, K the key pool (both stacks, `layer` the sparse
    layer); tables [P, CB]; start, length [P] (length 0: nothing);
    `width` the most tokens a row writes (static). Returns CK'."""
    k = kvc.raw(K)
    BS, cpb, span = sel.block, sel.per_block, sel.span
    P, CB = tables.shape
    N = CK.shape[1]
    n_pages = -(-(width + sel.kernel - 1) // BS) + 1
    first = jnp.maximum(start - (sel.kernel - 1), 0) // BS  # [P]
    cols = jnp.minimum(first[:, None] + jnp.arange(n_pages, dtype=jnp.int32), CB - 1)
    blk = jnp.take_along_axis(tables, cols, axis=1)
    pages = k[layer, blk].astype(jnp.float32)  # [P, n_pages, Hkv, BS, D]
    Hkv, D = pages.shape[2], pages.shape[-1]
    groups = pages.reshape(P, n_pages, Hkv, cpb, sel.stride, D).sum(axis=4)
    groups = jnp.moveaxis(groups, 2, 1).reshape(P, Hkv, n_pages * cpb, D)
    nj = n_pages * cpb - span + 1
    c = sum(groups[:, :, s:s + nj] for s in range(span)) / sel.kernel  # [P, Hkv, nj, D]
    j = first[:, None] * cpb + jnp.arange(nj, dtype=jnp.int32)  # [P, nj]
    last = j * sel.stride + sel.kernel - 1  # the token that completes key j
    done = (last >= start[:, None]) & (last < (start + length)[:, None])
    dest = jnp.take_along_axis(tables, jnp.minimum(j // cpb, CB - 1), axis=1)
    dest = jnp.where(done, dest, N)  # out of range: dropped
    row = (j % cpb)[..., None] + jnp.arange(Hkv, dtype=jnp.int32) * cpb  # [P, nj, Hkv]
    return CK.at[layer, dest[..., None], row, :].set(
        jnp.moveaxis(c, 1, 2).astype(CK.dtype), mode="drop"
    )


def _compressed_context(CK, layer, tables, per_block: int):
    """CK[layer] through `tables` [..., CB] -> [..., Hkv, CB cpb, D]: the
    compressed keys of a row's pages in order of j."""
    g = CK[layer, tables]  # [..., CB, Hkv cpb, D]
    g = g.reshape(*g.shape[:-2], -1, per_block, g.shape[-1])
    g = jnp.moveaxis(g, -3, -4)  # [..., Hkv, CB, cpb, D]
    return g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2], g.shape[-1])


def block_scores(q, ck, positions, scale: float, sel: Selection):
    """Stage 1's scores. q [T, Hkv, G, D]; ck [T, Hkv, J, D] or [Hkv, J, D]
    (rows that share a table); positions [T]. Returns s_b [T, Hkv, J / cpb]
    float32: the max over the compressed keys that overlap block b of the
    group's summed softmax (0 where none is visible)."""
    f32 = jnp.float32
    J = ck.shape[-2]
    eq = "thgd,thjd->thgj" if ck.ndim == 4 else "thgd,hjd->thgj"
    s = jnp.einsum(eq, q.astype(f32), ck.astype(f32), precision=_HI) * scale
    j = jnp.arange(J, dtype=jnp.int32)
    visible = (j[None, :] * sel.stride + sel.kernel - 1 <= positions[:, None])[:, None, None, :]
    s = jnp.where(visible, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    a = jnp.sum(e / jnp.where(z > 0, z, 1.0), axis=2)  # [T, Hkv, J]
    # a max-pool of width cpb + span - 1 and stride cpb, span - 1 keys of padding in front
    cpb, span = sel.per_block, sel.span
    return jax.lax.reduce_window(
        a, 0.0, jax.lax.max, (1, 1, cpb + span - 1), (1, 1, cpb),
        ((0, 0), (0, 0), (span - 1, 0)),
    )


def ranked_scores(scores, positions, sel: Selection):
    """What the selection ranks: scores [T, Hkv, NB], positions [T] ->
    [T, Hkv, max(NB, topk)] float32: a forced block FORCED, a block past
    the row's own -1, the others their score."""
    if scores.shape[-1] < sel.topk:  # a table narrower than the selection: no row is past dense_len
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, sel.topk - scores.shape[-1])))
    b = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, None, :]
    own = (positions // sel.block)[:, None, None]
    forced = (b < sel.init_blocks) | ((b > own - sel.local_blocks) & (b <= own))
    return jnp.where(forced, FORCED, jnp.where(b > own, -1.0, scores))


def _ordered_keys(x):
    """float32 -> uint32 in the same order. Zeros of either sign and
    subnormals are one key: the chip's compares flush them too."""
    x = jnp.where(jnp.abs(x) < jnp.finfo(jnp.float32).tiny, 0.0, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    i = jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)  # signed order = float order
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def _running_count(mask):
    """Inclusive running count of mask [NB, R] down its columns, int32:
    a triangular matmul of zeros and ones (exact in bfloat16 x bfloat16
    -> float32 up to 2^24), so the MXU does it and no scan stands."""
    n = mask.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    upto = (i[:, None] >= i[None, :]).astype(jnp.bfloat16)  # [b, a]: a <= b
    return jnp.dot(upto, mask.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def top_columns(ranked, topk: int):
    """The `topk` largest columns of each row of ranked [..., NB] float32
    (NB >= topk), ascending, ties to the LOWER index: `lax.top_k` and a
    sort of its indices, bit for bit, without either sort.

    The columns ride the MAJOR axis ([NB, R]: a count over them is vreg
    adds, a running count one matmul). (1) The threshold: the key of the
    topk-th largest, bit by bit from the top, 32 rounds of "how many
    columns are at or above the trial" in a ROLLED loop (unrolled it
    would cost every process its compile). (2) The ties: every column
    above the threshold is in, and of the columns AT it the first
    `topk - above`: a picked column's rank is its running count of
    `above` plus its running count of `equal` capped at that need.
    (3) The compaction: entry r of the table is the number of columns
    whose inclusive rank is <= r (the rank is monotone, so that is the
    index of the r-th picked): one compare-and-count, fused."""
    lead, NB = ranked.shape[:-1], ranked.shape[-1]
    u = _ordered_keys(ranked.reshape(-1, NB)).T  # [NB, R]
    top = jnp.uint32(0x80000000)

    def round_(i, t):
        trial = t | (top >> i.astype(jnp.uint32))
        n = jnp.sum((u >= trial).astype(jnp.int32), axis=0, keepdims=True)
        return jnp.where(n >= topk, trial, t)

    t = jax.lax.fori_loop(0, 32, round_, jnp.zeros((1, u.shape[1]), jnp.uint32))
    above, equal = u > t, u == t
    need = topk - jnp.sum(above.astype(jnp.int32), axis=0, keepdims=True)
    rank = _running_count(above) + jnp.minimum(_running_count(equal), need)  # [NB, R]
    r = jnp.arange(topk, dtype=jnp.int32)[:, None, None]
    table = jnp.sum((rank[None] <= r).astype(jnp.int32), axis=1)  # [topk, R]
    return table.T.reshape(*lead, topk)


def select_blocks(scores, positions, sel: Selection):
    """The `topk` LOGICAL blocks of each (row, KV head), ascending (the
    row's own block last): scores [T, Hkv, NB], positions [T] -> [T, Hkv,
    topk] int32. Only rows past dense_len are meaningful."""
    return top_columns(ranked_scores(scores, positions, sel), sel.topk)


@region("attn_select")
def virtual_tables(q, CK, layer, tables, positions, live, scale: float, sel: Selection,
                   width: int):
    """Stage 1: each row's table and context for the stage-2 launch.
    q [T, Hq, D]; tables [T, CB] (a row's own) or [CB] (rows of one
    chunk); positions [T]; live [T] bool (a dead row reads nothing);
    `width` the virtual table's columns: `topk` where only rows past
    dense_len are live, `dense_blocks` where rows under it ride along.
    Returns (vtables [T, Hkv, width] of the pool read as [N Hkv, 1, ..],
    vlens [T])."""
    T = q.shape[0]
    Hkv = CK.shape[2] // sel.per_block
    qg = q.reshape(T, Hkv, -1, q.shape[-1])
    CB = tables.shape[-1]

    def logical_blocks(cb: int):
        """The selection over the table's first `cb` columns: every row
        of the launch lies in them."""
        ck = _compressed_context(CK, layer, tables[..., :cb], sel.per_block)
        return select_blocks(block_scores(qg, ck, positions, scale, sel), positions, sel)

    # The work of stage 1 follows the table's WIDTH, not the contexts:
    # a launch whose farthest row lies in the first half or quarter of
    # the table scores that part alone (three branches of one program).
    widths = [w for w in (CB // 4, CB // 2) if w >= sel.topk and w * sel.block >= sel.dense_len]
    reach = jnp.max(jnp.where(live, positions, 0)) // sel.block  # the farthest live row's block
    logical = jax.lax.switch(
        sum((reach >= w).astype(jnp.int32) for w in widths),
        [lambda w=w: logical_blocks(w) for w in widths + [CB]],
    )  # [T, Hkv, topk]
    own = tables if tables.ndim == 2 else jnp.broadcast_to(tables, (T, tables.shape[0]))
    picked = jnp.take_along_axis(
        own[:, None, :], jnp.minimum(logical, own.shape[1] - 1), axis=2
    )
    picked = jnp.pad(picked, ((0, 0), (0, 0), (0, width - sel.topk)))
    dense = jnp.pad(own, ((0, 0), (0, max(0, width - own.shape[1]))))[:, None, :width]
    selected = live & (positions + 1 > sel.dense_len)
    vt = jnp.where(selected[:, None, None], picked, dense)
    vt = vt * Hkv + jnp.arange(Hkv, dtype=vt.dtype)[None, :, None]
    vlen = jnp.where(selected, (sel.topk - 1) * sel.block + positions % sel.block + 1,
                     positions + 1)
    return vt.astype(jnp.int32), jnp.where(live, vlen, 0).astype(jnp.int32)


def _by_head(cache):
    """The pool [La, N, Hkv, BS, D] as [La, N Hkv, 1, BS, D]: the same
    bytes, a KV head a block of its own."""
    d = kvc.raw(cache)
    return kvc.PagedKV(d.reshape(d.shape[0], d.shape[1] * d.shape[2], 1, *d.shape[3:]), None)


@region("attn")
def attend_virtual(q, K, V, vtables, vlens, scale: float, layer, use_kernel=None):
    """Stage 2: q [T, Hq, D] over each (row, KV head)'s virtual table
    [T, Hkv, W] at context vlens [T] -> [T, Hq, Dv]. The decode launch,
    a KV head a row."""
    T, Hq, D = q.shape
    Hkv, W = vtables.shape[1:]
    o = paged_attention(
        q.reshape(T * Hkv, Hq // Hkv, D), _by_head(K), _by_head(V),
        vtables.reshape(T * Hkv, W), jnp.repeat(vlens, Hkv), scale,
        use_kernel=use_kernel, layer=layer,
    )
    return o.reshape(T, Hq, o.shape[-1])


def decode_attention(q, K, V, CK, layer, tables, positions, active, scale, sel: Selection,
                     use_kernel=None):
    """Decode rows of a sparse layer: q [R, Hq, D], tables [R, CB],
    positions [R], active [R] -> [R, Hq, Dv]. One launch for the rows
    past dense_len (their selected blocks) and under it (their own)."""
    vt, vlens = virtual_tables(
        q, CK, layer, tables, positions, active, scale, sel, sel.dense_blocks
    )
    return attend_virtual(q, K, V, vt, vlens, scale, layer, use_kernel)


def chunk_selected_attention(q, K, V, CK, layer, table, positions, live, scale,
                             sel: Selection, use_kernel=None):
    """The rows of ONE prefill chunk that lie past dense_len: q [L, Hq, D],
    table [CB], positions [L], live [L] (valid and past dense_len) ->
    [L, Hq, Dv], zeros on the other rows. Tiles of ROW_TILE rows select
    and attend in turn, so stage 1's scores never stand for the whole
    chunk."""
    L, Hq, D = q.shape
    tile = min(ROW_TILE, L)
    pad = -L % tile
    rows = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        -1, tile, *x.shape[1:])

    def one(xs):
        qt, pt, lt = xs
        vt, vlens = virtual_tables(qt, CK, layer, table, pt, lt, scale, sel, sel.topk)
        return attend_virtual(qt, K, V, vt, vlens, scale, layer, use_kernel)

    o = jax.lax.map(one, (rows(q), rows(positions), rows(live)))
    return o.reshape(-1, Hq, o.shape[-1])[:L]
