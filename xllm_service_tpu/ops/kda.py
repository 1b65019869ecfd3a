"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) as a hybrid
stack's state-layer mixer: a gated delta rule with a decay PER CHANNEL of
the key, in its recurrent and its chunk form, and the pool that holds a
sequence's state beside the paged cache.

After the q, k and v projections, their causal convolution (the hybrid's
own, ops/mamba.py `conv_decode` / `conv_chunk`: the carried convolution
state is the last K-1 rows of the `3 H d` lanes) and the normalisations
(models/granite.py), for one sequence and one head, key and value width d:

    alpha_t = exp(g_t)  in (0, 1)^d       g_t <= 0, per channel of the key
    S <- Diag(alpha_t) S                  S [d, d]: key lane x value lane
    S <- S - beta_t k_t (k_t^T S) + beta_t k_t v_t^T        beta_t in (0, 2)
    o_t = S^T q_t

With `u_t = beta_t (v_t - (k_t^T Diag(alpha_t) S_{t-1}))` the update is
`S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`, which unrolls over a chunk of C
tokens from S_0, with `G_i = sum_{m<=i} g_m` and the DECAYED GRAM of two
row sets `M[x]_ij = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`:

    A   = tril(diag(beta) M[k], -1)        (I + A) U~ = diag(beta) (V - K+ S_0)
    T   = (I + A)^{-1} diag(beta)          K+_i = k_i * exp(G_i)
    U~  = T V - (T K+) S_0
    O   = (q * exp(G)) S_0 + tril(M[q]) U~
    S_C = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U~

**No exponent is positive.** `exp(G_i)` and `exp(G_C - G_j)` are decays
from the chunk's ends. The gram is formed in sub-blocks of 16 tokens
(`_decayed_gram`): a diagonal block takes `exp(G_i - G_j)` per pair and
channel, masked to i >= j before the exponential; a block of row block a
against EARLIER tokens j factors through the row block's entry point
`R_a = G_{16a-1}`: `exp(G_i - R_a) <= 1` and `exp(R_a - G_j) <= 1` because
G falls. A decay that underflows gives 0, the limit.

A token past the chunk's true length has `g = 0` and `beta = 0`: it moves
no state and no earlier token's output. A chunk that starts at position 0
ignores what the slot held, so a freed slot needs no cleaning.

**Layout.** `S [Lk, slots, H, d, d]` float32, key lane on the sublanes
(ops/pallas/kda.py says why): whole (8, 128) tiles at d = 128. The
convolution pool is ops/mamba.py's, `[Lk, slots, (K-1) * 3 H d]`. A
DECODE row's slot is its row index; a prefill chunk names its slot.

Two routes, one result, chosen by ONE predicate (`kernel_eligible`: on
the chip, whole (8, 128) tiles of state): there the decode update is the
Pallas kernel `kda_update_kernel`, in place on the stack the layer scan
carries, and a prefill chunk's whole chunk form is ONE launch a layer,
`kda_chunk_kernel` (`chunk_update`; `chunk_kernel_eligible`: chunks of a
power of two of sub-blocks): it reads the convolution's output, g and beta
token-major as the projections leave them, normalises q and k, forms the
gram, the inverse, U~ and both outputs in VMEM, carries a head's state
across the row's chunks in its output block and writes the slot once;
elsewhere `qkv_heads` and the `jax.numpy` route below
(`chunk_update_heads`, `_chunk_scan`), which is also what the kernel is
tested against. `kda_gram_kernel` (the gram's diagonal sub-blocks alone,
PR 54) is that route's `use_kernel` and no step's any more.
`chunk_update` counts which form a traced layer holds
(`xllm_engine_kda_chunk_kernel_total{form}`).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.obs.startup import TIMELINE
from xllm_service_tpu.ops.mamba import _units
from xllm_service_tpu.ops.pallas.kda import (
    head_tile,
    kda_chunk_kernel,
    kda_gram_kernel,
    kda_update_kernel,
)

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens of one chunk of the chunk form (the published kernels')
L2_EPS = 1e-6  # under the root of q's and k's normalisation
BLOCK = 16  # tokens of one sub-block of the decayed gram


def state_shapes(layers: int, slots: int, heads: int, d_head: int, d_conv: int):
    """(state pool shape, convolution pool shape)."""
    return (
        (layers, slots, heads, d_head, d_head),
        (layers, slots, (d_conv - 1) * 3 * heads * d_head),
    )


def kernel_eligible(S, requested: Optional[bool] = None) -> bool:
    if requested is not None:
        return requested
    from xllm_service_tpu.ops.attention import _on_tpu

    return _on_tpu() and S.shape[-1] % 128 == 0 and S.shape[-2] % 8 == 0


def chunk_kernel_eligible(S, chunk: int, requested: Optional[bool] = None) -> bool:
    """`kernel_eligible`, and chunks the kernel can walk: whole sub-blocks,
    a power of two of them (the inverse joins neighbours)."""
    n = chunk // BLOCK
    return chunk % BLOCK == 0 and n & (n - 1) == 0 and kernel_eligible(S, requested)


def columns(alpha, k, bk, q, tile: int):
    """Four [R, H, d] vectors -> [R, H / tile, d, 4 * tile]: the kernel's
    columns, vector-major on the lanes."""
    R, H, d = alpha.shape
    c = jnp.stack([alpha, k, bk, q], axis=1).reshape(R, 4, H // tile, tile, d)
    return c.transpose(0, 2, 4, 1, 3).reshape(R, H // tile, d, 4 * tile)


# ------------------------------------------------------------ the decode


def decode_update(
    S, layer, active, q, k, v, g, beta,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token into the slot of every active row (slot = row index),
    and its read-out. active [R] bool; q, k (normalised), v [R, H, d] f32;
    g [R, H, d] f32 (<= 0); beta [R, H] f32. Returns (o [R, H, d] f32,
    zero on inactive rows, S'); inactive rows touch no slot."""
    R = q.shape[0]
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    bk = beta[..., None] * k
    if kernel_eligible(S, use_kernel):
        n_live, unit_rows = _units(active)
        S, o = kda_update_kernel(
            S, layer, unit_rows, n_live,
            columns(alpha, k, bk, q, head_tile(S.shape[2])), v, interpret=interpret,
        )
    else:
        pool = jax.lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)
        old = pool[:R].astype(f32)  # [R, H, dk, dv]
        new = alpha[..., None] * old
        u = v - jnp.einsum("rhkv,rhk->rhv", new, k, precision=_HI)
        new = new + bk[..., None] * u[..., None, :]
        o = jnp.einsum("rhkv,rhk->rhv", new, q, precision=_HI)
        keep = jnp.where(active[:, None, None, None], new, old)
        S = jax.lax.dynamic_update_slice(S, keep.astype(S.dtype)[None], (layer, 0, 0, 0, 0))
    return jnp.where(active[:, None, None], o, 0.0), S


# --------------------------------------------------------- the chunk form


def _gram_diagonal_kernel(x, k, G, interpret: bool):
    """`_decayed_gram`'s diagonal sub-blocks [..., n, BLOCK, BLOCK] through
    `kda_gram_kernel`: tokens flattened ahead of the last leading
    dimension (the heads: [P, L, H, d] as the projections leave it when
    the leading ones are the chunks), each row set its own operand."""
    B, sets = BLOCK, x.shape[:x.ndim - k.ndim]
    *outer, H, C, d = k.shape if k.ndim > 2 else (1, *k.shape)
    rows = lambda t: jnp.moveaxis(t.reshape(-1, H, C, d), 1, 2).reshape(-1, H, d)
    xs = x.reshape(-1, *k.shape)
    diag = kda_gram_kernel(
        rows(G), rows(k), [rows(xs[s]) for s in range(xs.shape[0])], block=B, interpret=interpret,
    )  # [S, H, outer x C / B, B, B]
    diag = jnp.moveaxis(diag.reshape(-1, H, math.prod(outer), C // B, B, B), 1, 2)
    return diag.reshape(*sets, *k.shape[:-2], C // B, B, B)


def _decayed_gram(x, k, G, use_kernel: bool = False, interpret: bool = False):
    """M_ij = sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c]) for i >= j, 0
    above the diagonal. k, G [..., C, d], G falling along C; x the same
    or with more leading dimensions (several row sets against one k). No
    exponent taken is positive (the module docstring says how). With
    `use_kernel` the diagonal sub-blocks are `kda_gram_kernel`'s (whole
    sub-blocks of BLOCK tokens only); the rest is the same text."""
    *lead, C, d = x.shape
    B = BLOCK if C % BLOCK == 0 else C
    n = C // B
    blocks = lambda t: t.reshape(*t.shape[:-2], n, B, d)
    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    if use_kernel and B == BLOCK:
        diag = _gram_diagonal_kernel(x, k, G, interpret)
    else:
        pos = jnp.arange(B)
        tri = pos[:, None] >= pos[None, :]
        diff = Gb[..., :, None, :] - Gb[..., None, :, :]  # [.., n, B(i), B(j), d]
        decay = jnp.exp(jnp.where(tri[..., None], jnp.minimum(diff, 0.0), -jnp.inf))
        diag = jnp.sum(xb[..., :, None, :] * (kb[..., None, :, :] * decay), axis=-1)
    rows = []
    for a in range(n):
        parts = []
        if a:
            ref = G[..., a * B - 1:a * B, :]  # the row block's entry point
            xp = xb[..., a, :, :] * jnp.exp(jnp.minimum(Gb[..., a, :, :] - ref, 0.0))
            km = k[..., :a * B, :] * jnp.exp(jnp.minimum(ref - G[..., :a * B, :], 0.0))
            parts.append(jnp.einsum("...id,...jd->...ij", xp, km, precision=_HI))
        parts.append(diag[..., a, :, :])
        if a < n - 1:
            parts.append(jnp.zeros((*lead, B, C - (a + 1) * B), x.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse(A):
    """(I + A)^{-1} for A strictly lower triangular [..., n, n]: the
    diagonal blocks of 16 rows by forward substitution, row by row and all
    blocks at once, then neighbours joined level by level (the inverse of
    [[L11, 0], [A21, L22]] has -I22 A21 I11 below the diagonal)."""
    n = A.shape[-1]
    nb = n // BLOCK if n % BLOCK == 0 else 1
    if nb & (nb - 1):  # the joins pair neighbours: a power of two, or one block
        nb = 1
    b = n // nb
    diag = jnp.stack([A[..., i * b:(i + 1) * b, i * b:(i + 1) * b] for i in range(nb)], axis=-3)

    def row(inv, x):  # row i of the inverse from the rows above it (the rest still I's)
        i, a_i = x
        hot = rows == i
        new = hot.astype(A.dtype) - jnp.einsum("...j,...jk->...k", a_i, inv, precision=_HI)
        return jnp.where(hot[:, None], new[..., None, :], inv), None

    rows = jnp.arange(b)
    inv, _ = jax.lax.scan(
        row, jnp.broadcast_to(jnp.eye(b, dtype=A.dtype), diag.shape),
        (rows[1:], jnp.moveaxis(diag, -2, 0)[1:]),
    )
    level = [inv[..., i, :, :] for i in range(nb)]
    while len(level) > 1:
        b, joined = level[0].shape[-1], []
        for j in range(0, len(level), 2):
            i11, i22, at = level[j], level[j + 1], j * b
            a21 = A[..., at + b:at + 2 * b, at:at + b]
            i21 = -jnp.einsum("...ij,...jk,...kl->...il", i22, a21, i11, precision=_HI)
            top = jnp.concatenate([i11, jnp.zeros_like(i11)], axis=-1)
            joined.append(jnp.concatenate([top, jnp.concatenate([i21, i22], axis=-1)], axis=-2))
        level = joined
    return level[0]


def _chunk_scan(q, k, v, g, beta, s0, chunk: int, use_kernel: bool = False,
                interpret: bool = False):
    """The chunk form over sequences of n chunks from carried states.
    q, k, v, g [P, L, H, d] f32 (L a multiple of `chunk`; a masked token
    has g = 0), beta [P, L, H] (a masked token's is 0), s0 [P, H, d, d].
    Returns (o [P, L, H, d], S_L [P, H, d, d])."""
    Pn, L, H, d = q.shape
    n = L // chunk

    def heads_first(t):  # [P, L, H, ...] -> [n, P, H, chunk, ...]
        t = t.reshape(Pn, n, chunk, H, *t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    q, k, v, g, beta = (heads_first(t) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)  # [n, P, H, C, d]: falling, <= 0
    Mk, Mq = _decayed_gram(jnp.stack([k, q]), k, G, use_kernel, interpret)  # both grams in one pass
    A = jnp.tril(beta[..., None] * Mk, -1)
    T = _unit_lower_inverse(A) * beta[..., None, :]
    U = jnp.einsum("...ij,...jd->...id", T, v, precision=_HI)
    W = jnp.einsum("...ij,...jd->...id", T, k * jnp.exp(G), precision=_HI)
    GC = G[..., -1:, :]
    xs = (U, W, Mq, q * jnp.exp(G), k * jnp.exp(GC - G), jnp.exp(GC[..., 0, :]))

    def step(S, x):
        U, W, Mq, qp, kd, aC = x
        Ut = U - jnp.einsum("phik,phkv->phiv", W, S, precision=_HI)
        o = jnp.einsum("phik,phkv->phiv", qp, S, precision=_HI) \
            + jnp.einsum("phij,phjv->phiv", Mq, Ut, precision=_HI)
        S = aC[..., None] * S + jnp.einsum("phjk,phjv->phkv", kd, Ut, precision=_HI)
        return S, o

    S, o = jax.lax.scan(step, s0, xs)  # o [n, P, H, C, d]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [P, n, C, H, d]
    return o.reshape(Pn, L, H, d), S


def _masked(q, k, v, g, beta, length, chunk: int):
    """float32, tokens past `length` made inert, L padded to whole chunks."""
    f32 = jnp.float32
    L = q.shape[1]
    valid = jnp.arange(L, dtype=jnp.int32)[None, :] < length[:, None]  # [P, L]
    g = jnp.where(valid[..., None, None], g.astype(f32), 0.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    pad = -L % chunk
    out = [t.astype(f32) for t in (q, k, v)] + [g, beta]
    if pad:
        out = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in out]
    return out


def chunk_update_heads(
    S, layer, slots, start, length, q, k, v, g, beta, chunk: int = CHUNK,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One prefill chunk per row against the row's carried state. slots,
    start, length [P] int32 (length 0: a padding row, touches no slot;
    start 0: the slot's old content is ignored); q, k, v, g [P, Lc, H, d],
    beta [P, Lc, H]. Returns (o [P, Lc, H, d] f32, S')."""
    Pn, Lc = q.shape[:2]
    chunk = min(chunk, Lc)
    slots = jnp.clip(slots, 0, S.shape[1] - 1)
    olds = [
        jax.lax.dynamic_slice(S, (layer, slots[p], 0, 0, 0), (1, 1) + S.shape[2:])[0, 0]
        for p in range(Pn)
    ]
    s0 = jnp.stack(olds).astype(jnp.float32)
    s0 = jnp.where((start > 0)[:, None, None, None], s0, 0.0)
    o, sT = _chunk_scan(
        *_masked(q, k, v, g, beta, length, chunk), s0, chunk,
        kernel_eligible(S, use_kernel), interpret,
    )
    new = sT.astype(S.dtype)
    for p in range(Pn):
        row = jnp.where(length[p] > 0, new[p], olds[p])
        S = jax.lax.dynamic_update_slice(S, row[None, None], (layer, slots[p], 0, 0, 0))
    return o[:, :Lc], S


def qkv_heads(c, H: int, d: int):
    """The convolution's output [..., 3 H d] -> q (l2-normalised, scaled
    by d**-0.5), k (l2-normalised), v, each [..., H, d]."""
    q, k, v = (c[..., i * H * d:(i + 1) * H * d].reshape(*c.shape[:-1], H, d) for i in range(3))
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return l2(q) * d ** -0.5, l2(k), v


def chunk_update(
    S, layer, slots, start, length, qkv, g, beta, chunk: int = CHUNK,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One prefill chunk per row against the row's carried state, from the
    convolution's output qkv [P, Lc, 3 H d] (`qkv_heads` says what q, k
    and v are of it), g [P, Lc, H, d] and beta [P, Lc, H]; slots, start,
    length as `chunk_update_heads` has them. On the chip ONE launch,
    `kda_chunk_kernel`, which reads the rows where they lie and normalises
    q and k itself; elsewhere `qkv_heads` and `chunk_update_heads`. Counts
    which of the two a traced layer holds. Returns (o [P, Lc, H, d] f32,
    S')."""
    _, Lc, H, d = g.shape
    chunk = min(chunk, Lc)
    on_kernel = chunk_kernel_eligible(S, chunk, use_kernel)
    TIMELINE.count_kda_chunk_form("kernel" if on_kernel else "xla")  # when a program is traced
    if not on_kernel:
        return chunk_update_heads(S, layer, slots, start, length, *qkv_heads(qkv, H, d), g, beta,
                                  chunk=chunk, use_kernel=False)
    f32 = jnp.float32
    valid = jnp.arange(Lc, dtype=jnp.int32)[None, :] < length[:, None]  # [P, Lc]
    rows = [qkv.astype(f32), g.astype(f32), jnp.where(valid[..., None], beta.astype(f32), 0.0)]
    if -Lc % chunk:
        rows = [jnp.pad(t, ((0, 0), (0, -Lc % chunk)) + ((0, 0),) * (t.ndim - 2)) for t in rows]
    n_live, unit_rows = _units(length > 0)
    o, S = kda_chunk_kernel(
        S, layer, unit_rows, n_live, jnp.clip(slots, 0, S.shape[1] - 1), start, length, *rows,
        chunk=chunk, block=BLOCK, eps=L2_EPS, interpret=interpret,
    )
    return jnp.where((length > 0)[:, None, None, None], o[:, :Lc], 0.0), S


def chunk_form(q, k, v, g, beta, chunk: int = CHUNK, use_kernel: bool = False,
               interpret: bool = False):
    """A whole sequence in chunks from an empty state: q, k, v, g
    [T, H, d], beta [T, H] -> (o [T, H, d] f32, S_T [H, d, d])."""
    T, H, d = q.shape
    chunk = min(chunk, T)
    o, S = _chunk_scan(
        *_masked(*(t[None] for t in (q, k, v, g, beta)), jnp.full((1,), T, jnp.int32), chunk),
        jnp.zeros((1, H, d, d), jnp.float32), chunk, use_kernel, interpret,
    )
    return o[0, :T], S[0]


def recurrent_form(q, k, v, g, beta):
    """The definition, token by token, for one sequence from an empty
    state: same arguments as `chunk_form` -> (o [T, H, d] f32,
    S_T [H, d, d])."""
    H, d = q.shape[1:]

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=_HI))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)

    S, o = jax.lax.scan(
        step, jnp.zeros((H, d, d), jnp.float32),
        tuple(t.astype(jnp.float32) for t in (q, k, v, g, beta)),
    )
    return o, S
