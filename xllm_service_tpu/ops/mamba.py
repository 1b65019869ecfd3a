"""Mamba-2 (state-space duality, arXiv:2405.21060) as the hybrid family's
state-layer mixer: the causal convolution, the selective scan in its
recurrent and its chunk form, and the two pools that hold a sequence's
state beside the paged cache.

After the input projection and the split `[z | xBC | dt]` (models/granite.py),
for one sequence, H heads of P lanes, G groups of B and C, state width N:

    xBC'_t = silu(sum_{j<K} w_j * xBC_{t-K+1+j} + b)     depthwise, causal;
             the carried CONVOLUTION STATE is the last K-1 rows of xBC
    [x | B | C] = xBC'          x [H, P], B, C [G, N]; head h reads group h // (H/G)
    dt_h = softplus(dt_h + dt_bias_h)    a_h = exp(A_h dt_h),  A_h = -exp(A_log_h)
    S_h <- a_h S_h + dt_h x_h B^T        the SSM STATE, [P, N] a head
    y_h  = S_h C + D_h x_h

and the same thing for a chunk of Lc tokens at once, with
`l_i = sum_{k<=i} A dt_k` (`chunk_update`):

    Y   = ((C B^T) * exp(l_i - l_j) * [i >= j]) (dt * X)  +  exp(l_i) * C_i S_0
    S_T = exp(l_T) S_0 + sum_j exp(l_T - l_j) dt_j x_j B_j^T

A token past the chunk's true length has `dt = 0`: it moves neither the
state nor an earlier token's output. A chunk that starts at position 0
ignores what the slot held (state and convolution rows alike), so a freed
slot needs no cleaning.

**Layout.** `S [Lm, slots, H/k, N, k*P]` float32: `k = 128 // P` heads
side by side on the lanes (two at P = 64; one where P does not divide 128
or k does not divide H), N on the sublanes (ops/pallas/mamba.py says why).
Pool entry `[hp, n, j*P + p]` is `S_h[p, n]` of head `h = hp*k + j`.
The convolution pool is `[Lm, slots, (K-1)*conv_dim]` float32: a slot's
K-1 rows side by side on the lanes, oldest first, so that a layer's plane
`[slots, (K-1)*conv_dim]` is whole (8, 128) tiles (with the rows as a
3-long dimension of their own XLA re-tiled the whole pool on the way into
and out of every step program: 1 M cycles a step, compiled for a v5e).
A DECODE row's slot is its row index (the engine gives a sequence
one of the R running rows for its life, before its first chunk); a
prefill chunk names its slot.

Two routes, one result: on the chip the decode update is the Pallas kernel
`mamba_update_kernel`, in place on the stack the layer scan carries;
elsewhere the `jax.numpy` route below (the CPU, tests, other shapes). The
chunk form is `jax.numpy` on both. The kernel's layout rule
(`kernel_eligible`): whole (8, 128) float32 tiles (lanes a multiple of
128, N of 8) and no lane row with heads of two B/C groups ((H / G) % k
== 0), with 8 lane rows or more a group (or one group); any number of
groups.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.ops.pallas.mamba import head_tile, mamba_update_kernel

_HI = jax.lax.Precision.HIGHEST


def pack_factor(heads: int, d_head: int) -> int:
    k = 128 // d_head if d_head and 128 % d_head == 0 else 1
    return k if k >= 1 and heads % k == 0 else 1


def state_shapes(layers: int, slots: int, heads: int, d_head: int, d_state: int,
                 d_conv: int, conv_dim: int):
    """(SSM pool shape, convolution pool shape)."""
    k = pack_factor(heads, d_head)
    return (
        (layers, slots, heads // k, d_state, k * d_head),
        (layers, slots, (d_conv - 1) * conv_dim),
    )


def to_pool(s: jnp.ndarray, k: int) -> jnp.ndarray:
    """[..., H, P, N] -> [..., H/k, N, k*P]."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, H // k, k, P, N)
    s = jnp.moveaxis(s, -1, -3)  # [..., H/k, N, k, P]
    return s.reshape(*lead, H // k, N, k * P)


def from_pool(s: jnp.ndarray, k: int) -> jnp.ndarray:
    """[..., H/k, N, k*P] -> [..., H, P, N]."""
    *lead, HP, N, lanes = s.shape
    s = s.reshape(*lead, HP, N, k, lanes // k)
    s = jnp.moveaxis(s, -3, -1)  # [..., H/k, k, P, N]
    return s.reshape(*lead, HP * k, lanes // k, N)


def kernel_shape_ok(S, groups: int) -> bool:
    """What `mamba_update_kernel` needs of a pool `S [.., H/k, N, k*P]`
    with `groups` groups of B and C: whole (8, 128) float32 tiles, lane
    rows that divide over the groups (a lane row of k heads then reads
    ONE group's B and C), and a head tile inside a group that Mosaic
    takes (`head_tile`: 8 lane rows or more a group, or one group and
    all of its rows)."""
    HP, N, lanes = S.shape[-3:]
    return (lanes % 128 == 0 and N % 8 == 0 and HP % groups == 0
            and head_tile(HP // groups, N * lanes * 4, groups) > 0)


def kernel_eligible(S, groups: int, requested: Optional[bool] = None) -> bool:
    """The decode update of pool `S` takes the kernel: on the chip, at a
    shape the kernel takes (`kernel_shape_ok`); `requested` (tests, the
    controls) overrides both."""
    if requested is not None:
        return requested
    from xllm_service_tpu.ops.attention import _on_tpu

    return _on_tpu() and kernel_shape_ok(S, groups)


# ------------------------------------------------------------ convolution


def conv_decode(conv, layer, active, xbc, w, b):
    """One token a row through the causal convolution. conv
    [Lm, slots, (K-1)*Cd] (slot = row), xbc [R, Cd] f32, w [K, Cd],
    b [Cd]. Returns (silu(conv out) [R, Cd] f32, conv')."""
    R, Cd = xbc.shape
    f32 = jnp.float32
    old = jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)[:R].astype(f32)
    ext = jnp.concatenate([old, xbc.astype(f32)], axis=1)  # [R, K*Cd]
    out = jnp.sum(ext.reshape(R, -1, Cd) * w.astype(f32), axis=1) + b
    new = jnp.where(active[:, None], ext[:, Cd:], old)
    conv = jax.lax.dynamic_update_slice(conv, new.astype(conv.dtype)[None], (layer, 0, 0))
    return jax.nn.silu(out), conv


def _conv_rows(ext, w, b, Lc: int):
    """ext [K-1+Lc, Cd] -> silu(conv) [Lc, Cd]: K shifted adds."""
    K = w.shape[0]
    wf = w.astype(jnp.float32)
    out = sum(ext[j:j + Lc] * wf[j] for j in range(K))
    return jax.nn.silu(out + b)


def conv_chunk(conv, layer, slots, start, length, xbc, w, b):
    """One chunk a row. slots, start, length [P] (length 0: a padding
    row, touches no slot; start 0: the slot's old rows are ignored),
    xbc [P, Lc, Cd] f32. Returns (silu(conv out) [P, Lc, Cd], conv')."""
    P, Lc, Cd = xbc.shape
    K1 = conv.shape[2] // Cd
    slots = jnp.clip(slots, 0, conv.shape[1] - 1)
    outs = []
    for p in range(P):
        old = jax.lax.dynamic_slice(
            conv, (layer, slots[p], 0), (1, 1, K1 * Cd)
        ).reshape(K1, Cd).astype(jnp.float32)
        ext = jnp.concatenate([jnp.where(start[p] > 0, old, 0.0), xbc[p]], axis=0)
        outs.append(_conv_rows(ext, w, b, Lc))
        # the last K-1 VALID rows; with length 0 that is the old rows
        new = jax.lax.dynamic_slice_in_dim(ext, length[p], K1, axis=0)
        new = jnp.where(length[p] > 0, new, old)
        conv = jax.lax.dynamic_update_slice(
            conv, new.astype(conv.dtype).reshape(1, 1, K1 * Cd), (layer, slots[p], 0)
        )
    return jnp.stack(outs), conv


def conv_dense(xbc, w, b):
    """A whole sequence from an empty state: xbc [T, Cd] -> [T, Cd]."""
    K = w.shape[0]
    ext = jnp.pad(xbc.astype(jnp.float32), ((K - 1, 0), (0, 0)))
    return _conv_rows(ext, w, b, xbc.shape[0])


# ------------------------------------------------------------- the scan


def _grouped(x, G: int):
    """[..., H, P] -> [..., G, H/G, P]."""
    *lead, H, P = x.shape
    return x.reshape(*lead, G, H // G, P)


def _units(live):
    """Kernel unit order: live rows first; a dead unit repeats the last
    live one. Returns (n_live, unit_rows)."""
    n = live.shape[0]
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(n, dtype=jnp.int32), jnp.maximum(n_live - 1, 0))]
    return n_live, rows


def decode_update(
    S, layer, active, x, dt, A, B, C, D,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token into the slot of every active row (slot = row index),
    and its read-out. active [R] bool, x [R, H, P] f32, dt [R, H] f32
    (after softplus), A [H] (negative), B, C [R, G, N] f32, D [H].
    Returns (y [R, H, P] f32, zero on inactive rows, S'); inactive rows
    touch no slot."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    k = S.shape[-1] // P
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    a = jnp.exp(dt * A.astype(f32))  # [R, H]
    dtx = dt[..., None] * x  # [R, H, P]

    if kernel_eligible(S, G, use_kernel):
        n_live, unit_rows = _units(active)
        lanes = S.shape[-1]
        if G == 1:  # one plane a row
            along = lambda t: jnp.broadcast_to(t[:, 0, :, None], (R, N, lanes))
        else:  # a plane a group; a head tile's block spec picks its own
            along = lambda t: jnp.broadcast_to(t[..., None], (R, G, N, lanes))
        S, y = mamba_update_kernel(
            S, layer, unit_rows, n_live,
            jnp.repeat(a, P, axis=-1).reshape(R, H // k, lanes),
            dtx.reshape(R, H // k, lanes),
            along(B), along(C),
            interpret=interpret,
        )
        y = y.reshape(R, H, P)
    else:
        pool = jax.lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)
        old = from_pool(pool[:R].astype(f32), k)  # [R, H, P, N]
        Bh = jnp.repeat(B, H // G, axis=1)  # [R, H, N]
        Ch = jnp.repeat(C, H // G, axis=1)
        new = a[..., None, None] * old + dtx[..., None] * Bh[:, :, None, :]
        y = jnp.einsum("rhpn,rhn->rhp", new, Ch, precision=_HI)
        keep = jnp.where(active[:, None, None, None], new, old)
        S = jax.lax.dynamic_update_slice(
            S, to_pool(keep, k).astype(S.dtype)[None], (layer, 0, 0, 0, 0)
        )
    y = y + D.astype(f32)[:, None] * x
    return jnp.where(active[:, None, None], y, 0.0), S


def _chunk_terms(x, dt, A, B, C, length):
    """What the chunk form needs of one batch of chunks: the masked
    steps, the cumulative log decay, and the intra-chunk output.
    x [P, Lc, H, Pd], dt [P, Lc, H], B, C [P, Lc, G, N]."""
    Pn, Lc, H, _ = x.shape
    G = B.shape[2]
    f32 = jnp.float32
    valid = jnp.arange(Lc, dtype=jnp.int32)[None, :] < length[:, None]  # [P, Lc]
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    l = jnp.cumsum(dt * A.astype(f32), axis=1)  # [P, Lc, H], <= 0, falling
    xdt = x.astype(f32) * dt[..., None]
    cb = jnp.einsum("pign,pjgn->pgij", C.astype(f32), B.astype(f32), precision=_HI)
    diff = (l[:, :, None, :] - l[:, None, :, :]).transpose(0, 3, 1, 2)  # [P, H, i, j]
    pos = jnp.arange(Lc)
    tri = (pos[:, None] >= pos[None, :])[None, None]
    decay = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    m = decay.transpose(0, 2, 3, 1).reshape(Pn, Lc, Lc, G, H // G)
    m = m * cb.transpose(0, 2, 3, 1)[..., None]
    y = jnp.einsum("pijgk,pjgkd->pigkd", m, _grouped(xdt, G), precision=_HI)
    return dt, l, xdt, y.reshape(x.shape)


def chunk_update(
    S, layer, slots, start, length, x, dt, A, B, C, D,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One prefill chunk per row against the row's carried state. slots,
    start, length [P] int32 (length 0: a padding row, touches no slot;
    start 0: the slot's old content is ignored), x [P, Lc, H, Pd],
    dt [P, Lc, H] (after softplus), A [H], B, C [P, Lc, G, N], D [H].
    Returns (y [P, Lc, H, Pd] f32, S')."""
    Pn, Lc, H, Pd = x.shape
    G = B.shape[2]
    k = S.shape[-1] // Pd
    f32 = jnp.float32
    slots = jnp.clip(slots, 0, S.shape[1] - 1)
    dt, l, xdt, y = _chunk_terms(x, dt, A, B, C, length)
    olds = [
        jax.lax.dynamic_slice(S, (layer, slots[p], 0, 0, 0), (1, 1) + S.shape[2:])[0, 0]
        for p in range(Pn)
    ]
    s0 = from_pool(jnp.stack(olds).astype(f32), k)  # [P, H, Pd, N]
    s0 = jnp.where((start > 0)[:, None, None, None], s0, 0.0)
    s0g = s0.reshape(Pn, G, H // G, Pd, -1)
    y_inter = jnp.einsum("pign,pgkdn->pigkd", C.astype(f32), s0g, precision=_HI)
    y = y + jnp.exp(l)[..., None] * y_inter.reshape(x.shape)
    lT = l[:, -1]  # [P, H]
    w = jnp.exp(lT[:, None] - l)  # [P, Lc, H]; a masked token's xdt is 0
    upd = jnp.einsum(
        "pjgkd,pjgn->pgkdn", _grouped(xdt * w[..., None], G), B.astype(f32),
        precision=_HI,
    )
    sT = jnp.exp(lT)[..., None, None] * s0 + upd.reshape(s0.shape)
    new = to_pool(sT, k).astype(S.dtype)
    for p in range(Pn):
        row = jnp.where(length[p] > 0, new[p], olds[p])
        S = jax.lax.dynamic_update_slice(S, row[None, None], (layer, slots[p], 0, 0, 0))
    y = y + D.astype(f32)[:, None] * x.astype(f32)
    return y, S


def recurrent_form(x, dt, A, B, C, D):
    """The definition, token by token, for one sequence from an empty
    state: x [T, H, P], dt [T, H] (after softplus), B, C [T, G, N] ->
    (y [T, H, P] f32, S_T [H, P, N])."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    f32 = jnp.float32

    def step(S, t):
        xt, dtt, Bt, Ct = t
        Bh, Ch = jnp.repeat(Bt, H // G, axis=0), jnp.repeat(Ct, H // G, axis=0)
        S = jnp.exp(dtt * A)[:, None, None] * S + (dtt[:, None] * xt)[..., None] * Bh[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, Ch, precision=_HI) + D[:, None] * xt

    S, y = jax.lax.scan(
        step, jnp.zeros((H, P, N), f32),
        tuple(t.astype(f32) for t in (x, dt, B, C)),
    )
    return y, S


def chunk_form(x, dt, A, B, C, D):
    """A whole sequence as ONE chunk from an empty state (the dense
    forward's mixer): same arguments as `recurrent_form`, y only."""
    T = x.shape[0]
    _, _, _, y = _chunk_terms(
        x[None], dt[None], A, B[None], C[None], jnp.full((1,), T, jnp.int32)
    )
    return y[0] + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
