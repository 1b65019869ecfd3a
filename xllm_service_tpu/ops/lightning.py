"""Lightning linear attention (Lightning Attention-2, arXiv:2401.04658; the
MiniMax-01 / MiniCPM-SALA layer) as a state-layer mixer of the hybrid
stack: a decayed outer-product state a head, in its recurrent and its
chunked form, and the pool that holds a sequence's state beside the paged
cache.

For one sequence and one head of width d, after QK-norm and rotary
(models/granite.py), with a CONSTANT decay `lambda` in (0, 1):

    S_t = lambda S_{t-1} + k_t^T v_t            the STATE, [d, d] float32
    o_t = q_t S_t                               (the caller scales q by d**-0.5)

and the same thing for C tokens at once from a carried state (`chunk_update`,
which walks a prefill chunk in sub-chunks of SUB tokens):

    O   = ((Q K^T) * D) V + (lambda^(i+1) Q_i) S_0,   D_ij = lambda^(i-j) [i >= j]
    S_C = lambda^C S_0 + sum_j lambda^(C-1-j) k_j^T v_j

`D` is a constant of the head (no cumulative sum, no difference of logs:
every exponent is a whole number of steps, none positive), so nothing
overflows and a fast head's far pairs underflow to exact zeros. A token
past the chunk's true length moves nothing: its key is zeroed and the
state decays over the valid tokens alone. A chunk that starts at position
0 ignores what the slot held, so a freed slot needs no cleaning.

**The decay** is not a weight: `lambda = exp(-s_h (1 - l / (L - 1) + 1e-5))`
with the ALiBi-style slope `s_h = 2^(-8 (h + 1) / H)` of head h of H and
`l` the layer's PUBLISHED index of L published layers (`log_decay`).

**Layout.** `S [Ls, slots, H, d, d]` float32: the key dimension on the
sublanes, the value dimension on the lanes, so a head's plane is whole
(8, 128) tiles at d = 128 and the read-out is a sum over sublanes. A
DECODE row's slot is its row index; a prefill chunk names its slot (as
ops/mamba.py). There is no convolution and so no second slot pool.

Two routes, one result: on the chip the decode update is the Pallas
kernel `lightning_update_kernel` (ops/pallas/lightning.py), in place on
the stack the layer scan carries; elsewhere the `jax.numpy` route below.
The chunked form is `jax.numpy` on both.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
SUB = 256  # tokens of one sub-chunk of the chunked form


def log_decay(heads: int, layer_ids, published_layers: int) -> np.ndarray:
    """log lambda [len(layer_ids), heads] float32, every entry < 0."""
    slope = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads)
    layer = 1.0 - np.asarray(layer_ids, np.float64) / max(published_layers - 1, 1) + 1e-5
    return (-slope[None, :] * layer[:, None]).astype(np.float32)


def state_shape(layers: int, slots: int, heads: int, d_head: int):
    return (layers, slots, heads, d_head, d_head)


def kernel_shape_ok(S) -> bool:
    """What `lightning_update_kernel` needs of a pool: whole (8, 128)
    float32 tiles a head."""
    return S.shape[-1] % 128 == 0 and S.shape[-2] % 8 == 0


def kernel_eligible(S, requested: Optional[bool] = None) -> bool:
    if requested is not None:
        return requested
    from xllm_service_tpu.ops.attention import _on_tpu

    return _on_tpu() and kernel_shape_ok(S)


def decode_update(
    S, layer, active, q, k, v, log_lam,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token into the slot of every active row (slot = row index), and
    its read-out. active [R] bool; q, k, v [R, H, d]; log_lam [H].
    Returns (o [R, H, d] f32, zero on inactive rows, S'); inactive rows
    touch no slot."""
    R = q.shape[0]
    f32 = jnp.float32
    q, k, v = (t.astype(f32) for t in (q, k, v))
    lam = jnp.exp(log_lam.astype(f32))
    if kernel_eligible(S, use_kernel):
        from xllm_service_tpu.ops.mamba import _units
        from xllm_service_tpu.ops.pallas.lightning import lightning_update_kernel

        n_live, unit_rows = _units(active)
        S, o = lightning_update_kernel(
            S, layer, unit_rows, n_live, lam, q, k, v, interpret=interpret
        )
    else:
        old = jax.lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)[:R].astype(f32)
        new = lam[None, :, None, None] * old + k[..., :, None] * v[..., None, :]
        o = jnp.einsum("rhk,rhkv->rhv", q, new, precision=_HI)
        keep = jnp.where(active[:, None, None, None], new, old)
        S = jax.lax.dynamic_update_slice(S, keep.astype(S.dtype)[None], (layer, 0, 0, 0, 0))
    return jnp.where(active[:, None, None], o, 0.0), S


def _sub_chunk(S, q, k, v, n, log_lam):
    """One sub-chunk of C tokens from the carried state S [H, d, d]: q, k,
    v [C, H, d] f32, n valid tokens (the rest move nothing) -> (o, S')."""
    C = q.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(C, dtype=jnp.int32)
    k = jnp.where((pos < n)[:, None, None], k, 0.0)
    steps = (pos[:, None] - pos[None, :]).astype(f32)  # i - j
    decay = jnp.where(
        steps >= 0, jnp.exp(log_lam[:, None, None] * jnp.maximum(steps, 0.0)), 0.0
    )  # [H, C, C]
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision=_HI) * decay
    o = jnp.einsum("hij,jhd->ihd", scores, v, precision=_HI)
    carry = jnp.exp(log_lam[None, :] * (pos[:, None] + 1).astype(f32))  # lambda^(i+1) [C, H]
    o = o + carry[..., None] * jnp.einsum("ihk,hkv->ihv", q, S, precision=_HI)
    left = jnp.maximum(n - 1 - pos, 0).astype(f32)  # steps from token j to the last valid one
    w = jnp.exp(log_lam[None, :] * left[:, None])  # [C, H]; an invalid token's key is zero
    S = jnp.exp(log_lam * n.astype(f32))[:, None, None] * S + jnp.einsum(
        "jhk,jhv->hkv", k * w[..., None], v, precision=_HI
    )
    return o, S


def chunk_update(
    S, layer, slots, start, length, q, k, v, log_lam, sub: int = SUB,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One prefill chunk per row against the row's carried state. slots,
    start, length [P] int32 (length 0: a padding row, touches no slot;
    start 0: the slot's old content is ignored); q, k, v [P, Lc, H, d];
    log_lam [H]. Returns (o [P, Lc, H, d] f32, S')."""
    Pn, Lc, H, d = q.shape
    f32 = jnp.float32
    C = min(sub, Lc)
    pad = -Lc % C
    slots = jnp.clip(slots, 0, S.shape[1] - 1)
    log_lam = log_lam.astype(f32)
    outs = []
    for p in range(Pn):
        old = jax.lax.dynamic_slice(S, (layer, slots[p], 0, 0, 0), (1, 1) + S.shape[2:])[0, 0]
        s0 = jnp.where(start[p] > 0, old.astype(f32), 0.0)
        qkv = [jnp.pad(t[p].astype(f32), ((0, pad), (0, 0), (0, 0))).reshape(-1, C, H, d)
               for t in (q, k, v)]
        first = jnp.arange(qkv[0].shape[0], dtype=jnp.int32) * C

        def body(s, xs):
            qc, kc, vc, c0 = xs
            o, s = _sub_chunk(s, qc, kc, vc, jnp.clip(length[p] - c0, 0, C), log_lam)
            return s, o

        sT, o = jax.lax.scan(body, s0, (*qkv, first))
        outs.append(o.reshape(-1, H, d)[:Lc])
        row = jnp.where(length[p] > 0, sT.astype(S.dtype), old)
        S = jax.lax.dynamic_update_slice(S, row[None, None], (layer, slots[p], 0, 0, 0))
    return jnp.stack(outs), S


def recurrent_form(q, k, v, log_lam):
    """The definition, token by token, for one sequence from an empty
    state: q, k, v [T, H, d], log_lam [H] -> (o [T, H, d] f32, S_T)."""
    f32 = jnp.float32
    lam = jnp.exp(log_lam.astype(f32))[:, None, None]

    def step(S, t):
        qt, kt, vt = t
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=_HI)

    H, d = q.shape[1:]
    S, o = jax.lax.scan(
        step, jnp.zeros((H, d, d), f32), tuple(t.astype(f32) for t in (q, k, v))
    )
    return o, S
