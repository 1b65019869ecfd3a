"""Power retention (arXiv:2507.04239), degree 2: the recurrent sequence
state of the Brumby family, as a pool of fixed slots beside the paged cache.

Per KV head, with `phi: R^d -> R^D`, `D = d(d+1)/2`,
`phi(x)_(a<=b) = x_a x_b (sqrt 2 if a<b else 1) / sqrt d`, so that
`phi(q).phi(k) = (q.k / sqrt d)^2` exactly:

    S_t = exp(gamma_t) S_{t-1} + phi(k_t) v_t^T        [D, d]
    z_t = exp(gamma_t) z_{t-1} + phi(k_t)              [D]
    y_t = phi(q_t)^T S_t / (phi(q_t).z_t + EPS)

and the same thing in attention form, with `G_t = sum_{m<=t} gamma_m`:
`a_tj = exp(G_t - G_j) (q_t.k_j / sqrt d)^2` for `j <= t`,
`y_t = sum_j a_tj v_j / (sum_j a_tj + EPS)` (`attention_form`: the dense
oracle; the intra-chunk part of a prefill chunk).

**Layout.** The D products are stored by circular offset: feature row
`r` in `0..d/2`, lane `c` in `0..d-1` holds `w_r x_c x_{(c+r) mod d}`,
`w_0 = w_{d/2} = 1/sqrt d`, `w_r = sqrt(2/d)` between (row d/2 holds each
of its pairs twice at weight 1: the same inner product). That is
`(d/2 + 1) d` stored for `d(d+1)/2` true features (8320 for 8256 at
d = 128), every row a whole 128-lane vector made by one lane rotation.
The pool is `S [L, slots, Hkv, T, tR, d(v), d(c)]` and, as an array of
its own so that the state's minor dimension stays d lanes, the normaliser
`z [L, slots, Hkv, T, tR, d]` (`T * tR = d/2 + 1`; `tR` is the kernels'
tile). float32 (the executor's `state_dtype`; the functions here follow
the pool's dtype, which benchmarks/tests/control_brumby.py lowers for its
bfloat16 control).

A slot belongs to one sequence for its life (runtime/block_manager.py
`StateSlotManager`); a chunk that starts at position 0 ignores what the
slot held, so a freed slot needs no cleaning.

Two routes, one result: on the chip with d = 128 the Pallas kernels of
ops/pallas/retention.py, in place on the stack the layer scan carries;
elsewhere the `jax.numpy` route below (the CPU, tests, any other d).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops.pallas.retention import (
    retention_chunk_kernel,
    retention_update_kernel,
)

_HI = jax.lax.Precision.HIGHEST
EPS = 1e-6  # added to the summed weights


def feature_rows(d: int) -> int:
    return d // 2 + 1


def tile_rows(d: int) -> int:
    """tR: the largest divisor of d/2 + 1 that is at most 16."""
    n = feature_rows(d)
    return max(t for t in range(1, 17) if n % t == 0)


def state_shapes(num_layers: int, slots: int, kv_heads: int, d: int):
    """(S shape, z shape) of the pool."""
    t_r = tile_rows(d)
    lead = (num_layers, slots, kv_heads, feature_rows(d) // t_r, t_r)
    return lead + (d, d), lead + (d,)


def state_bytes(num_layers: int, slots: int, kv_heads: int, d: int, itemsize: int = 4) -> int:
    s, z = state_shapes(num_layers, slots, kv_heads, d)
    return (int(np.prod(s)) + int(np.prod(z))) * itemsize


def _weights(d: int) -> np.ndarray:
    w = np.full((feature_rows(d),), np.sqrt(2.0 / d), np.float32)
    w[0] = w[-1] = d ** -0.5
    return w


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """x [..., d] -> [..., d/2 + 1, d] float32, the layout above."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    idx = (np.arange(d)[None, :] + np.arange(feature_rows(d))[:, None]) % d
    return x[..., None, :] * x[..., idx] * _weights(d)[:, None]


def use_kernels(d: int, requested: Optional[bool] = None) -> bool:
    if requested is not None:
        return requested
    from xllm_service_tpu.ops.attention import _on_tpu

    return _on_tpu() and d == 128


def _layer_rows(a, layer, n_tail):
    """a [L, NS, Hkv, T, tR, ...] -> layer `layer` as [NS, Hkv, NR, ...]."""
    al = jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    return al.reshape(al.shape[:2] + (-1,) + al.shape[-n_tail:])


def _put_rows(a, layer, idx, new):
    """Rows `idx` (out of range = dropped) of layer `layer` <- new."""
    al = jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    al = al.at[idx].set(new.reshape((-1,) + al.shape[1:]).astype(a.dtype), mode="drop")
    return jax.lax.dynamic_update_index_in_dim(a, al, layer, 0)


def _units(live, slots):
    """Kernel unit order: live rows first; a dead unit repeats the last
    live one. Returns (n_live, unit_slots, unit_rows)."""
    n = live.shape[0]
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(n, dtype=jnp.int32), jnp.maximum(n_live - 1, 0))]
    return n_live, slots[rows], rows


@region("state_mixer")
def decode_update(
    S, z, layer, slots, active, q, k, v, gamma,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token into the slot of every active row, and its read-out.
    slots [R] int32, active [R] bool, q [R, Hq, d], k, v [R, Hkv, d],
    gamma [R, Hkv] float32 (log decay). Returns (y [R, Hq, d] float32,
    zero on inactive rows, S', z'); inactive rows touch no slot."""
    R, Hq, d = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    n_slots = S.shape[1]
    f32 = jnp.float32
    slots = jnp.clip(slots, 0, n_slots - 1)
    decay = jnp.exp(gamma.astype(f32))
    qf, kf, vf = q.astype(f32).reshape(R, Hkv, g, d), k.astype(f32), v.astype(f32)

    if use_kernels(d, use_kernel):
        n_live, unit_slots, unit_rows = _units(active, slots)
        q8 = jnp.pad(qf, ((0, 0), (0, 0), (0, 8 - g), (0, 0)))
        S, z, y16, den16 = retention_update_kernel(
            S, z, layer, unit_slots, unit_rows, n_live,
            jnp.concatenate([q8, q8], axis=2),
            jnp.broadcast_to(kf[:, :, None, :], (R, Hkv, 8, d)),
            jnp.broadcast_to(vf[..., :, None], (R, Hkv, d, d)),
            jnp.broadcast_to(decay[..., None, None], (R, Hkv, 1, d)),
            interpret=interpret,
        )
        num = y16[:, :, :g] + y16[:, :, 8:8 + g]  # [R, Hkv, g, d]
        den = den16[:, :, :g].sum(-1)
    else:
        put = jnp.where(active, slots, n_slots)
        phik, phiq = phi(kf), phi(qf)  # [R, Hkv, NR, d], [R, Hkv, g, NR, d]
        z_new = decay[..., None, None] * _layer_rows(z, layer, 1)[slots].astype(f32) + phik
        z = _put_rows(z, layer, put, z_new)
        den = jnp.einsum("rhgnc,rhnc->rhg", phiq, z_new, precision=_HI)
        s_new = (
            decay[..., None, None, None] * _layer_rows(S, layer, 2)[slots].astype(f32)
            + vf[..., None, :, None] * phik[..., None, :]
        )  # [R, Hkv, NR, d(v), d(c)]
        S = _put_rows(S, layer, put, s_new)
        num = jnp.einsum("rhgnc,rhnvc->rhgv", phiq, s_new, precision=_HI)
    y = num / (den[..., None] + EPS)
    y = jnp.where(active[:, None, None, None], y, 0.0)
    return y.reshape(R, Hq, d), S, z


def _chunk_terms(q, k, v, gamma, start, length):
    """What both routes of `chunk_update` share: float32 views, the decay
    terms and the intra-chunk part in attention form."""
    P, Lc, Hq, d = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    f32 = jnp.float32
    qf = q.astype(f32).reshape(P, Lc, Hkv, g, d)
    kf, vf = k.astype(f32), v.astype(f32)
    valid = jnp.arange(Lc, dtype=jnp.int32)[None, :] < length[:, None]  # [P, Lc]
    carry = (start > 0).astype(f32)  # a chunk at position 0 starts clean
    G = jnp.cumsum(jnp.where(valid[..., None], gamma.astype(f32), 0.0), axis=1)
    GL = G[:, -1]  # [P, Hkv]
    b = jnp.exp(G) * carry[:, None, None]  # [P, Lc, Hkv]
    bL = jnp.exp(GL) * carry[:, None]
    w = jnp.exp(GL[:, None] - G) * valid[..., None]  # [P, Lc, Hkv]
    s = jnp.einsum("pthgd,pjhd->phgtj", qf, kf, precision=_HI) ** 2 / d
    diff = (G[:, :, None] - G[:, None, :]).transpose(0, 3, 1, 2)  # [P, Hkv, t, j]
    pos = jnp.arange(Lc)
    mask = (pos[:, None] >= pos[None, :])[None, None] & valid[:, None, None, :]
    a = s * jnp.where(mask, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)[:, :, None]
    y_intra = jnp.einsum("phgtj,pjhd->pthgd", a, vf, precision=_HI)
    den_intra = a.sum(-1).transpose(0, 3, 1, 2)  # [P, Lc, Hkv, g]
    return qf, kf, vf, b, bL, w, y_intra, den_intra


@region("state_mixer")
def chunk_update(
    S, z, layer, slots, start, length, q, k, v, gamma,
    use_kernel: Optional[bool] = None, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One prefill chunk per row against the row's carried state.
    slots, start, length [P] int32 (length 0: a padding row, touches no
    slot; start 0: the slot's old content is ignored), q [P, Lc, Hq, d],
    k, v [P, Lc, Hkv, d], gamma [P, Lc, Hkv]. Returns (y [P, Lc, Hq, d]
    float32, S', z')."""
    P, Lc, Hq, d = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    n_slots = S.shape[1]
    f32 = jnp.float32
    slots = jnp.clip(slots, 0, n_slots - 1)
    live = length > 0
    qf, kf, vf, b, bL, w, y_intra, den_intra = _chunk_terms(q, k, v, gamma, start, length)

    if use_kernels(d, use_kernel):
        n_live, unit_slots, unit_rows = _units(live, slots)
        qm = qf.transpose(0, 2, 1, 3, 4).reshape(P, Hkv, Lc * g, d)
        bm = jnp.repeat(b.transpose(0, 2, 1), g, axis=-1)[..., None]  # [P, Hkv, M, 1]
        wt = w.transpose(0, 2, 1)  # [P, Hkv, Lc]
        S, z, y_m, den_m = retention_chunk_kernel(
            S, z, layer, unit_slots, unit_rows, n_live,
            qm, qm * bm, kf.transpose(0, 2, 1, 3),
            (vf * w[..., None]).transpose(0, 2, 3, 1),
            jnp.pad(wt[:, :, None, :], ((0, 0), (0, 0), (0, 7), (0, 0))),
            jnp.broadcast_to(bL[..., None, None], (P, Hkv, 1, d)),
            interpret=interpret,
        )
        y_inter = y_m.reshape(P, Hkv, Lc, g, d).transpose(0, 2, 1, 3, 4)
        den_inter = den_m.sum(-1).reshape(P, Hkv, Lc, g).transpose(0, 2, 1, 3)
        dead = jnp.logical_not(live)[:, None, None, None]
        y_inter = jnp.where(dead[..., None], 0.0, y_inter)  # dead rows: not written
        den_inter = jnp.where(dead, 0.0, den_inter)
    else:
        put = jnp.where(live, slots, n_slots)
        s_old = _layer_rows(S, layer, 2)[slots].astype(f32)  # [P, Hkv, NR, d, d]
        z_old = _layer_rows(z, layer, 1)[slots].astype(f32)
        phiq, phik = phi(qf), phi(kf)
        y_inter = b[..., None, None] * jnp.einsum(
            "pthgnc,phnvc->pthgv", phiq, s_old, precision=_HI)
        den_inter = b[..., None] * jnp.einsum(
            "pthgnc,phnc->pthg", phiq, z_old, precision=_HI)
        s_new = bL[..., None, None, None] * s_old + jnp.einsum(
            "pjh,pjhv,pjhnc->phnvc", w, vf, phik, precision=_HI)
        z_new = bL[..., None, None] * z_old + jnp.einsum(
            "pjh,pjhnc->phnc", w, phik, precision=_HI)
        S = _put_rows(S, layer, put, s_new)
        z = _put_rows(z, layer, put, z_new)
    y = (y_inter + y_intra) / (den_inter + den_intra + EPS)[..., None]
    return y.reshape(P, Lc, Hq, d), S, z


def attention_form(q, k, v, gamma) -> jnp.ndarray:
    """The whole sequence at once, no state: q [T, Hq, d], k, v
    [T, Hkv, d], gamma [T, Hkv] -> y [T, Hq, d] float32."""
    T = q.shape[0]
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), T, jnp.int32)
    *_, y, den = _chunk_terms(q[None], k[None], v[None], gamma[None], zero, full)
    return (y / (den + EPS)[..., None]).reshape(q.shape)
