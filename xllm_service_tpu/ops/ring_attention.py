"""Ring attention: sequence/context-parallel exact attention for long
prefill (SURVEY.md §5 long-context row; the task brief makes SP
first-class).

TPU-first design: the sequence axis is sharded over an `sp` mesh axis.
Each device keeps its QUERY shard resident and the K/V shards rotate
around the ring with `jax.lax.ppermute` over ICI — sp steps of
(block attention + online-softmax merge), compute overlapping the
neighbor exchange. HBM never holds more than 1/sp of the context per
device, so max context scales linearly with the ring size; the math is
EXACT (flash-style log-sum-exp accumulation, not an approximation).

Blockwise/causal: with causal masking, chunks entirely in the future of a
query shard contribute nothing; their scores are masked to -inf and the
merge is a no-op (the ppermute still runs — the ring must stay in
lockstep; skipping compute for dead chunks is a `lax.cond` refinement
that does not change results).

GQA throughout: q [B, L, Hq, D], k/v [B, L, Hkv, D], Hq % Hkv == 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_scores(q, k, scale):
    """q [B, Lq, Hkv, G, D] f32, k [B, Lk, Hkv, D] f32 ->
    scores [B, Hkv, G, Lq, Lk] f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale


def _ring_attention_local(
    q,  # [B, Lc, Hq, D] — this device's query shard
    k,  # [B, Lc, Hkv, D] — this device's (initial) K shard
    v,  # [B, Lc, Hkv, D]
    *,
    axis_name: str,
    scale: float,
    causal: bool,
):
    B, Lc, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32).reshape(B, Lc, Hkv, G, D)
    rows = me * Lc + jnp.arange(Lc, dtype=jnp.int32)  # global query positions

    m0 = jnp.full((B, Hkv, G, Lc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Lc, 1), jnp.float32)
    a0 = jnp.zeros((B, Lc, Hkv, G, D), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(s, carry):
        m_prev, l_prev, acc, k_cur, v_cur = carry
        # After s forward rotations this device holds chunk (me - s) mod n.
        src = jax.lax.rem(me - s + n, n)
        cols = src * Lc + jnp.arange(Lc, dtype=jnp.int32)

        scores = _block_scores(qf, k_cur.astype(jnp.float32), scale)
        if causal:
            mask = cols[None, :] <= rows[:, None]  # [Lc_q, Lc_k]
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)

        m_cur = jnp.max(scores, axis=-1, keepdims=True)  # [B,Hkv,G,Lq,1]
        m_new = jnp.maximum(m_prev, m_cur)
        # All-masked blocks keep m_new at NEG_INF: exp(0)=1 would pollute l,
        # so clamp the shift to stay a no-op.
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        p = jnp.exp(scores - m_new)
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cur.astype(jnp.float32))
        acc = acc * jnp.moveaxis(alpha, -2, 1)[..., 0][..., None] + pv

        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, acc, k_nxt, v_nxt

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, a0, k, v))
    l_q = jnp.moveaxis(l, -2, 1)[..., 0][..., None]  # [B, Lc, Hkv, G, 1]
    out = acc / jnp.maximum(l_q, 1e-30)
    return out.reshape(B, Lc, Hq, D).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, L, Hq, D], L sharded over sp
    k: jnp.ndarray,  # [B, L, Hkv, D]
    v: jnp.ndarray,
    mesh: Mesh,
    sp_axis: str = "sp",
    scale: Optional[float] = None,
    causal: bool = True,
    tp_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Sequence-parallel exact attention over `mesh`'s `sp_axis`.

    Call under `jit` with the mesh installed; inputs carry (or are given)
    shardings with L split over `sp_axis`. Returns [B, L, Hq, D] with the
    same sequence sharding.

    `tp_axis` COMPOSES sequence and tensor parallelism: the head axis
    additionally shards over that mesh axis (Hq and Hkv both divisible
    by its size — GQA grouping is per-shard). The ring's ppermute runs
    over sp only; heads need no cross-device communication, so the tp
    dimension is purely spatial here and the surrounding projections
    keep their Megatron sharding on the SAME mesh (VERDICT r4 #6)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(None, sp_axis, tp_axis, None)
    local = functools.partial(
        _ring_attention_local,
        axis_name=sp_axis,
        scale=scale,
        causal=causal,
    )
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
