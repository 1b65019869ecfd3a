"""Latency-hiding collective matmuls for the sharded hot loop.

ROADMAP item 6 (ISSUE 18). PRs 12/13 made every GQA and MoE kernel
dispatch per-shard, but the hot loop still serializes compute against
its collectives: the tp o-proj/down-proj matmuls contract over a
sharded axis and the GSPMD partitioner lowers them as local-matmul
THEN all-reduce — the reduction sits on the critical path after the
compute it depends on. This module decomposes those sites into
`lax.ppermute`-based collective-matmul pipelines (the classic ring
reduce-scatter + ring all-gather schedule):

  * **Ring reduce-scatter matmul** — the output-column axis E splits
    into n chunks of Ec = E/n. At step 0 shard i computes its local
    tile of chunk (i-1) mod n; at step s it rotates the running
    partial one hop around the ring (i -> i+1) and adds its tile of
    chunk (i-1-s) mod n. Each tile matmul is independent of the
    in-flight permute, so XLA schedules the collective-permute DMA
    under the next tile's compute — the reduction rides beneath the
    matmul instead of after it. After n-1 steps shard i holds the
    FULLY reduced chunk i.
  * **Ring all-gather** — n-1 more hops rotate the reduced chunks so
    every shard reassembles the replicated [.., E] output (the serving
    steps consume the o-proj/down-proj output replicated, exactly like
    the psum the schedule replaces).

2(n-1) permutes total, each of size |out|/n — same bytes on the wire
as the all-reduce it replaces, but pipelined under compute.

Numerics: the ring adds partials in ring order while the GSPMD
all-reduce uses its own reduction tree, so arrays may differ by f32
reduction-order noise (~1e-6) — the PR-12 contract: token streams must
stay BIT-EQUAL, which tests/test_overlap_collectives.py pins across
tp x ep virtual meshes. The ep expert-combine is stricter: per-slot
values are exact zeros on non-owning shards, so `ring_all_reduce`
reproduces the psum bits exactly.

Hatch: `XLLM_OVERLAP_COLLECTIVES=1` opts in (default OFF — serving
keeps the GSPMD psum lowering until the overlap validates on chip);
`=0` always wins. The tp context is the one the executor already
declares before every jitted step family (ops.attention's per-thread
shard context, read raw — the overlap tier gates on its own hatch,
not on XLLM_SHARDED_KERNELS). Ineligible geometries (axis extent that
doesn't divide H or E) fall back to the caller's einsum, so the hatch
can never change which shapes serve.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def overlap_collectives_enabled() -> bool:
    """Whether the sharded hot loop decomposes its tp/ep combines into
    ring collective-matmul pipelines. Opt-in; =0 always wins."""
    return os.environ.get("XLLM_OVERLAP_COLLECTIVES", "0") not in (
        "", "0", "false", "off",
    )


def tp_overlap_context() -> Optional[Tuple[object, str]]:
    """(mesh, axis) for the tp ring when the overlap hatch is on and the
    executor has declared a tp>1 shard context for this thread; else
    None. Reads the RAW context (ops.attention declares it for any tp>1
    mesh) — XLLM_SHARDED_KERNELS gates kernel dispatch, not this tier."""
    if not overlap_collectives_enabled():
        return None
    from xllm_service_tpu.ops import attention as att

    return att.declared_shard_context()


# Trace-time instrumentation: how many matmul sites actually took the
# ring schedule (the engine's per-step counter multiplies this by
# dispatches; the differential suite asserts it moved). Thread-local
# like the shard context — one engine thread per executor.
_TRACE_TLS = threading.local()


def overlap_sites_traced() -> int:
    return getattr(_TRACE_TLS, "sites", 0)


def _note_site() -> None:
    _TRACE_TLS.sites = getattr(_TRACE_TLS, "sites", 0) + 1


def _ring_perm(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def ring_all_reduce(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Drop-in `lax.psum(x, axis)` replacement inside a shard_map body:
    ring reduce-scatter over x's LAST axis followed by a ring
    all-gather, so each hop's add overlaps the next hop's permute.
    Falls back to psum when the last axis doesn't split n ways.

    Used by the grouped-MoE ep combine: per-slot outputs are exact
    zeros off the owning shard, so ring order reproduces the psum bits
    exactly (0 + v == v + 0 == v in every order)."""
    E = x.shape[-1]
    if n <= 1 or E % n != 0:
        return jax.lax.psum(x, axis)
    Ec = E // n
    i = jax.lax.axis_index(axis).astype(jnp.int32)
    perm = _ring_perm(n)
    last = x.ndim - 1

    def chunk(c):
        return jax.lax.dynamic_slice_in_dim(x, c * Ec, Ec, axis=last)

    # Reduce-scatter: after step s, the partial travelling through
    # shard i covers chunk (i-1-s) mod n summed over s+1 shards; the
    # final hop lands chunk i on shard i fully reduced.
    acc = chunk((i - 1) % n)
    for s in range(1, n):
        acc = jax.lax.ppermute(acc, axis, perm) + chunk((i - 1 - s) % n)

    # All-gather: rotate the reduced chunks back around the ring.
    out = jnp.zeros_like(x)
    out = jax.lax.dynamic_update_slice_in_dim(out, acc, i * Ec, axis=last)
    g = acc
    for s in range(1, n):
        g = jax.lax.ppermute(g, axis, perm)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, g, ((i - s) % n) * Ec, axis=last
        )
    return out


def _ring_matmul_body(x, w, *, axis: str, n: int):
    """Per-shard body: x [..., H/n] (this shard's slice of the
    contraction axis), w [H/n, E] (this shard's row block) ->
    [..., E] replicated fully-reduced product.

    The tile matmul at step s is independent of the permute launched at
    step s, which is what lets XLA hide the DMA under compute."""
    E = w.shape[-1]
    Ec = E // n
    i = jax.lax.axis_index(axis).astype(jnp.int32)
    perm = _ring_perm(n)

    def tile(c):
        wc = jax.lax.dynamic_slice_in_dim(w, c * Ec, Ec, axis=1)
        return jnp.matmul(x, wc)

    acc = tile((i - 1) % n)
    for s in range(1, n):
        acc = jax.lax.ppermute(acc, axis, perm) + tile((i - 1 - s) % n)

    out = jnp.zeros(x.shape[:-1] + (E,), acc.dtype)
    last = out.ndim - 1
    out = jax.lax.dynamic_update_slice_in_dim(out, acc, i * Ec, axis=last)
    g = acc
    for s in range(1, n):
        g = jax.lax.ppermute(g, axis, perm)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, g, ((i - s) % n) * Ec, axis=last
        )
    return out


def maybe_overlap_matmul(
    x: jnp.ndarray, w: jnp.ndarray
) -> Optional[jnp.ndarray]:
    """Overlapped row-parallel matmul `x @ w` (x [..., H] with H the
    mesh-sharded contraction axis, w [H, E]) when the hatch + a tp>1
    context apply and the geometry divides; else None — the caller
    keeps its original einsum so the default path's lowering (and
    bits) are untouched when the hatch is off."""
    ctx = tp_overlap_context()
    if ctx is None:
        return None
    mesh, axis = ctx
    n = int(mesh.shape[axis])
    H, E = int(w.shape[0]), int(w.shape[1])
    if n <= 1 or H % n != 0 or E % n != 0 or int(x.shape[-1]) != H:
        return None
    from jax.sharding import PartitionSpec as P

    x_spec = P(*([None] * (x.ndim - 1) + [axis]))
    fn = jax.shard_map(
        lambda xb, wb: _ring_matmul_body(xb, wb, axis=axis, n=n),
        mesh=mesh,
        in_specs=(x_spec, P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )
    _note_site()
    return fn(x, w)
