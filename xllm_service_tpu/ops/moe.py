"""Grouped ragged MoE expert dispatch: routing-to-groups layout, the
CPU/parity oracle, expert-parallel (ep) shard_map wrapping, and the
XLLM_MOE_KERNEL dispatch decision.

The serving-tier counterpart of ops/pallas/moe_dispatch.py (ISSUE 15;
docs/MOE.md). The model layer (models/llama.py `_mlp_block`) hands the
router's top-k output here; this module owns everything below it:

  * **Group layout** — the ragged-attention metadata contract applied
    to experts: STATIC per-group capacity `cap` (group g's rows start
    at g*cap, fixed at trace time — the seg_lens analog) with DYNAMIC
    occupancy `occ[g] = min(assignments, cap)` (the q_len analog).
    Assignments are ranked in router order by a cumsum over the
    one-hot expert matrix; rank >= cap is a CAPACITY OVERFLOW — the
    slot contributes zero to its token (standard MoE capacity-drop
    semantics) and is counted for the obs instruments. The default
    capacity is LOSSLESS (cap = T: a group can never exceed the token
    count), so nothing drops unless XLLM_MOE_CAPACITY_FACTOR opts into
    a tighter buffer.
  * **ep dispatch** — under a declared expert-parallel shard context
    (runtime/executor.py sets it from the mesh, mirroring the PR-12
    attention tp context) the dispatch wraps in `shard_map` over `ep`:
    tokens and routing metadata replicate (the "token shuffle" is each
    shard selecting the slots its expert slice owns), each shard runs
    ONE grouped dispatch over its X/ep-expert slice, and the combine is
    a psum of per-slot outputs. Per-slot values are bit-identical to
    the single-device dispatch (fixed-shape matmuls; non-local slots
    contribute exact zeros), which is what lets the EP differential
    suite (tests/test_moe_engine.py) demand byte-identical token
    streams. GSPMD alone cannot partition the Pallas launch — the same
    silent-replication failure PR 12 fixed for attention — so
    XLLM_SHARDED_KERNELS=0 also drops the MoE kernel back to the
    oracle under plain GSPMD.
  * **Dispatch decision** — XLLM_MOE_KERNEL follows the repo's
    opt-in-until-chip-validated convention (=1 opt in, =0 force the
    oracle/dense, XLLM_MOE_INTERPRET=1 drives the kernel branch on CPU
    for CI); `moe_kernel_eligible` is the tile/lane gate
    (gqa_kernel_eligible's analog: E and F must be 128-lane multiples).

The DENSE all-experts einsum in models/llama.py `_mlp` stays the
default serving path — grouped dispatch is a different numeric regime
(different matmul shapes), so flipping it on changes streams vs dense;
within the grouped regime every engine mode and mesh size is
byte-stable, which the differential suite pins.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp


# ------------------------------------------------------------ hatches

def grouped_moe_enabled() -> bool:
    """Whether MoE blocks route through the grouped ragged dispatch
    instead of the dense all-experts einsum. Opt-in (serving default
    stays dense until moe-* chip cases validate); the interpret hook
    opts in on its own — it exists to DRIVE the grouped branch on CPU
    (the XLLM_RAGGED_INTERPRET convention). =0 always wins."""
    env = os.environ.get("XLLM_MOE_KERNEL")
    if env == "0":
        return False
    return env == "1" or moe_interpret()


def moe_interpret() -> bool:
    """CI hook: run the grouped Pallas kernel in interpret mode on CPU."""
    return os.environ.get("XLLM_MOE_INTERPRET") == "1"


def moe_kernel_eligible(E: int, F: int, on: bool) -> bool:
    """Tile/lane eligibility for the grouped Pallas kernel (the
    gqa_kernel_eligible analog): token rows carry E lanes, weight
    chunks FT lanes — both must be 128 multiples (mosaic_rules rule 1).
    `on` is the platform gate (_on_tpu() or interpret)."""
    return on and E % 128 == 0 and F % 128 == 0


def moe_capacity(T: int, X: int, K: int) -> int:
    """Static per-expert group capacity for a T-token dispatch. Default
    LOSSLESS (cap = T); XLLM_MOE_CAPACITY_FACTOR=f sizes the classic
    balanced-load buffer ceil(f * T*K/X) instead — overflow drops (and
    is counted by the obs instruments)."""
    f = os.environ.get("XLLM_MOE_CAPACITY_FACTOR")
    if not f:
        return T
    cap = int(math.ceil(float(f) * T * K / max(X, 1)))
    return max(1, min(T, cap))


def resolved_moe_dispatch(E: int, F: int) -> str:
    """The MoE dispatch the serving path would take RIGHT NOW for this
    geometry — what kernel_report()/bench report instead of the raw env
    var: "dense" (the all-experts einsum), "grouped" (the Pallas
    kernel), or "grouped-ref" (grouped semantics on the blockwise
    oracle — enabled but kernel-ineligible, e.g. CPU without the
    interpret hook)."""
    from xllm_service_tpu.ops.attention import _on_tpu

    if not grouped_moe_enabled():
        return (
            "dense (forced-off)"
            if os.environ.get("XLLM_MOE_KERNEL") == "0"
            else "dense"
        )
    if moe_kernel_eligible(E, F, _on_tpu() or moe_interpret()):
        return "grouped"
    return "grouped-ref"


# -------------------------------------------------- ep shard context
# Mirrors ops.attention's per-thread tp context: the executor declares
# its mesh before every jitted-step entry; the grouped dispatch wraps
# in shard_map over `ep` when the axis is real. Shares the PR-12
# XLLM_SHARDED_KERNELS escape hatch — with it off, ep>1 meshes serve
# the grouped ORACLE under plain GSPMD instead (correct, no per-shard
# launch).

_EP_TLS = threading.local()


def set_ep_context(mesh, axis: str = "ep") -> None:
    """Declare the mesh the current thread's MoE dispatches run under
    (None clears). Ignored for meshes without a >1 `axis` extent."""
    if mesh is not None and mesh.shape.get(axis, 1) > 1:
        _EP_TLS.ctx = (mesh, axis)
    else:
        _EP_TLS.ctx = None


def ep_context():
    """(mesh, axis) when per-shard MoE dispatch applies, else None."""
    from xllm_service_tpu.ops.attention import sharded_kernels_enabled

    ctx = getattr(_EP_TLS, "ctx", None)
    if ctx is None or not sharded_kernels_enabled():
        return None
    return ctx


# ----------------------------------------------------------- stats sink
# Expert-load / capacity-overflow instruments without touching the model
# step signatures OR the scan structure: grouped_moe runs inside every
# step family's layer scan, where a side-channel traced value would leak
# (UnexpectedTracerError) and an extra scan output would rewrite six
# model functions — so each grouped dispatch instead emits its
# (assignment counts, dropped, capacity rows) through an UNORDERED
# jax.debug.callback to a per-thread host sink the executor registers at
# every step entry (runtime/executor.py moe_stats). The callback is
# async (never blocks the device or the overlap pipeline), fires once
# per MoE layer per step only when the grouped dispatch is enabled, and
# is absent from the trace entirely when no sink is registered.

_STATS_TLS = threading.local()


def set_stats_sink(sink) -> None:
    """Register the calling thread's stats sink —
    `sink(counts: np.ndarray[X], dropped: int, cap_rows: int)`, called
    from JAX's callback thread once per grouped dispatch — or None to
    clear. Read at TRACE time (the jitted steps bake the sink in), the
    same lifetime as every other per-thread context here."""
    _STATS_TLS.sink = sink


def _record(counts: jnp.ndarray, dropped: jnp.ndarray, cap_rows: int):
    sink = getattr(_STATS_TLS, "sink", None)
    if sink is None:
        return

    def emit(c, d, sink=sink, rows=cap_rows):
        import numpy as np

        sink(np.asarray(c), int(d), rows)

    jax.debug.callback(emit, counts, dropped, ordered=False)


# --------------------------------------------------------- the oracle

def _act_fn(act: str):
    """Gated-MLP activation by config name — THE selector shared by the
    dense path (models/llama.py _act delegates), the blockwise oracle,
    and the Pallas kernel, so the three can never drift on activation
    semantics."""
    if act == "gelu_tanh":
        return lambda t: jax.nn.gelu(t, approximate=True)
    return jax.nn.silu


def moe_blockwise(
    xg: jnp.ndarray,     # [G, E] grouped token rows (kernel layout)
    occ: jnp.ndarray,    # [Xl] int32 live rows per group
    w_gate: jnp.ndarray,  # [Xl, E, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [Xl, F, E]
    cap: int,
    act: str = "silu",
) -> jnp.ndarray:
    """Blockwise oracle for the grouped-dispatch contract: one
    fixed-shape [cap, E] FFN per expert group via lax.scan, dead rows
    (rank >= occ, padding tail) zeroed. Exact; the CPU/parity reference
    for ops/pallas/moe_dispatch.py AND the serving path when the
    grouped dispatch is enabled but the kernel is ineligible. The
    per-expert shapes are mesh-size-independent, which is what keeps
    per-slot outputs bit-identical between ep shards and one device."""
    G, E = xg.shape
    Xl = w_gate.shape[0]
    activate = _act_fn(act)
    xe = xg[: Xl * cap].reshape(Xl, cap, E)
    ranks = jnp.arange(cap, dtype=jnp.int32)[:, None]  # [cap, 1]

    def body(_, inp):
        xrows, wg, wu, wd, oc = inp
        gate = jax.lax.dot_general(
            xrows, wg,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        up = jax.lax.dot_general(
            xrows, wu,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        h = activate(gate) * up
        h = jnp.where(ranks < oc, h, 0.0)
        out = jnp.dot(
            h.astype(wd.dtype), wd, preferred_element_type=jnp.float32,
        )
        return None, out.astype(xg.dtype)

    _, og = jax.lax.scan(
        body, None, (xe, w_gate, w_up, w_down, occ.astype(jnp.int32))
    )
    og = og.reshape(Xl * cap, E)
    if G > Xl * cap:
        og = jnp.concatenate(
            [og, jnp.zeros((G - Xl * cap, E), og.dtype)], axis=0
        )
    return og


# ------------------------------------------------------- the dispatch

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _dispatch_local(
    x: jnp.ndarray,        # [T, E] token rows (replicated under ep)
    loc_e: jnp.ndarray,    # [S] int32 — slot expert id, LOCAL index
    rank: jnp.ndarray,     # [S] int32 — slot rank within its expert
    live: jnp.ndarray,     # [S] bool — local AND under capacity
    tok: jnp.ndarray,      # [S] int32 — slot token index
    counts_l: jnp.ndarray,  # [Xl] int32 — local per-expert assignments
    w_gate: jnp.ndarray,   # [Xl, E, F] local expert slice
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    cap: int,
    act: str,
    use_kernel: bool,
    interpret: bool,
) -> jnp.ndarray:
    """Grouped dispatch over ONE expert slice: build the capacity-padded
    group buffer, run the kernel (or oracle), gather per-slot outputs.
    Returns y_slots [S, E] f32 with dead slots exactly 0."""
    from xllm_service_tpu.ops.pallas.moe_dispatch import (
        moe_grouped_dispatch_kernel,
        tile_rows,
    )

    T, E = x.shape
    Xl = w_gate.shape[0]
    TT = tile_rows(Xl * cap)
    Gp = _round_up(Xl * cap, TT)
    occ = jnp.minimum(counts_l.astype(jnp.int32), cap)
    dst = jnp.where(live, loc_e * cap + rank, Gp)  # dead → garbage row
    xg = jnp.zeros((Gp + 1, E), x.dtype).at[dst].set(x[tok])
    if use_kernel:
        og = moe_grouped_dispatch_kernel(
            xg[:Gp], occ, w_gate, w_up, w_down, cap, act=act,
            interpret=interpret,
        )
    else:
        og = moe_blockwise(xg[:Gp], occ, w_gate, w_up, w_down, cap, act)
    og = jnp.concatenate([og, jnp.zeros((1, E), og.dtype)], axis=0)
    return og[dst].astype(jnp.float32)  # dead slots read the zero row


def grouped_moe(
    x: jnp.ndarray,        # [T, E]
    topi: jnp.ndarray,     # [T, K] int32 router top-k expert ids
    weights: jnp.ndarray,  # [T, K] f32 router combine weights
    w_gate: jnp.ndarray,   # [X, E, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,   # [X, F, E]
    act: str = "silu",
    cap: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    row_mask: Optional[jnp.ndarray] = None,  # [T] bool; False = padding
) -> jnp.ndarray:
    """Routed-expert block via the grouped ragged dispatch: ONE launch
    per expert slice instead of X per-expert launches or the dense
    all-experts einsum. Returns y [T, E] in x.dtype (the shared-expert
    tail stays with the caller — it is dense and family-specific).

    `row_mask` marks the LIVE token rows: padding lanes and inactive
    decode slots (False) are excluded from routing — they neither count
    in the expert-load stats (a mostly-idle R-slot batch must not feed
    the master garbage hotness) nor consume group capacity (under
    XLLM_MOE_CAPACITY_FACTOR a padding row taking a capacity slot would
    displace a REAL token's expert contribution), and their output rows
    are exactly 0 (discarded downstream, like the dense path's garbage
    rows)."""
    T, K = topi.shape
    X, E, F = w_gate.shape
    if cap is None:
        cap = moe_capacity(T, X, K)
    cap = max(1, min(cap, T))
    interp = moe_interpret() if interpret is None else interpret
    if use_kernel is None:
        from xllm_service_tpu.ops.attention import _on_tpu

        use_kernel = moe_kernel_eligible(E, F, _on_tpu() or interp)
        if (
            use_kernel
            and getattr(_EP_TLS, "ctx", None) is not None
            and ep_context() is None
        ):
            # An ep mesh is declared but XLLM_SHARDED_KERNELS=0 dropped
            # the shard_map wrap: a pallas_call under plain GSPMD would
            # run replicated over gathered weights (the PR-12 failure
            # mode) — serve the partitionable oracle instead.
            use_kernel = False

    # Global slot metadata (replicated under ep so every shard ranks
    # identically): slot s = (token s//K, choice s%K). Dead rows (the
    # row_mask) zero out of the one-hot BEFORE ranking, so they hold no
    # rank, no capacity, and no stats.
    flat_e = topi.reshape(T * K).astype(jnp.int32)
    oh = (
        flat_e[:, None] == jnp.arange(X, dtype=jnp.int32)[None, :]
    ).astype(jnp.int32)  # [S, X]
    slot_ok = None
    if row_mask is not None:
        slot_ok = jnp.repeat(row_mask.reshape(T), K)
        oh = oh * slot_ok[:, None].astype(jnp.int32)
    counts = oh.sum(axis=0)  # [X]
    rank = jnp.take_along_axis(
        jnp.cumsum(oh, axis=0) - oh, flat_e[:, None], axis=1
    )[:, 0]
    live = rank < cap
    if slot_ok is not None:
        live = live & slot_ok
    dropped = jnp.sum(jnp.maximum(counts - cap, 0))
    _record(counts, dropped, X * cap)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K

    ctx = ep_context()
    n_shards = ctx[0].shape[ctx[1]] if ctx is not None else 1
    if ctx is not None and n_shards > 1 and X % n_shards == 0:
        from jax.sharding import PartitionSpec as P
        from xllm_service_tpu.ops import collective_matmul as cm_ops

        # Trace-time hatch read (the jitted steps bake it in, like
        # every other kernel hatch here).
        overlap = cm_ops.overlap_collectives_enabled()
        mesh, axis = ctx
        Xl = X // n_shards

        def body(xb, fe, rk, lv, tk, cnts, wgb, wub, wdb):
            lo = jax.lax.axis_index(axis).astype(jnp.int32) * Xl
            local = (fe >= lo) & (fe < lo + Xl)
            counts_l = jax.lax.dynamic_slice(cnts, (lo,), (Xl,))
            y = _dispatch_local(
                xb, fe - lo, rk, lv & local, tk, counts_l,
                wgb, wub, wdb, cap, act, use_kernel, interp,
            )
            # The combine "shuffle": each slot's value lives on exactly
            # one shard (the rest contribute exact zeros), so the psum
            # reproduces the single-device per-slot bits. Under
            # XLLM_OVERLAP_COLLECTIVES the psum decomposes into the
            # ring reduce-scatter/all-gather schedule so the combine
            # pipelines under the dispatch compute — still bit-exact
            # (adding exact zeros commutes in every order).
            if overlap:
                return cm_ops.ring_all_reduce(y, axis, n_shards)
            return jax.lax.psum(y, axis)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P())
            + (P(axis, None, None),) * 3,
            out_specs=P(),
            check_vma=False,
        )
        y_slots = fn(
            x, flat_e, rank, live, tok, counts, w_gate, w_up, w_down,
        )
    else:
        y_slots = _dispatch_local(
            x, flat_e, rank, live, tok, counts,
            w_gate, w_up, w_down, cap, act, use_kernel, interp,
        )

    y = jnp.sum(
        y_slots.reshape(T, K, E)
        * weights.astype(jnp.float32).reshape(T, K, 1),
        axis=1,
    )
    return y.astype(x.dtype)
