"""The routed half of an MoE layer: the pairs a router made, sorted by
expert, through ONE grouped, ragged expert product over the experts this
holder has; the expert-parallel (ep) wrap; and the counts a step hands
out with its tokens.

models/llama.py `_mlp_block` hands the router's top-k here (the router
is as wide as the PUBLISHED expert count, ModelConfig.num_experts); this
module owns everything below it:

  * **The pairs.** A pair is one (live token, chosen expert). Pairs whose
    expert is held here (ModelConfig.experts_held, or this ep shard's
    slice) sort by expert to the front of one row buffer of T*K rows;
    pairs of absent experts and of dead rows (padding, idle slots) sort
    behind them and are never computed: what they would add is another
    holder's to add. There is no capacity: the buffer holds every pair a
    step can make, so no pair is dropped at any imbalance, and the work
    (ops/pallas/moe_dispatch.py) follows the pairs that fall here.
  * **The product.** `moe_grouped_kernel` where the platform and the
    widths allow it (TPU, or XLLM_MOE_INTERPRET=1 for CI; E and F lane
    multiples), else the same contract through jax.lax.ragged_dot
    (`expert_product_reference`): the CPU path and the kernels' oracle.
    It is every expert model's path; the all-experts einsum of
    models/llama.py `_mlp` is the dense ORACLE of the tests only.
  * **ep.** Under a declared expert-parallel shard context the product
    wraps in `shard_map` over `ep`: each shard holds X/ep experts, the
    tokens and the router's choice replicate, and the per-pair outputs
    are psum'd (a pair lives on one shard, the others add exact zeros).
    GSPMD alone cannot partition a Pallas launch, so
    XLLM_SHARDED_KERNELS=0 serves the reference under plain GSPMD.
  * **The counts.** Each call records the router's choice counts over
    its live rows and which experts it touched ([2 x num_experts]
    int32). They are collected per layer inside the layer scan
    (`layer_stats`) and per step program
    (`step_stats`, entered by the executor), and leave the device as one
    small output of the step program, beside its tokens: no callback,
    no transfer a layer.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from xllm_service_tpu.obs.spans import region


# ------------------------------------------------------------ hatches

def moe_interpret() -> bool:
    """CI hook: run the grouped Pallas kernels in interpret mode on CPU."""
    return os.environ.get("XLLM_MOE_INTERPRET") == "1"


def moe_kernel_eligible(E: int, F: int, on: bool) -> bool:
    """Tile/lane eligibility for the grouped Pallas kernels (the
    gqa_kernel_eligible analog): pair rows carry E lanes, hidden rows F
    lanes: both must be 128 multiples. `on` is the platform gate
    (_on_tpu() or interpret)."""
    return on and E % 128 == 0 and F % 128 == 0


def resolved_moe_dispatch(E: int, F: int) -> str:
    """The expert product the serving path takes RIGHT NOW for this
    geometry: "grouped" (the Pallas kernels) or "grouped-ref" (the same
    contract in plain XLA: the CPU, or widths that are not lane
    multiples)."""
    from xllm_service_tpu.ops.attention import _on_tpu

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


# --------------------------------------------------------------- counts
# What the router chose, out of the step program with its tokens. Each
# grouped_moe call records its [2 x num_experts] int32 counts; a layer
# scan's body collects what its layer recorded (`layer_stats`) and
# returns it as a scan output, the scan's caller adds the layers' sum to
# the step's (`add_step`), and the executor's step programs enter
# `step_stats` and return the total as one more small output. Outside a
# layer scope (the oracles, a direct ops-level call) nothing is recorded,
# so no traced value can leak from a scan it was made in.

_STATS_TLS = threading.local()


class _Collector:
    def __init__(self, slot: str):
        self.slot, self.items = slot, []

    def __enter__(self):
        self.prev = getattr(_STATS_TLS, self.slot, None)
        setattr(_STATS_TLS, self.slot, self.items)
        return self

    def __exit__(self, *exc):
        setattr(_STATS_TLS, self.slot, self.prev)

    def total(self):
        """Sum of what was recorded, or None."""
        if not self.items:
            return None
        out = self.items[0]
        for c in self.items[1:]:
            out = out + c
        return out


def layer_stats() -> _Collector:
    """Scope of ONE layer's body inside a layer scan (trace time)."""
    return _Collector("layer")


def step_stats() -> _Collector:
    """Scope of one step program (trace time; runtime/executor.py)."""
    return _Collector("step")


def add_step(layers_counts) -> None:
    """Hand a scan's stacked per-layer counts [L, 2X] (or None) to the
    enclosing step scope, if there is one."""
    step = getattr(_STATS_TLS, "step", None)
    if step is not None and layers_counts is not None:
        step.append(layers_counts.sum(axis=0))


def _record(counts: jnp.ndarray) -> None:
    layer = getattr(_STATS_TLS, "layer", None)
    if layer is not None:
        layer.append(counts)


# ------------------------------------------------------------ the oracle

def _act_fn(act: str):
    """Gated-MLP activation by config name: THE selector shared by the
    dense oracle (models/llama.py _act delegates), the reference and the
    Pallas kernels, so the three can never drift."""
    if act == "gelu_tanh":
        return lambda t: jax.nn.gelu(t, approximate=True)
    return jax.nn.silu


def expert_product_reference(
    xs: jnp.ndarray,           # [M, E] pair rows sorted by held expert
    group_sizes: jnp.ndarray,  # [Xh] int32
    w_gate: jnp.ndarray,       # [Xh, E, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,       # [Xh, F, E]
    act: str = "silu",
) -> jnp.ndarray:
    """The grouped product's contract in plain XLA: the CPU path and the
    oracle of ops/pallas/moe_dispatch.py. One fixed-shape [M, E] FFN an
    expert (lax.scan), each keeping the rows of its own span; rows past
    the last span come out as zeros. The shapes do not follow the spans,
    so a row's value is the same bits wherever the sort put it: an ep
    shard and one device agree bit for bit (tests/test_moe_engine.py).
    Its cost is every expert over every row: test sizes, and widths the
    kernels decline."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    rows = jnp.arange(xs.shape[0], dtype=jnp.int32)[:, None]
    activate = _act_fn(act)

    def body(out, inp):
        wg, wu, wd, lo, hi = inp
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        h = (activate(dot(xs, wg)) * dot(xs, wu)).astype(xs.dtype)
        own = (rows >= lo) & (rows < hi)
        return jnp.where(own, dot(h, wd).astype(xs.dtype), out), None

    out, _ = jax.lax.scan(
        body, jnp.zeros_like(xs),
        (w_gate, w_up, w_down, ends - group_sizes.astype(jnp.int32), ends),
    )
    return out


# ------------------------------------------------------- the dispatch

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _held_product(
    x: jnp.ndarray,       # [T, E] token rows (replicated under ep)
    loc_e: jnp.ndarray,   # [S] int32 pair's expert, index into THIS slice
    held: jnp.ndarray,    # [S] bool: live row and an expert of this slice
    w_gate: jnp.ndarray,  # the slice: [Xh, E, F], or the layers' stack
    w_up: jnp.ndarray,    # [n, Xh, E, F] with `layer`
    w_down: jnp.ndarray,
    K: int,
    act: str,
    use_kernel: bool,
    interpret: bool,
    layer=None,
) -> jnp.ndarray:
    """One grouped product over ONE slice of experts: sort the pairs it
    holds to the front, run the kernel (or the reference), hand each pair
    its row back. Returns y_pairs [S, E] float32, 0 for a pair not held."""
    from xllm_service_tpu.ops.pallas.moe_dispatch import (
        moe_grouped_kernel,
        tile_rows,
    )

    S = loc_e.shape[0]
    Xh = w_gate.shape[-3]
    with region("moe_route"):  # the grouping
        key = jnp.where(held, loc_e, Xh)  # absent and dead pairs sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.zeros((Xh + 1,), jnp.int32).at[key].add(1)[:Xh]
        M = _round_up(S, tile_rows(S))
        rows = jnp.pad(order // K, (0, M - S))
        xs = x[rows]
    if use_kernel:
        ys = moe_grouped_kernel(
            xs, sizes, w_gate, w_up, w_down, act=act, interpret=interpret,
            layer=layer,
        )
    else:
        if layer is not None:  # plain XLA reads the layer through its dots
            w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
        ys = expert_product_reference(xs, sizes, w_gate, w_up, w_down, act)
    pos = jnp.zeros((S,), jnp.int32).at[order].set(
        jnp.arange(S, dtype=jnp.int32)
    )
    return jnp.where(held[:, None], ys[pos].astype(jnp.float32), 0.0)


@region("moe_experts")
def grouped_moe(
    x: jnp.ndarray,        # [T, E]
    topi: jnp.ndarray,     # [T, K] int32 router top-k ids, of num_experts
    weights: jnp.ndarray,  # [T, K] f32 router combine weights
    w_gate: jnp.ndarray,   # [Xh, E, F] the experts held, or with `layer`
    w_up: jnp.ndarray,     # the layers' stacked leaves [n, Xh, E, F]: the
    w_down: jnp.ndarray,   # kernels index the stack ([.., Xh, F, E])
    act: str = "silu",
    layer=None,            # int32 scalar: which layer of the stacks
    first: int = 0,        # the held span starts at this expert
    num_experts: Optional[int] = None,  # the router's width (default Xh)
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    row_mask: Optional[jnp.ndarray] = None,  # [T] bool; False = padding
) -> jnp.ndarray:
    """The routed experts' part of the layer that the held experts give:
    y[t] = sum over t's chosen experts HELD HERE of weight x FFN_e(x[t]),
    in x.dtype (the shared experts stay with the caller). No pair is
    dropped at any imbalance.

    `row_mask` marks the LIVE token rows: padding lanes and inactive
    decode slots (False) make no pair: they are neither counted nor
    computed, and their output rows are exactly 0."""
    T, K = topi.shape
    Xh, E, F = w_gate.shape[-3:]
    X = Xh if num_experts is None else num_experts
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
            # run replicated over gathered weights: serve the
            # partitionable reference instead.
            use_kernel = False

    with region("moe_route"):  # the counts output
        flat_e = topi.reshape(T * K).astype(jnp.int32)
        live = (
            jnp.ones((T * K,), bool) if row_mask is None
            else jnp.repeat(row_mask.reshape(T), K)
        )
        counts = (
            jnp.zeros((X + 1,), jnp.int32)
            .at[jnp.where(live, flat_e, X)].add(1)[:X]
        )
        # [2X]: pairs an expert, then 1 where the layer touched it at all
        # (summed over layers: in how many layers its weights were read).
        _record(jnp.concatenate([counts, (counts > 0).astype(jnp.int32)]))

    ctx = ep_context()
    n_shards = ctx[0].shape[ctx[1]] if ctx is not None else 1
    if ctx is not None and n_shards > 1 and Xh % n_shards == 0:
        from jax.sharding import PartitionSpec as P
        from xllm_service_tpu.ops import collective_matmul as cm_ops

        overlap = cm_ops.overlap_collectives_enabled()  # trace-time hatch
        mesh, axis = ctx
        Xl = Xh // n_shards

        def body(xb, fe, lv, wgb, wub, wdb):
            lo = first + jax.lax.axis_index(axis).astype(jnp.int32) * Xl
            y = _held_product(
                xb, fe - lo, lv & (fe >= lo) & (fe < lo + Xl),
                wgb, wub, wdb, K, act, use_kernel, interp, layer,
            )
            # A pair's value lives on exactly one shard (the rest add
            # exact zeros), so the psum reproduces the one-device bits.
            if overlap:
                return cm_ops.ring_all_reduce(y, axis, n_shards)
            return jax.lax.psum(y, axis)

        y_pairs = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P())
            + (P(*(None,) * (w_gate.ndim - 3), axis),) * 3,
            out_specs=P(),
            check_vma=False,
        )(x, flat_e, live, w_gate, w_up, w_down)
    else:
        y_pairs = _held_product(
            x, flat_e - first,
            live & (flat_e >= first) & (flat_e < first + Xh),
            w_gate, w_up, w_down, K, act, use_kernel, interp, layer,
        )

    y = jnp.sum(
        y_pairs.reshape(T, K, E)
        * weights.astype(jnp.float32).reshape(T, K, 1),
        axis=1,
    )
    return y.astype(x.dtype)
