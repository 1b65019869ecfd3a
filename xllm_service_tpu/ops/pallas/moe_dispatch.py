"""Pallas TPU grouped, ragged expert product: the routed half of an MoE
layer over the experts THIS holder has, with work that follows the pairs.

A pair is one (token, chosen expert). ops/moe.py sorts the pairs that fall
to a held expert by expert, so expert g's rows are the contiguous span
[off[g], off[g+1]) of one row buffer xs [M, E]; pairs that fall to absent
experts sort behind them and are never visited. Nothing has a capacity,
so nothing can be dropped: the buffer holds every pair the step can make
(M = T*K rounded up to a tile), whatever the imbalance.

Two launches, the same walk (after the grouped matmul of MegaBlocks,
arXiv:2211.15841, as jax.experimental.pallas.ops.tpu.megablox lays it
out for the TPU):

  * `moe_grouped_kernel` (gate and up, fused with the activation):
    grid (step, k tile). A STEP is one (expert, row tile) meeting: expert
    g meets the tiles its span touches, so a tile that holds the ends of
    several spans is met once by each, consecutively, and each meeting
    stores only its own rows. Steps beyond the live ones stay on the last
    live step's last blocks (no DMA) and compute nothing. E is tiled on the k
    axis with float32 accumulators, so a 5120-wide model's weight blocks
    fit VMEM; the weights are read where the model keeps them, the
    STACKED leaves [n, Xh, E, F] with the layer in scalar memory (a
    per-layer [Xh, E, F] leaf is the n = 1 case): no layer's experts are
    sliced out of the stack for the call (1.9 GB a layer at DeepSeek-V2's
    widths, read and written once more a step), no relayout.
  * `moe_grouped_down_kernel`: grid (n tile, step), F whole, E tiled on
    the output's lanes.

The cost follows the live steps: at most (row tiles + held experts - 1),
about one a touched expert for a decode batch (its weights streamed once:
the HBM floor) and about (pairs / tile + touched experts) for a chunk.

Oracle and CPU path: ops/moe.py `expert_product_reference`
(plain XLA). `interpret=True` runs the kernels on the CPU for CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 1024 * 1024  # weight blocks of a 5120-wide model, twice


def tile_rows(rows: int, tile_q: int = 128) -> int:
    """Static row-tile height over the sorted pair buffer: 16-row (bf16
    sublane) aligned, capped at `tile_q`."""
    return max(16, min(tile_q, (rows + 15) // 16 * 16))


def lane_tile(width: int, cap: int) -> int:
    """The largest 128-multiple divisor of `width` that is <= cap."""
    t = min(width, cap)
    t -= t % 128
    while width % t:
        t -= 128
    return t


def group_steps(group_sizes: jnp.ndarray, m_tiles: int, tm: int):
    """The walk both kernels share, from the held experts' pair counts:
    (offsets [Xh+1], step_group [S], step_tile [S], live steps) with
    S = m_tiles + Xh - 1 static. Expert g meets tiles off[g] // tm ..
    (off[g+1] - 1) // tm; an empty expert meets none."""
    sizes = group_sizes.astype(jnp.int32)
    n = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    step_end = jnp.cumsum(tiles)
    live = step_end[-1]
    s = jnp.arange(m_tiles + n - 1, dtype=jnp.int32)
    g = jnp.minimum(
        jnp.searchsorted(step_end, s, side="right").astype(jnp.int32), n - 1
    )
    t = jnp.minimum(first[g] + s - (step_end - tiles)[g], m_tiles - 1)
    last = jnp.maximum(live - 1, 0)
    g = jnp.where(s < live, g, g[last])
    t = jnp.where(s < live, t, t[last])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, g, t, live.reshape(1)


def _own_rows(off_ref, g, tile, tm: int, width: int):
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (rows >= off_ref[g]) & (rows < off_ref[g + 1])


def _gate_up_kernel(
    off_ref, grp_ref, tile_ref, live_ref, layer_ref,  # scalar prefetch
    x_ref,   # [tm, tk]
    wg_ref,  # [tk, F] expert grp[s]'s gate rows of this k tile
    wu_ref,  # [tk, F]
    h_ref,   # [tm, F] out: act(gate) * up for the rows this expert owns
    acc_g, acc_u,  # [tm, F] float32
    *, tm: int, k_tiles: int, act: str,
):
    from xllm_service_tpu.ops.moe import _act_fn

    s, k = pl.program_id(0), pl.program_id(1)

    @pl.when(s < live_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_g[...] = jnp.zeros_like(acc_g)
            acc_u[...] = jnp.zeros_like(acc_u)

        x = x_ref[...]
        acc_g[...] += jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        acc_u[...] += jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

        @pl.when(k == k_tiles - 1)
        def _():
            own = _own_rows(off_ref, grp_ref[s], tile_ref[s], tm, h_ref.shape[1])
            h = (_act_fn(act)(acc_g[...]) * acc_u[...]).astype(h_ref.dtype)
            h_ref[...] = jnp.where(own, h, h_ref[...])


def _down_kernel(
    off_ref, grp_ref, tile_ref, live_ref, layer_ref,  # scalar prefetch
    h_ref,   # [tm, F]
    wd_ref,  # [F, tn]
    o_ref,   # [tm, tn]
    *, tm: int,
):
    s = pl.program_id(1)

    @pl.when(s < live_ref[0])
    def _():
        own = _own_rows(off_ref, grp_ref[s], tile_ref[s], tm, o_ref.shape[1])
        o = jnp.dot(h_ref[...], wd_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(own, o.astype(o_ref.dtype), o_ref[...])


@functools.partial(
    jax.jit, static_argnames=("act", "interpret", "tile_q", "k_cap", "n_cap")
)
def moe_grouped_kernel(
    xs: jnp.ndarray,           # [M, E] pair rows sorted by held expert
    group_sizes: jnp.ndarray,  # [Xh] int32 pairs of each held expert
    w_gate: jnp.ndarray,       # [n, Xh, E, F] the stack (or [Xh, E, F])
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,       # [n, Xh, F, E]
    act: str = "silu",
    interpret: bool = False,
    tile_q: int = 128,
    k_cap: int = 1280,
    n_cap: int = 1280,
    layer=None,                # int32 scalar when the leaves are stacks
) -> jnp.ndarray:
    """ys [M, E]: row r of expert g's span is SwiGLU_g(xs[r]). Rows past
    the last span are not written (the caller reads none of them)."""
    if w_gate.ndim == 3:
        w_gate, w_up, w_down, layer = w_gate[None], w_up[None], w_down[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    M, E = xs.shape
    _, Xh, _, F = w_gate.shape
    tm = tile_rows(M, tile_q)
    assert M % tm == 0, f"pair buffer [{M}] is not whole {tm}-row tiles"
    m_tiles = M // tm
    tk, tn = lane_tile(E, k_cap), lane_tile(E, n_cap)
    k_tiles, n_tiles = E // tk, E // tn
    meta = group_steps(group_sizes, m_tiles, tm) + (layer,)
    steps = m_tiles + Xh - 1
    params = dict(vmem_limit_bytes=VMEM_LIMIT)
    wbytes = w_gate.dtype.itemsize

    def kt(s, k, n):
        # A dead step stays on the last live step's LAST k block: walking
        # k there would fetch that expert's weights once more a dead step
        # (seen on the chip: the gate/up launch 6x the down launch's time).
        return jnp.where(s < n[0], k, k_tiles - 1)

    h = pl.pallas_call(
        functools.partial(_gate_up_kernel, tm=tm, k_tiles=k_tiles, act=act),
        name="moe_grouped_kernel",  # op name in the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda s, k, off, g, t, n, l: (t[s], kt(s, k, n))),
                pl.BlockSpec((None, None, tk, F),
                             lambda s, k, off, g, t, n, l: (l[0], g[s], kt(s, k, n), 0)),
                pl.BlockSpec((None, None, tk, F),
                             lambda s, k, off, g, t, n, l: (l[0], g[s], kt(s, k, n), 0)),
            ],
            out_specs=pl.BlockSpec((tm, F), lambda s, k, off, g, t, n, l: (t[s], 0)),
            scratch_shapes=[pltpu.VMEM((tm, F), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), **params
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * steps * tm * E * F,
            bytes_accessed=2 * steps * E * F * wbytes + 2 * M * (E + F),
            transcendentals=steps * tm * F,
        ),
        interpret=interpret,
    )(*meta, xs, w_gate, w_up)

    return pl.pallas_call(
        functools.partial(_down_kernel, tm=tm),
        name="moe_grouped_down_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles, steps),
            in_specs=[
                pl.BlockSpec((tm, F), lambda j, s, off, g, t, n, l: (t[s], 0)),
                pl.BlockSpec((None, None, F, tn),
                             lambda j, s, off, g, t, n, l: (l[0], g[s], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, s, off, g, t, n, l: (t[s], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, E), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), **params
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * steps * tm * E * F,
            bytes_accessed=steps * E * F * wbytes + 2 * M * (E + F),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*meta, h, w_down)
