"""Pallas TPU grouped ragged MoE expert dispatch: ONE kernel launch over
variable-size per-expert token groups.

The MoE serving shape the xLLM Technical Report's engine (arxiv
2510.14686) is built around, with the PR-9 ragged-attention design DNA
(ISSUE 15 tentpole): router top-k produces X token groups of dynamic,
wildly unequal sizes; instead of X per-expert matmul launches (dispatch
overhead and dead launches for empty experts) or a dense all-experts
einsum (compute ∝ total params instead of ACTIVE params), one launch
walks the grouped token buffer tile by tile and streams only the expert
weights the live rows in each tile actually need.

Contract (shared with ops.moe.moe_blockwise, the CPU/parity oracle):

  * tokens ride GROUPED: xg [G, E] is the capacity-padded per-expert
    layout — expert e's tokens occupy rows [e*cap, e*cap + occ[e]), in
    router-assignment order; rows past occ[e] (and the padding tail
    past Xl*cap) are DEAD and emit zeros. `cap` is the STATIC per-group
    capacity (the seg_lens analog — group offsets e*cap are fixed at
    trace time), `occ` the dynamic occupancy (the q_len analog;
    occ[e] == 0 = empty expert). ops.moe builds this layout in-graph
    from the router output (scatter by expert*cap + rank).
  * weights ride pre-split on the F axis so every DMA offset is a
    LEADING-dim index (mosaic_rules rule 2): w_gate/w_up
    [Xl, NF, E, FT], w_down [Xl, NF, FT, E] with NF*FT == F. The
    wrapper relayouts from the model's [Xl, E, F]/[Xl, F, E] leaves;
    a production checkpoint loader can persist this layout and skip
    the per-call transpose.

Design (the ragged-attention kernel's structure with expert-weight DMA
in place of KV-page DMA):

  * grid = (NT,): one program per TT-row tile of the grouped buffer.
    Tiles freely CROSS group boundaries (cap need not be a TT
    multiple), so the launch count depends only on G, not on how the
    router skewed the groups.
  * per tile, the kernel loops over the experts overlapping it (the
    range is STATIC — group offsets are static — and rides scalar
    prefetch like the ragged kernel's tile_start/tile_cnt), and per
    expert streams that expert's weights HBM→VMEM through a 2-slot
    double buffer, one [E, FT]+[E, FT]+[FT, E] f-chunk per inner step
    (F-chunking keeps VMEM residency at 6·E·FT·itemsize regardless of
    F; E itself is not tiled — DeepSeek-V3-scale E needs an E-tile
    axis before chip validation, noted in docs/MOE.md).
  * the whole [TT, E] x [E, FT] gate/up matmuls are ONE MXU issue per
    chunk; rows not owned by the current expert (other groups, dead
    capacity tail) mask their activations to 0 before the down-proj
    accumulation, so the accumulator needs no per-expert state. A
    tile whose overlap with an expert's LIVE prefix is empty skips
    that expert's DMA and compute entirely — with a balanced router
    the streamed/computed work tracks occ (≈ T·K rows, the ACTIVE
    params), not X·cap.
  * TPU grid programs run sequentially per core, so serializing a
    tile's experts costs nothing vs per-expert launches — the fusion
    buys one launch, expert skipping at tile granularity, and weight
    DMA overlapped with the previous chunk's matmuls.

Following the repo's opt-in-until-chip-validated convention the kernel
is NEW silicon surface: XLLM_MOE_KERNEL=1 opts in (XLLM_MOE_INTERPRET=1
drives it in interpret mode on CPU for CI), queued as moe-* cases for
the next chip window (docs/KERNELS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic


def tile_rows(group_rows: int, tile_q: int = 128) -> int:
    """Static tile height over the grouped token buffer: TT rows per
    program, 8-row (sublane) aligned, capped at `tile_q`."""
    r = (group_rows + 7) // 8 * 8
    return max(8, min(tile_q, r))


def f_chunk(F: int, cap: int = 512) -> int:
    """Static F-axis chunk: the largest 128-multiple divisor of F that is
    <= cap — one double-buffered [E, FT] weight slice per inner step."""
    ft = min(F, cap)
    ft -= ft % 128
    while F % ft:
        ft -= 128
    return ft


def _tile_expert_ranges(n_tiles: int, tt: int, cap: int, n_experts: int):
    """Static per-tile (first_expert, expert_count): group offsets are
    e*cap, so the experts overlapping tile t form a contiguous static
    range; tiles wholly in the padding tail carry (0, 0)."""
    first, cnt = [], []
    for t in range(n_tiles):
        lo, hi = t * tt, (t + 1) * tt
        f = min(lo // cap, n_experts)
        c = max(0, min(-(-hi // cap), n_experts) - f)
        first.append(f if c else 0)
        cnt.append(c)
    return first, cnt


def _moe_kernel(
    # scalar prefetch
    occ_ref,        # [Xl] SMEM — dynamic live rows per expert group
    tfirst_ref,     # [NT] SMEM — first expert overlapping each tile
    tcnt_ref,       # [NT] SMEM — experts overlapping each tile
    # inputs
    x_ref,          # [TT, E] VMEM — one tile of grouped token rows
    wg_hbm,         # [Xl, NF, E, FT] HBM
    wu_hbm,         # [Xl, NF, E, FT] HBM
    wd_hbm,         # [Xl, NF, FT, E] HBM
    # outputs + scratch
    o_ref,          # [TT, E] VMEM
    wg_buf,         # [2, E, FT] VMEM
    wu_buf,         # [2, E, FT] VMEM
    wd_buf,         # [2, FT, E] VMEM
    sems,           # DMA sems [2, 3]
    *,
    cap: int,
    tt: int,
    n_f: int,
    act: str,
):
    # The ONE activation selector (ops/moe.py) — kernel, oracle, and
    # dense path must stay in lockstep on activation semantics.
    from xllm_service_tpu.ops.moe import _act_fn

    t = pl.program_id(0)
    x = x_ref[...]  # [TT, E]
    row0 = t * tt
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tt, 1), 0)
    activate = _act_fn(act)

    def dmas(slot, e, c):
        return [
            mosaic.async_copy(
                mosaic.checked_at(wg_hbm, e, c),
                mosaic.checked_at(wg_buf, slot),
                sems.at[slot, 0],
            ),
            mosaic.async_copy(
                mosaic.checked_at(wu_hbm, e, c),
                mosaic.checked_at(wu_buf, slot),
                sems.at[slot, 1],
            ),
            mosaic.async_copy(
                mosaic.checked_at(wd_hbm, e, c),
                mosaic.checked_at(wd_buf, slot),
                sems.at[slot, 2],
            ),
        ]

    def expert_body(bi, acc):
        e = tfirst_ref[t] + bi
        lo = e * cap
        # Overlap of the expert's LIVE prefix with this tile: empty →
        # the whole f-chunk walk (DMA included) is skipped, which is
        # what makes compute track occupancy instead of X*cap.
        s = jnp.maximum(lo, row0)
        en = jnp.minimum(lo + occ_ref[e], row0 + tt)
        nc = jnp.where(en > s, n_f, 0)

        @pl.when(nc > 0)
        def _first():
            for d in dmas(0, e, 0):
                d.start()

        owned = (rows >= s) & (rows < en)  # [TT, 1]

        def f_body(c, acc):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < nc)
            def _prefetch():
                for d in dmas(jax.lax.rem(c + 1, 2), e, c + 1):
                    d.start()

            for d in dmas(slot, e, c):
                d.wait()
            gate = jax.lax.dot_general(
                x, wg_buf[slot],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [TT, FT] f32
            up = jax.lax.dot_general(
                x, wu_buf[slot],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            h = activate(gate) * up
            # Rows owned by OTHER experts (or dead) contribute exactly 0
            # to the accumulator — groups are disjoint, so each live row
            # is written by precisely one expert iteration.
            h = jnp.where(owned, h, 0.0)
            pv = jnp.dot(
                h.astype(wd_buf.dtype), wd_buf[slot],
                preferred_element_type=jnp.float32,
            )  # [TT, E] f32
            return acc + pv

        return jax.lax.fori_loop(0, nc, f_body, acc)

    acc0 = jnp.zeros((tt, x.shape[-1]), jnp.float32)
    acc = jax.lax.fori_loop(0, tcnt_ref[t], expert_body, acc0)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cap", "act", "interpret", "tile_q", "f_cap"),
)
def moe_grouped_dispatch_kernel(
    xg: jnp.ndarray,   # [G, E] grouped token rows (G = Xl*cap padded to TT)
    occ: jnp.ndarray,  # [Xl] int32 — live rows per expert group (<= cap)
    w_gate: jnp.ndarray,  # [Xl, E, F]
    w_up: jnp.ndarray,    # [Xl, E, F]
    w_down: jnp.ndarray,  # [Xl, F, E]
    cap: int,
    act: str = "silu",
    interpret: bool = False,
    tile_q: int = 128,
    f_cap: int = 512,
) -> jnp.ndarray:
    """One grouped ragged expert dispatch. Returns og [G, E] in xg.dtype
    with dead rows zeroed; the caller scatter-combines per-slot outputs
    by router weight (ops.moe.grouped_moe)."""
    G, E = xg.shape
    Xl, _, F = w_gate.shape
    TT = tile_rows(Xl * cap, tile_q)
    assert G % TT == 0 and G >= Xl * cap, (
        f"grouped buffer [{G}] must cover Xl*cap={Xl * cap} rows padded "
        f"to the {TT}-row tile (ops.moe builds this layout)"
    )
    FT = f_chunk(F, f_cap)
    NF = F // FT
    NT = G // TT
    tfirst, tcnt = _tile_expert_ranges(NT, TT, cap, Xl)

    # Leading-dim F split (mosaic rule 2: DMA offsets ride only untiled
    # leading dims): w_gate/w_up pay one relayout transpose per call —
    # the production loader can persist this layout — w_down's split is
    # a free reshape.
    wg = w_gate.reshape(Xl, E, NF, FT).transpose(0, 2, 1, 3)
    wu = w_up.reshape(Xl, E, NF, FT).transpose(0, 2, 1, 3)
    wd = w_down.reshape(Xl, NF, FT, E)

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((TT, E), lambda t, *_: (t, 0)),
            hbm,
            hbm,
            hbm,
        ],
        out_specs=pl.BlockSpec((TT, E), lambda t, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, E, FT), wg.dtype),
            pltpu.VMEM((2, E, FT), wu.dtype),
            pltpu.VMEM((2, FT, E), wd.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )
    kernel = functools.partial(
        _moe_kernel, cap=cap, tt=TT, n_f=NF, act=act,
    )
    wbytes = wg.dtype.itemsize
    return pl.pallas_call(
        kernel,
        name="moe_grouped_dispatch_kernel",  # op name in the device trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, E), xg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            # Upper bound: every grouped row live (the tile walk skips
            # dead spans at runtime).
            flops=6 * G * E * F,
            bytes_accessed=(
                2 * G * E * xg.dtype.itemsize + 3 * Xl * E * F * wbytes
            ),
            transcendentals=G * F,
        ),
        interpret=interpret,
    )(
        occ.astype(jnp.int32),
        jnp.asarray(tfirst, jnp.int32),
        jnp.asarray(tcnt, jnp.int32),
        xg, wg, wu, wd,
    )
