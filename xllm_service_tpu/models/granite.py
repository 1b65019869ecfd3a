"""The hybrid stack: state layers, GQA layers, window layers and blocks
with BOTH a state mixer and a GQA mixer in ONE stack, a top-k expert
block (beside a shared MLP where the family has one) in every layer
behind a dense prefix, or a dense MLP in every layer (`num_experts` 0),
over the same runtime as the other families. Five published families run
on it, and the difference between them is DATA of the configuration
(`layer_types`, the multipliers, the head): Granite 4.0-H (HF
`model_type: granitemoehybrid`: Mamba-2 state layers, a tied head, four
multipliers), Solar-Open2 (`solar_open2`: KDA state layers, a gate on
the GQA layers, an untied head, every multiplier 1), MiMo-V2-Flash
(`mimo_v2_flash`: window layers beside full GQA layers, rotary on part
of a head, key heads wider than value heads, a dense first layer,
sigmoid-scored experts and no shared one), Laguna (`laguna`: window and
full GQA layers that differ in QUERY heads, rotary lanes and rotary table,
a gate per head, a shared expert under a routed scale) and Falcon-H1 (`falcon_h1`:
every block the parallel kind, full rotary, two B/C groups, a dense MLP,
muP multipliers inside the projections) and MiniCPM-SALA
(`minicpm_sala`: block-sparse attention layers whose queries select their
pages beside Lightning linear-attention layers, a dense MLP, MiniCPM's muP
scalars on the four multipliers).

With `h` the residual stream, `rms` RMSNorm with a learned gain, and the
four multipliers of the configuration (each 1 by default):

    h0 = embedding_multiplier * E[token]
    every layer l:  h <- h + residual_multiplier * mixer_l(rms_1(h))
                    u  = rms_2(h)
                    h <- h + residual_multiplier * (moe(u) + shared(u))
    logits = (rms_f(h_L) @ head) / logits_scaling   (head = E^T where tied)

`mixer_l` is what `cfg.layer_types[l]` names; the pattern is DATA.

* "mamba": `[z | xBC | dt] = W_in u` (d_inner | d_inner + 2 G N | H); the
  depthwise causal convolution and silu over xBC, the selective scan over
  `[x | B | C]` (ops/mamba.py has both, the equations and the pools'
  layout); gate THEN norm, `y = rms_g(y * silu(z))` over each group's
  d_inner / G lanes; out `= W_out y`. No projection bias, a convolution
  bias.
* "kda": `q~, k~, v~ = W_q u, W_k u, W_v u`, side by side through the same
  depthwise causal convolution and silu; per head `q = l2norm(q~) /
  sqrt(d)`, `k = l2norm(k~)`; the decay per channel of the key `g =
  -exp(A_log) softplus(W_f2 W_f1 u + dt_bias)`, `beta = 2 sigmoid(w_b u)`;
  the gated delta rule over `(q, k, v, g, beta)` (ops/kda.py has it, in
  its recurrent and its chunk form); out `= W_o [sigmoid(W_g2 W_g1 u) *
  rms_o(o)]`, `rms_o` over a head's value lanes with one gain vector.
* "attention": GQA, no bias, scores scaled by `attention_multiplier` (0
  = head_dim**-0.5), causal, full, over the paged K/V pool; with
  `cfg.attn_gate` the output is gated per lane before `W_o`,
  `W_o [sigmoid(W_gate u) * attn]` (with `attn_gate_per_head` per head:
  `W_gate` [E, Hq]). Rotary on lanes [0, `rotary_dim`) of
  every q and k head (0: none; Granite and Solar-Open2 are NoPE by
  construction), theta `rope_theta`, or with `rope_scaling_type` the
  scaled table of ops/rope.rope_parameters over those lanes, its
  attention factor on cos and sin (`rotary_tables`); values
  `attn_value_scale * W_v u`, `attn_v_head_dim` lanes a head where set.
* "window": the same mixer with `window_kv_heads` KV heads (and
  `window_num_heads` query heads, `window_rotary_dim` lanes, where set),
  plain theta `window_rope_theta`, over the last `sliding_window` positions
  (j > i - window) and, with `window_sink`, a learned logit a query head
  in the softmax's denominator whose mass is dropped, over a paged pool
  of its own.
* "parallel" (Falcon-H1): BOTH of the above on the same normed rows `u`,
  each behind its input multiplier and scaled by its output multiplier,
  in ONE residual add:
  `h <- h + attention_out_multiplier * Attn(attention_in_multiplier * u)
  + ssm_out_multiplier * Mamba(ssm_in_multiplier * u)`; inside the Mamba
  mixer the z, x, B, C and dt lanes of `W_in`'s result are scaled by
  `ssm_multipliers[0..4]`, inside attention k by `key_multiplier`. The
  layer writes a state slot, its convolution rows AND its K/V rows: all
  four carried pools move in one scan body. Where the multipliers land in
  the program (the reference keeps them unfolded): `ssm_in_multiplier`
  and `ssm_multipliers` are ONE `[d_in + conv + H]` vector on the float32
  result of `W_in` (`_ssm_lane_scales`; the weights stay as installed);
  `attention_in_multiplier` and `key_multiplier` are in the score scale
  (`_scale`: the product is linear in q and k, so the cache holds the
  plain `W_k u` and rounds as every family's does) and, for v, in the
  branch's scalar beside `attention_out_multiplier` (`_branch_scales`).
* "lightning" (MiniCPM-SALA's `lightning-attn`): `q, k, v = W_q u, W_k u,
  W_v u` a head of `lightning_d_head` lanes; `q = rope(rms_q(q)) /
  sqrt(d)`, `k = rope(rms_k(k))` (rotary on EVERY lane at `rope_theta`:
  a state mixer reads its rows' positions); `S <- lambda S + k^T v`, `o =
  q S` over a float32 state slot (ops/lightning.py: the recurrence for a
  decode row, the chunked form in sub-chunks for a prefill chunk), the
  decay a CONSTANT of the head and of the layer's PUBLISHED index
  (`layer_ids`, `published_layers`); out `= W_o [sigmoid(W_g u) *
  rms_o(o)]`, `rms_o` over a head's lanes with a gain over all of them.
  No convolution: the stack's fourth pool is free, and holds:
* "sparse" (MiniCPM-SALA's `minicpm4`, InfLLM-V2): the "attention" mixer
  with QK-norm (`q_norm`, `k_norm` in the `attn` stack; the cache holds
  the normed key), NoPE, the gate, over the SAME K/V pool, plus a pool of
  compressed keys (the mean of `sparse_kernel_size` keys every
  `sparse_kernel_stride`) under the same block table. A row whose context
  is at most `sparse_dense_len` attends all of it through the launches a
  full layer takes; a row past it attends the `sparse_topk` blocks its
  KV head's query group selects (ops/sparse_attention.py: stage 1 in XLA,
  stage 2 the decode launch over a virtual table, a KV head a row). The
  switch is per ROW, so a token's output does not depend on how its
  request was cut into chunks.
* experts: `llama.moe_route` (softmax or sigmoid scores, top-k,
  renormalised) and the grouped product over the experts HELD
  (`cfg.experts_held`), the shared MLP where `n_shared_experts` > 0
  (`llama._mlp_block`); the first `first_k_dense_replace` layers have a
  dense SwiGLU of `intermediate_size` in the block's place; with
  `num_experts` 0 every layer has that dense SwiGLU (its leaves under
  `layers`), the gate's pre-activation times `mlp_multipliers[0]`
  (llama._mlp) and the down product times `mlp_multipliers[1]` (in the
  residual add).

**A sequence's two kinds of memory.** The carried caches are a pair of
pairs, `k_caches = (K, S)` and `v_caches = (V, conv)`: K and V stacks
`[La, N, Hkv, BS, D]` over the ATTENTION layers only, in paged blocks that
grow with the context, and the state and convolution pools over the
STATE layers (of either kind), one slot a sequence for its life
(`state_shapes`). All four ride the carry of every segment's scan
(llama.py `_scan_layers`' rule: no scan slices a pool in or stacks it
out). A stack with WINDOW layers carries their K and V stacks
`[Lw, Nw, Hkv_w, BS, .]` in the state pools' places (it has no state
layers): a second paged pool with a block table of its own, which rides
the step's tables behind the full layers' columns (`_split_windows`) and
is indexed by position // BS like them; the engine frees a window block
once every position in it is `sliding_window` behind the sequence and
zeroes its entry, and the kernels' walk starts at the first in-window
block, so a freed entry is never read. Key rows wider than a 128-lane
tile are padded with zero lanes to whole tiles in the pool
(`key_lanes`), value rows keep their own width. A decode row's slot is
its row index; a prefill row names its slot
in the LAST column of its block table (slot + 1; 0 = a padding row),
which the executor appends for a family that has both kinds
(runtime/executor.py `slot_column`).

Runs of equal layer kind are one scan each (`_segments`), and a pattern
that repeats is one scan over its period (`_period`); the parameter
tree keeps what every layer has under `layers` (norms, router, experts,
shared MLP: L entries) and the mixers under their kind's stack
(`MIXER_STACKS`: `mamba`, `kda`, `attn`), each scan indexing the stacks it
needs; the expert leaves (and the router's) have an entry a ROUTED layer
and the dense prefix its own stack (`dense_layers`). There is ONE layer body
(`_layer`) and ONE segment scan (`_run_layers`) for every kind and every
family; what differs between
the state-layer kinds is one row of `STATE_KINDS` each (mixer, pool
shapes, kernel eligibility, parameter stack).

Same step surface as llama.py. The mixed step runs ONE batch of
R + P*Lpad token rows through every matmul (as models/deepseek.py), so a
touched expert streams once a step.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import LAYER_MIXERS, PARALLEL_KIND, ModelConfig
from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kda as kda_ops
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops import kv_write as kv_write_ops
from xllm_service_tpu.ops import lightning as lightning_ops
from xllm_service_tpu.ops import mamba as mamba_ops
from xllm_service_tpu.ops import moe as moe_ops
from xllm_service_tpu.ops import rope as rope_ops
from xllm_service_tpu.ops import sparse_attention as sparse_ops
from xllm_service_tpu.ops.attention import (
    attention_routes as pool_routes,
    mixed_attention,
    paged_attention,
    prefill_attention,
)
from xllm_service_tpu.ops.norms import block_norm, rms_norm
from xllm_service_tpu.ops.quant import wdtype, wt

Params = Dict

NUM_CACHES = 2  # K and V (each paired with a state pool on the carry)
QUANTIZABLE_WEIGHT_LEAVES = llama.QUANTIZABLE_WEIGHT_LEAVES + ("w_in", "w_out", "w_ogate")
# a layer kind's stack of the parameter tree
MIXER_STACKS = {"mamba": "mamba", "kda": "kda", "attention": "attn", "window": "attn_w",
                "lightning": "lightning"}
# the device region of a layer kind's residual add (obs.spans.DEVICE_REGIONS);
# the parallel kind's ONE add of both branches is the state mixer's
MIXER_REGIONS = {"mamba": "state_mixer", "kda": "state_mixer", "attention": "attn_proj",
                 "window": "attn_proj", PARALLEL_KIND: "state_mixer",
                 "lightning": "state_mixer", "sparse": "attn_proj"}


def key_lanes(cfg: ModelConfig) -> int:
    """Lanes of a key row in the pool: the head's width, padded with zero
    lanes to whole 128-lane tiles where it is wider than one (a DMA of
    the kernels moves whole tiles, and the chip lays a 192-lane row out
    in 256 anyway)."""
    D = cfg.head_dim
    return D if D <= 128 else -(-D // 128) * 128


def cache_row_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, row_dim) of one paged-cache KEY row of an attention layer."""
    return cfg.num_kv_heads, key_lanes(cfg)


def pool_shapes(cfg: ModelConfig, blocks: int, window_blocks: int, block_size: int):
    """((K, V) of the full layers, (K, V) of the window layers): the four
    paged stacks of a stack with window layers."""
    def pair(layers, n, heads):
        return ((layers, n, heads, block_size, key_lanes(cfg)),
                (layers, n, heads, block_size, cfg.value_head_dim))

    return (pair(cfg.num_attention_layers, blocks, cfg.num_kv_heads),
            pair(cfg.num_window_layers, window_blocks, cfg.window_kv_heads))


def attention_routes(
    cfg: ModelConfig, k_caches, tp: int = 1, prefill_rows: int = 0
):
    """The decisions for the attention launches over this family's paged
    pools: the full layers' and, in a window family, the window layers'
    (a sink takes the verify shapes off the multi-query kernel). The
    mixer pads its queries to the pool's key row, so that is their width."""

    def over(K, kind="attention", sinks=False):
        return pool_routes(K, cfg.attn_heads(kind), kvc.raw(K).shape[-1], tp=tp, sinks=sinks)

    full = over(k_caches[0])
    if not cfg.num_window_layers:
        return (full,)
    return full, over(k_caches[1], "window", cfg.window_sink)


def kernel_report(
    cfg: ModelConfig, k_caches, tp: int = 1, prefill_rows: int = 0
) -> dict:
    """The full layers' launches by name, and the second pool's route:
    `window` in a window family, `state` beside a state pool."""
    full, *window = attention_routes(cfg, k_caches, tp)
    rep = full.report()
    if not window:
        rep["state"] = state_route(cfg, k_caches[1])
        if cfg.num_sparse_layers:
            # stage 1 is XLA on every platform; stage 2 is the decode
            # launch over the selected pages, for decode rows and for a
            # chunk's rows past dense_len (rows under it: `prefill`)
            rep["sparse"] = f"select-xla+{rep['decode']}"
        return rep
    (w,) = window  # the decode and flash kernels under their window names,
    # their gather and blockwise twins, or the pair where a hatch split them
    if w.decode == w.prefill:
        rep["window"] = f"window-{'pallas' if w.decode else 'xla'}"
    else:
        rep["window"] = f"window-{w.report()['mixed']}"
    # by kind: the launch, the query group, the window in blocks of the
    # pool and the rotary table its rows take
    BS = kvc.raw(k_caches[1]).shape[-2]
    tables = rotary_tables(cfg)
    rep["kinds"] = {
        kind: {
            "launch": launch,
            "query_group": cfg.attn_heads(kind) // kv_heads,
            "window_blocks": -(-cfg.sliding_window // BS) if kind == "window" else 0,
            "rotary": tables[kind].name,
        }
        for kind, launch, kv_heads in (
            ("attention", rep["mixed"], cfg.num_kv_heads),
            ("window", rep["window"], cfg.window_kv_heads),
        )
    }
    return rep


# rope_scaling types whose frequencies are one table for every position
# (ops/rope.rope_parameters); "longrope" chooses short or long by position
ONE_TABLE_SCALINGS = ("linear", "dynamic", "llama3", "yarn")


class RotaryTable(NamedTuple):
    """What an attention kind's q and k rotate by: `lanes` of a head from
    lane 0 (0: none), at plain `theta`, or by a scaled table `inv_freq`
    [lanes / 2] whose `scale` multiplies cos and sin."""

    lanes: int
    theta: float
    inv_freq: Optional[np.ndarray] = None
    scale: float = 1.0
    scaling: str = ""  # "yarn x64": the scaled table's kind and factor

    @property
    def name(self) -> str:
        if not self.lanes:
            return "none"
        return f"{self.scaling or 'plain'} / {self.lanes} lanes"


@functools.lru_cache(maxsize=None)
def rotary_tables(cfg: ModelConfig) -> Dict[str, RotaryTable]:
    """The rotary table of each attention kind of the stack, made once a
    configuration: the full layers' over `rotary_dim` lanes at
    `rope_theta`, scaled where `rope_scaling_type` says (YaRN's
    frequencies and attention factor over THOSE lanes:
    ops/rope.rope_parameters at dim = rotary_dim); the window layers' over
    `window_rotary_dim` lanes, plain at `window_rope_theta`."""
    lanes = cfg.attn_rotary_dim("attention")
    full = RotaryTable(lanes, cfg.rope_theta)
    if cfg.rope_scaling_type not in ("",) + ONE_TABLE_SCALINGS:
        raise ValueError(
            f"rope_scaling_type {cfg.rope_scaling_type!r} is not built for the hybrid "
            f"stack: its attention layers rotate by ONE table a kind "
            f"({', '.join(ONE_TABLE_SCALINGS)}), and this type picks a table by position"
        )
    if lanes and cfg.rope_scaling_type:
        inv_freq, scale = rope_ops.rope_parameters(lanes, cfg)
        full = RotaryTable(
            lanes, cfg.rope_theta, inv_freq, scale,
            f"{cfg.rope_scaling_type} x{cfg.rope_scaling_factor:g}",
        )
    return {"attention": full,
            "window": RotaryTable(cfg.attn_rotary_dim("window"), cfg.window_rope_theta)}


def state_shapes(cfg: ModelConfig, slots: int, blocks: int = 0):
    """(state pool shape, convolution pool shape) over the stack's state
    layers, in their kind's layout (ops/mamba.py, ops/kda.py); a stack
    with sparse layers has no convolution and holds the compressed-key
    pool of `blocks` pages in its place (ops/sparse_attention.py)."""
    state, conv = STATE_KINDS[cfg.state_layer_kind].shapes(cfg, slots)
    if cfg.num_sparse_layers:
        conv = sparse_ops.pool_shape(cfg, blocks)
    return state, conv


def state_route(cfg: ModelConfig, state) -> str:
    """Which route the decode update of `state` (the state pool) takes:
    "<kind>-pallas" (the kind's update kernel) or "<kind>-xla". For
    "kda" the same predicate routes a prefill chunk's chunk form, so
    "kda-pallas" says the update AND the chunk form are the kernels
    (`kda_update_kernel`, `kda_chunk_kernel`) and "kda-xla" that neither is."""
    kind = cfg.state_layer_kind
    on_kernel = STATE_KINDS[kind].on_kernel(cfg, state)
    return f"{kind}-{'pallas' if on_kernel else 'xla'}"


class Segment(NamedTuple):
    kind: str  # one of MIXER_STACKS
    first: int  # the run's first layer, of all layers
    kind_first: int  # ... and of the layers of its kind
    n: int
    dense: bool = False  # a run of the dense prefix: no experts


def _segments(cfg: ModelConfig) -> List[Segment]:
    """Runs of equal layer kind; the dense prefix ends a run."""
    out: List[Segment] = []
    seen = dict.fromkeys(LAYER_MIXERS, 0)
    for l, kind in enumerate(cfg.layer_types):
        if kind not in seen:
            raise ValueError(f"layer_types[{l}] = {kind!r}: one of {sorted(seen)}")
        dense = l < cfg.first_k_dense_replace
        if out and out[-1].kind == kind and out[-1].dense == dense:
            out[-1] = out[-1]._replace(n=out[-1].n + 1)
        else:
            out.append(Segment(kind, l, seen[kind], 1, dense))
        seen[kind] += 1
    return out


def _period(segs: List[Segment]) -> Tuple[List[Segment], int]:
    """(the runs of one period, how often it repeats): the shortest
    prefix of `segs` that the whole list repeats, kind by kind and length
    by length (1: no repeat). A pattern that repeats is ONE scan over its
    period, so a step program holds each kind's layer body once and not
    once a repeat: that is its size in the compile cache and its seconds
    to compile."""
    shape = [(s.kind, s.n, s.dense) for s in segs]
    for m in range(1, len(segs) // 2 + 1):
        if len(segs) % m == 0 and shape == shape[:m] * (len(segs) // m):
            return segs[:m], len(segs) // m
    return segs, 1


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    if len(cfg.layer_types) != cfg.num_layers:
        raise ValueError("hybrid stack: one layer type a layer")
    if not cfg.is_moe and cfg.first_k_dense_replace:
        raise ValueError("hybrid stack: a dense prefix stands before ROUTED layers")
    if cfg.num_window_layers and cfg.state_layer_kind:
        raise ValueError("hybrid stack: window layers' pools ride in the state pools' places")
    if PARALLEL_KIND in cfg.layer_types and set(cfg.layer_types) != {PARALLEL_KIND}:
        raise ValueError("hybrid stack: the parallel kind's two mixer stacks are indexed "
                         "by the layer, so every layer of the stack is of that kind")
    if cfg.num_sparse_layers:
        if cfg.mixer_layers("attention") != cfg.num_sparse_layers or cfg.state_layer_kind != "lightning":
            raise ValueError("hybrid stack: sparse layers index the attention stack and keep "
                             "their compressed keys in the convolution pool's place, so they "
                             "stand beside lightning layers alone")
        sparse_ops.selection_of(cfg)
    E, L, kd = cfg.hidden_size, cfg.num_layers, cfg.first_k_dense_replace
    Ls, La, Lw = cfg.num_state_layers, cfg.num_attention_layers, cfg.num_window_layers
    Hq, Hkv, D, Dv = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.value_head_dim
    X, Xh, Fm = cfg.num_experts, cfg.held_experts[1], cfg.moe_intermediate_size
    Fs, Lm = cfg.n_shared_experts * Fm, L - kd
    # (the first twenty keys and their order are Granite's draw since PR 42)
    keys = itertools.chain(
        jax.random.split(key, 20), jax.random.split(jax.random.fold_in(key, 20), 20)
    )

    def w(shape, fan_in):
        z = jax.random.normal(next(keys), shape, jnp.float32)
        return (z / jnp.sqrt(fan_in)).astype(dtype)

    ones = lambda shape: jnp.ones(shape, jnp.float32)
    params = {
        "embed": w((cfg.vocab_size, E), E),
        "final_norm": ones((E,)),
        "layers": {"attn_norm": ones((L, E)), "mlp_norm": ones((L, E))},
    }
    if cfg.is_moe:
        params["layers"].update({
            "router": w((Lm, E, X), E),
            "w_gate": w((Lm, Xh, E, Fm), E), "w_up": w((Lm, Xh, E, Fm), E),
            "w_down": w((Lm, Xh, Fm, E), Fm),
        })
    else:  # a dense SwiGLU in every layer
        F = cfg.intermediate_size
        params["layers"].update({
            "w_gate": w((L, E, F), E), "w_up": w((L, E, F), E), "w_down": w((L, F, E), F),
        })
    if Fs:
        params["layers"].update({
            "w_sh_gate": w((Lm, E, Fs), E), "w_sh_up": w((Lm, E, Fs), E),
            "w_sh_down": w((Lm, Fs, E), Fs),
        })
    if cfg.state_layer_kind:
        params[MIXER_STACKS[cfg.state_layer_kind]] = STATE_KINDS[cfg.state_layer_kind].init(
            cfg, w, ones, Ls
        )

    def gqa(layers, kv_heads, heads=Hq):
        return {
            "wq": w((layers, E, heads * D), E), "wk": w((layers, E, kv_heads * D), E),
            "wv": w((layers, E, kv_heads * Dv), E),
            "wo": w((layers, heads * Dv, E), heads * Dv),
        }

    if cfg.attn_gate_per_head and not cfg.attn_gate:
        raise ValueError("attn_gate_per_head says which form attn_gate takes: set attn_gate too")

    def gate(layers, heads=Hq):  # a sigmoid a lane, or a head
        return w((layers, E, heads if cfg.attn_gate_per_head else heads * D), E)

    params["attn"] = gqa(La, Hkv)
    if cfg.attn_gate:
        params["attn"]["w_ogate"] = gate(La)
    if cfg.num_sparse_layers and cfg.qk_norm:
        params["attn"].update({"q_norm": ones((La, D)), "k_norm": ones((La, D))})
    if Lw:
        Hw = cfg.attn_heads("window")
        params["attn_w"] = gqa(Lw, cfg.window_kv_heads, Hw)
        if cfg.attn_gate:
            params["attn_w"]["w_ogate"] = gate(Lw, Hw)
        if cfg.window_sink:
            params["attn_w"]["sink"] = w((Lw, Hw), 1.0).astype(jnp.float32)
    if cfg.topk_method == "noaux_tc":  # the router's selection bias
        params["layers"]["router_bias"] = w((Lm, X), 100.0).astype(jnp.float32)
    if kd:
        F = cfg.intermediate_size
        params["dense_layers"] = {
            "w_gate": w((kd, E, F), E), "w_up": w((kd, E, F), E), "w_down": w((kd, F, E), F),
        }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((E, cfg.vocab_size), E)
    return params


def _wd(params: Params):
    return wdtype(params["attn"]["wo"])


@region("embed")
def _embed(params: Params, cfg: ModelConfig, token_ids) -> jnp.ndarray:
    x = params["embed"][token_ids].astype(jnp.float32) * cfg.embedding_multiplier
    return x.astype(_wd(params))


@region("head")
def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return _head_scaled(cfg, llama._unembed(params, cfg, x))


def _head_scaled(cfg: ModelConfig, logits):
    logits = logits / cfg.logits_scaling
    return logits if cfg.lm_head_multiplier == 1.0 else logits * cfg.lm_head_multiplier


def _scale(cfg: ModelConfig) -> float:
    """The score scale: with muP multipliers q is `W_q (m_in u)` and k
    `key_multiplier W_k (m_in u)`, and the product is linear in both."""
    return (cfg.attention_multiplier or cfg.head_dim ** -0.5) * (
        cfg.key_multiplier * cfg.attention_in_multiplier ** 2
    )


def _add(cfg: ModelConfig, x, y, scale: float = 1.0):
    """x + residual_multiplier * scale * y, the product in float32."""
    return x + (y.astype(jnp.float32) * (cfg.residual_multiplier * scale)).astype(x.dtype)


def _ffn_scale(cfg: ModelConfig) -> float:
    """`mlp_multipliers[1]`, on the dense MLP's down product (1 without)."""
    return cfg.mlp_multipliers[1] if cfg.mlp_multipliers else 1.0


def _branch_scales(cfg: ModelConfig) -> Tuple[float, float]:
    """What the parallel kind's (attention, state) branches are scaled by
    on the way into the residual add: each output multiplier, and the
    attention branch's input multiplier for its VALUES (q's and k's share
    is in `_scale`; the state branch's is in `_ssm_lane_scales`)."""
    return (cfg.attention_out_multiplier * cfg.attention_in_multiplier,
            cfg.ssm_out_multiplier)


def _ssm_lane_scales(cfg: ModelConfig):
    """The `[d_in + conv + H]` vector on `W_in`'s float32 result:
    `ssm_in_multiplier` (the product is linear in its input) times
    `ssm_multipliers[0..4]` on the z, x, B, C and dt lanes. None where
    every one is 1: the product is then left as it is."""
    if cfg.ssm_in_multiplier == 1.0 and not cfg.ssm_multipliers:
        return None
    d_in, gn = cfg.mamba_d_inner, cfg.mamba_n_groups * cfg.mamba_d_state
    mup = np.repeat(
        np.asarray(cfg.ssm_multipliers or (1.0,) * 5, np.float64),
        (d_in, d_in, gn, gn, cfg.mamba_n_heads),
    )
    return jnp.asarray(mup * cfg.ssm_in_multiplier, jnp.float32)


def _ssm_in(lp, cfg: ModelConfig, h):
    """`[z | xBC | dt]` of normed rows h [T, E], float32: `W_in`'s product,
    its lanes scaled (`_ssm_lane_scales`)."""
    d_in, Cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxd = jnp.einsum("te,ef->tf", h, wt(lp["w_in"]), preferred_element_type=jnp.float32)
    mup = _ssm_lane_scales(cfg)
    if mup is not None:
        zxd = zxd * mup
    return zxd[:, :d_in], zxd[:, d_in:d_in + Cd], zxd[:, d_in + Cd:]


# ------------------------------------------------------------ the halves


class _Dec(NamedTuple):
    """The decode rows of a step: flat rows [0, R)."""

    R: int
    tables: jnp.ndarray  # [R, CB]
    seq_lens: jnp.ndarray  # [R]: context with this token; 0 = inactive
    active: jnp.ndarray  # [R] bool
    plan: object  # where the rows' K/V go
    use_kernel: Optional[bool]
    positions: Optional[jnp.ndarray] = None  # [R]: the rows' positions (rotary)
    tables_w: Optional[jnp.ndarray] = None  # [R, CB]: the window pool's table
    plan_w: object = None  # ... and where the rows' K/V go in it


class _Pf(NamedTuple):
    """The prefill chunks of a step: flat rows [R, R + P*Lpad)."""

    P: int
    Lpad: int
    tables: jnp.ndarray  # [P, CB]: the KV blocks' columns alone
    slots: jnp.ndarray  # [P]: state slot (-1 = a padding row)
    start: jnp.ndarray  # [P]
    length: jnp.ndarray  # [P]
    plan: object
    positions: Optional[jnp.ndarray] = None  # [P * Lpad]: the rows' positions (rotary)
    tables_w: Optional[jnp.ndarray] = None  # [P, CB]: the window pool's table
    plan_w: object = None


def _split_tables(block_tables):
    """A prefill row's table -> (its KV blocks' columns, its state slot)."""
    return block_tables[:, :-1], block_tables[:, -1].astype(jnp.int32) - 1


def _split_windows(block_tables):
    """A row's table of a stack with window layers -> (its full layers'
    columns, its window layers' columns): two tables of one width, each
    indexed by position // BS (runtime/executor.py `window_tables`)."""
    CB = block_tables.shape[1] // 2
    return block_tables[:, :CB], block_tables[:, CB:]


@region("state_mixer")
def _mamba_mixer(lp, cfg: ModelConfig, h, m, S, conv, dec: Optional[_Dec],
                 pf: Optional[_Pf]):
    """The Mamba-2 mixer over flat rows h [T, E] (decode rows first, then
    the chunks' rows), Mamba layer `m`: returns (out [T, E], S', conv')."""
    H, Pd, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    d_in, Cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    f32 = jnp.float32
    z, xbc, dt = _ssm_in(lp, cfg, h)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"].astype(f32))
    R = dec.R if dec is not None else 0

    def parts(c):  # [.., Cd] -> x [.., H, Pd], B, C [.., G, N]
        lead = c.shape[:-1]
        return (c[..., :d_in].reshape(*lead, H, Pd),
                c[..., d_in:d_in + G * N].reshape(*lead, G, N),
                c[..., d_in + G * N:].reshape(*lead, G, N))

    ys = []
    if dec is not None:
        c, conv = mamba_ops.conv_decode(conv, m, dec.active, xbc[:R], lp["conv_w"], lp["conv_b"])
        x, B, C = parts(c)
        y, S = mamba_ops.decode_update(
            S, m, dec.active, x, dt[:R], A, B, C, lp["D"], use_kernel=dec.use_kernel
        )
        ys.append(y.reshape(R, d_in))
    if pf is not None:
        c, conv = mamba_ops.conv_chunk(
            conv, m, pf.slots, pf.start, pf.length,
            xbc[R:].reshape(pf.P, pf.Lpad, Cd), lp["conv_w"], lp["conv_b"],
        )
        x, B, C = parts(c)
        y, S = mamba_ops.chunk_update(
            S, m, pf.slots, pf.start, pf.length, x,
            dt[R:].reshape(pf.P, pf.Lpad, H), A, B, C, lp["D"],
        )
        ys.append(y.reshape(pf.P * pf.Lpad, d_in))
    y = jnp.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
    return _gated_out(lp, cfg, y, z), S, conv


@region("state_mixer")
def _gated_out(lp, cfg: ModelConfig, y, z):
    """rms_g(y * silu(z)) over each group's lanes, then W_out."""
    G = cfg.mamba_n_groups
    g = (y * jax.nn.silu(z)).reshape(y.shape[0], G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    g = g.reshape(y.shape) * lp["gate_norm"]
    w_out = wt(lp["w_out"])
    return jnp.einsum("tf,fe->te", g.astype(w_out.dtype), w_out)


def _kda_inputs(lp, cfg: ModelConfig, h):
    """What a KDA layer projects from normed rows h [T, E]: (the
    convolution's input [q~ | k~ | v~] [T, 3 H d], the log decay g
    [T, H, d] <= 0, beta [T, H], the output gate [T, H, d]), float32.
    Every product is fenced (llama._plain_product): its consumers are
    head-batched."""
    T = h.shape[0]
    H, d = cfg.kda_n_heads, cfg.kda_d_head
    f32 = jnp.float32

    def through(*names):  # h through a leaf, or through a low-rank pair
        y = h
        for n in names:
            y = llama._plain_product(jnp.einsum(
                "te,ef->tf", y.astype(h.dtype), wt(lp[n]), preferred_element_type=f32
            ))
        return y

    qkv = jnp.concatenate([through("wq"), through("wk"), through("wv")], axis=-1)
    # (a head's rate on its d lanes, flat: the rows stay (token, lane) tiles for the chunk kernel)
    rate = jnp.repeat(-jnp.exp(lp["A_log"].astype(f32)), d)
    g = (rate * jax.nn.softplus(through("w_f1", "w_f2") + lp["dt_bias"])).reshape(T, H, d)
    beta = (2.0 if cfg.kda_neg_eigval else 1.0) * jax.nn.sigmoid(through("w_beta"))
    gate = jax.nn.sigmoid(through("w_g1", "w_g2")).reshape(T, H, d)
    return qkv, g, beta, gate


@region("state_mixer")
def _kda_out(lp, cfg: ModelConfig, o, gate):
    """W_o [gate * rms_o(o)]: o, gate [T, H, d]."""
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = (gate * y * lp["o_norm"]).reshape(o.shape[0], -1)
    wo = wt(lp["wo"])
    return jnp.einsum("tf,fe->te", y.astype(wo.dtype), wo)


@region("state_mixer")
def _kda_mixer(lp, cfg: ModelConfig, h, m, S, conv, dec: Optional[_Dec],
               pf: Optional[_Pf]):
    """The KDA mixer over flat rows h [T, E] (decode rows first, then the
    chunks' rows), KDA layer `m`: returns (out [T, E], S', conv')."""
    H, d, Cd = cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_conv_dim
    qkv, g, beta, gate = _kda_inputs(lp, cfg, h)
    # cut by rows while flat: the chunk's rows reach the kernel in the tiles they lie in
    g = g.reshape(-1, H * d)
    R = dec.R if dec is not None else 0
    os = []
    if dec is not None:
        c, conv = mamba_ops.conv_decode(conv, m, dec.active, qkv[:R], lp["conv_w"], lp["conv_b"])
        o, S = kda_ops.decode_update(
            S, m, dec.active, *kda_ops.qkv_heads(c, H, d), g[:R].reshape(R, H, d), beta[:R],
            use_kernel=dec.use_kernel,
        )
        os.append(o)
    if pf is not None:
        c, conv = mamba_ops.conv_chunk(
            conv, m, pf.slots, pf.start, pf.length,
            qkv[R:].reshape(pf.P, pf.Lpad, Cd), lp["conv_w"], lp["conv_b"],
        )
        o, S = kda_ops.chunk_update(
            S, m, pf.slots, pf.start, pf.length, c,
            g[R:].reshape(pf.P, pf.Lpad, H, d), beta[R:].reshape(pf.P, pf.Lpad, H),
            use_kernel=dec.use_kernel if dec is not None else None,
        )
        os.append(o.reshape(pf.P * pf.Lpad, H, d))
    o = jnp.concatenate(os, axis=0) if len(os) > 1 else os[0]
    return _kda_out(lp, cfg, o, gate), S, conv


@region("attn_proj")
def _qkv(lp, cfg: ModelConfig, h, kind="attention", positions=None):
    """h [T, E] -> q [T, Hq, D], k [T, Hkv, D], v [T, Hkv, Dv] of an
    attention layer of `kind` (its own query heads): no bias; rotary on
    the kind's first lanes of q and k at `positions` [T] by the kind's
    table (`rotary_tables`: 0 lanes: none); the values times
    `attn_value_scale`."""
    T = h.shape[0]
    window = kind == "window"
    Hkv = cfg.window_kv_heads if window else cfg.num_kv_heads
    fence = llama._plain_product  # the attention kernels and the K/V write take heads
    q = fence(jnp.einsum("te,eh->th", h, wt(lp["wq"]))).reshape(
        T, cfg.attn_heads(kind), cfg.head_dim)
    k = fence(jnp.einsum("te,eh->th", h, wt(lp["wk"]))).reshape(T, Hkv, cfg.head_dim)
    v = fence(jnp.einsum("te,eh->th", h, wt(lp["wv"]))).reshape(T, Hkv, cfg.value_head_dim)
    if "q_norm" in lp:  # QK-norm: a sparse layer's (the cache holds the normed key)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    table = rotary_tables(cfg)[kind]
    if table.inv_freq is not None:
        q, k = (rope_ops.apply_partial_rope_table(
            t, positions, table.inv_freq, table.scale, table.lanes) for t in (q, k))
    elif table.lanes:
        q = rope_ops.apply_partial_rope(q, positions, table.theta, table.lanes)
        k = rope_ops.apply_partial_rope(k, positions, table.theta, table.lanes)
    if cfg.attn_value_scale != 1.0:
        v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
    return q, k, v


def _row_positions(dec: Optional[_Dec], pf: Optional[_Pf]):
    """The positions of a step's flat rows: decode rows, then the chunks'."""
    positions = [half.positions for half in (dec, pf) if half is not None]
    return jnp.concatenate(positions) if len(positions) > 1 else positions[0]


def _pad_lanes(x, lanes: int):
    """Zero lanes behind a head's own, up to the pool's key row."""
    if x.shape[-1] == lanes:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, lanes - x.shape[-1]),))


def _gated(lp, cfg: ModelConfig, h, o):
    """The attention output o [T, Hq D] through the layer's gate,
    sigmoid(W_gate h) per lane (or per head: `W_gate` [E, Hq], a head's
    sigmoid on all of its lanes), where the configuration has one."""
    if not cfg.attn_gate:
        return o
    gate = jax.nn.sigmoid(llama._plain_product(jnp.einsum(
        "te,eh->th", h, wt(lp["w_ogate"]), preferred_element_type=jnp.float32
    )))
    if cfg.attn_gate_per_head:
        T, Hq = gate.shape
        return (gate[:, :, None] * o.reshape(T, Hq, -1)).reshape(o.shape)
    return gate * o


def _attn_mixer(lp, cfg: ModelConfig, h, a, K, V, dec: Optional[_Dec],
                pf: Optional[_Pf], kind="attention"):
    """The GQA mixer over flat rows h [T, E], layer `a` of the attention
    layers of `kind`, K and V that kind's stacks: every row's K/V lands
    in the stacks first, then each half attends (a window layer through
    its own table, over its window, with its sink)."""
    R = dec.R if dec is not None else 0
    scale = _scale(cfg)
    if kind == "window":
        # the window pool's table and plan, the window and the sink as
        # keywords: a full layer's calls stay what they were
        at = lambda half: (half.tables_w, half.plan_w)
        kw = {"window": cfg.sliding_window}
        if cfg.window_sink:
            kw["sinks"] = lp["sink"]
    else:
        at = lambda half: (half.tables, half.plan)
        kw = {}
    positions = _row_positions(dec, pf) if cfg.rotary_dim else None
    q, k, v = _qkv(lp, cfg, h, kind, positions)
    lanes = kvc.raw(K).shape[-1]  # the pool's key row: key_lanes(cfg)
    q, k = _pad_lanes(q, lanes), _pad_lanes(k, lanes)
    if dec is not None:
        dec_tables, dec_plan = at(dec)
        K, V = kv_write_ops.write_kv(K, V, dec_plan, k[:R], v[:R], a)
    if pf is not None:
        pf_tables, pf_plan = at(pf)
        K, V = kv_write_ops.write_kv(K, V, pf_plan, k[R:], v[R:], a)
        q_pf = q[R:].reshape(pf.P, pf.Lpad, *q.shape[1:])
    if dec is not None and pf is not None:
        o_dec, o_pf = mixed_attention(
            q[:R], q_pf, K, V, dec_tables, dec.seq_lens, pf_tables, pf.start,
            pf.length, scale, use_kernel=dec.use_kernel, layer=a, **kw,
        )
        o = jnp.concatenate([o_dec, o_pf.reshape(-1, *o_pf.shape[2:])], axis=0)
    elif dec is not None:
        o = paged_attention(
            q, K, V, dec_tables, dec.seq_lens, scale,
            use_kernel=dec.use_kernel, layer=a, **kw,
        )
    else:
        o = prefill_attention(
            q_pf, K, V, pf_tables, pf.start, pf.length, scale, layer=a, **kw,
        )
        o = o.reshape(-1, *o.shape[2:])
    with region("attn_proj"):
        flat = _gated(lp, cfg, h, o.reshape(o.shape[0], -1)).astype(h.dtype)
        return jnp.einsum("th,he->te", flat, wt(lp["wo"])), K, V


def _sparse_mixer(lp, cfg: ModelConfig, h, a, K, V, CK, dec: Optional[_Dec],
                  pf: Optional[_Pf]):
    """The block-sparse GQA mixer over flat rows h [T, E], sparse layer
    `a`: every row's K/V lands in the stacks, then the compressed keys
    those rows complete land in CK, then each half attends: a decode row
    and a chunk's row past `sparse_dense_len` over the blocks its query
    group selects (ops/sparse_attention.py), a chunk's rows under it
    through the flash launch as a full layer's do. The switch is per ROW:
    a token's output does not depend on how its request was cut up.
    Returns (out [T, E], K', V', CK')."""
    R = dec.R if dec is not None else 0
    scale, sel = _scale(cfg), sparse_ops.selection_of(cfg)
    q, k, v = _qkv(lp, cfg, h)
    outs = []
    if dec is not None:
        K, V = kv_write_ops.write_kv(K, V, dec.plan, k[:R], v[:R], a)
        CK = sparse_ops.write_compressed(
            CK, K, a, dec.tables, dec.positions, dec.active.astype(jnp.int32), 1, sel
        )
    if pf is not None:
        K, V = kv_write_ops.write_kv(K, V, pf.plan, k[R:], v[R:], a)
        CK = sparse_ops.write_compressed(CK, K, a, pf.tables, pf.start, pf.length, pf.Lpad, sel)
    if dec is not None:
        outs.append(sparse_ops.decode_attention(
            q[:R], K, V, CK, a, dec.tables, dec.positions, dec.active, scale, sel,
            use_kernel=dec.use_kernel,
        ))
    if pf is not None:
        q_pf = q[R:].reshape(pf.P, pf.Lpad, *q.shape[1:])
        pos = pf.positions.reshape(pf.P, pf.Lpad)
        valid = jnp.arange(pf.Lpad, dtype=jnp.int32)[None, :] < pf.length[:, None]
        past = valid & (pos + 1 > sel.dense_len)
        blank = jnp.zeros((*q_pf.shape[:3], kvc.raw(V).shape[-1]), q.dtype)
        # a chunk wholly on one side of dense_len runs one of the two
        o_pf = jax.lax.cond(
            jnp.any(valid & ~past),
            lambda: prefill_attention(
                q_pf, K, V, pf.tables, pf.start, pf.length, scale, layer=a,
            ).astype(q.dtype),
            lambda: blank,
        )
        for p in range(pf.P):
            o_sel = jax.lax.cond(
                jnp.any(past[p]),
                lambda p=p: sparse_ops.chunk_selected_attention(
                    q_pf[p], K, V, CK, a, pf.tables[p], pos[p], past[p], scale, sel,
                    use_kernel=dec.use_kernel if dec is not None else None,
                ).astype(q.dtype),
                lambda p=p: blank[p],
            )
            o_pf = o_pf.at[p].set(jnp.where(past[p][:, None, None], o_sel, o_pf[p]))
        outs.append(o_pf.reshape(-1, *o_pf.shape[2:]))
    o = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    with region("attn_proj"):
        flat = _gated(lp, cfg, h, o.reshape(o.shape[0], -1)).astype(h.dtype)
        return jnp.einsum("th,he->te", flat, wt(lp["wo"])), K, V, CK


def lightning_layer_ids(cfg: ModelConfig) -> Tuple[int, ...]:
    """The PUBLISHED index of every lightning layer of the stack."""
    ids = cfg.layer_ids or tuple(range(cfg.num_layers))
    return tuple(i for i, kind in zip(ids, cfg.layer_types) if kind == "lightning")


@region("state_mixer")
def _lightning_mixer(lp, cfg: ModelConfig, h, m, S, conv, dec: Optional[_Dec],
                     pf: Optional[_Pf]):
    """The Lightning mixer over flat rows h [T, E] (decode rows first, then
    the chunks' rows), lightning layer `m`: QK-norm, rotary on every lane
    of q and k, the decayed state (ops/lightning.py), the output norm
    over a head's lanes and the per-lane gate. `conv` (the stack's fourth
    pool: a sparse layer's compressed keys) passes through. Returns
    (out [T, E], S', conv)."""
    T = h.shape[0]
    H, d = cfg.lightning_n_heads, cfg.lightning_d_head
    f32 = jnp.float32
    R = dec.R if dec is not None else 0

    def through(name):  # fenced: the consumers are head-batched
        return llama._plain_product(jnp.einsum(
            "te,ef->tf", h, wt(lp[name]), preferred_element_type=f32
        ))

    positions = _row_positions(dec, pf)

    def head(name, gain):  # norm over a head's lanes, then rotary
        x = rms_norm(through(name).reshape(T, H, d), lp[gain], cfg.rms_norm_eps)
        return rope_ops.apply_rope(x, positions, cfg.rope_theta)

    q, k = head("wq", "q_norm") * d ** -0.5, head("wk", "k_norm")
    v = through("wv").reshape(T, H, d)
    log_lam = jnp.asarray(lightning_ops.log_decay(
        H, lightning_layer_ids(cfg), cfg.published_layers or cfg.num_layers
    ))[m]
    os = []
    if dec is not None:
        o, S = lightning_ops.decode_update(
            S, m, dec.active, q[:R], k[:R], v[:R], log_lam, use_kernel=dec.use_kernel
        )
        os.append(o)
    if pf is not None:
        chunks = lambda t: t[R:].reshape(pf.P, pf.Lpad, H, d)
        o, S = lightning_ops.chunk_update(
            S, m, pf.slots, pf.start, pf.length, chunks(q), chunks(k), chunks(v), log_lam
        )
        os.append(o.reshape(pf.P * pf.Lpad, H, d))
    o = jnp.concatenate(os, axis=0) if len(os) > 1 else os[0]
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = jax.nn.sigmoid(through("w_ogate")) * (y.reshape(T, H * d) * lp["o_norm"])
    wo = wt(lp["wo"])
    return jnp.einsum("tf,fe->te", y.astype(wo.dtype), wo), S, conv


def _lightning_stack(cfg: ModelConfig, w, ones, Ls):
    E, H, d = cfg.hidden_size, cfg.lightning_n_heads, cfg.lightning_d_head
    return {
        "wq": w((Ls, E, H * d), E), "wk": w((Ls, E, H * d), E), "wv": w((Ls, E, H * d), E),
        "w_ogate": w((Ls, E, H * d), E),
        "q_norm": ones((Ls, d)), "k_norm": ones((Ls, d)), "o_norm": ones((Ls, H * d)),
        "wo": w((Ls, H * d, E), H * d),
    }


def _mamba_stack(cfg: ModelConfig, w, ones, Ls):
    E = cfg.hidden_size
    H, d_in, conv = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    return {
        "w_in": w((Ls, E, d_in + conv + H), E),
        "conv_w": w((Ls, cfg.mamba_d_conv, conv), cfg.mamba_d_conv).astype(jnp.float32),
        "conv_b": jnp.zeros((Ls, conv), jnp.float32),
        # softplus(dt_bias) about 0.01-0.1 and A = -exp(A_log) in
        # -1..-16 (Mamba-2's own init): slow decays
        "dt_bias": jnp.full((Ls, H), -3.0, jnp.float32),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)), (Ls, H)
        ),
        "D": ones((Ls, H)),
        "gate_norm": ones((Ls, d_in)),
        "w_out": w((Ls, d_in, E), d_in),
    }


def _kda_stack(cfg: ModelConfig, w, ones, Ls):
    E = cfg.hidden_size
    H, d, d_in, conv = cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_inner, cfg.kda_conv_dim
    r = cfg.kda_gate_rank
    if r <= 0:
        raise ValueError("kda: the decay's and the gate's projections are low-rank pairs, "
                         "kda_gate_rank > 0")
    return {
        "wq": w((Ls, E, d_in), E), "wk": w((Ls, E, d_in), E), "wv": w((Ls, E, d_in), E),
        "conv_w": w((Ls, cfg.kda_d_conv, conv), cfg.kda_d_conv).astype(jnp.float32),
        "conv_b": jnp.zeros((Ls, conv), jnp.float32),
        "w_f1": w((Ls, E, r), E), "w_f2": w((Ls, r, d_in), r),
        # per-token decays exp(-exp(A_log) softplus(dt_bias)) of
        # about 0.99 to 0.9 a channel: a memory of tens of tokens
        "dt_bias": jnp.full((Ls, d_in), -3.0, jnp.float32),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.linspace(0.2, 2.0, H, dtype=jnp.float32)), (Ls, H)
        ),
        "w_beta": w((Ls, E, H), E),
        "w_g1": w((Ls, E, r), E), "w_g2": w((Ls, r, d_in), r),
        "o_norm": ones((Ls, d)),
        "wo": w((Ls, d_in, E), d_in),
    }


class StateKind(NamedTuple):
    """What the stack asks of a layer kind with a state slot."""

    # (lp, cfg, rows, layer of its kind, state pool, convolution pool,
    # decode half, prefill half) -> (out, S', conv')
    mixer: Callable
    shapes: Callable  # (cfg, slots) -> (state pool shape, convolution pool shape)
    on_kernel: Callable  # (cfg, state pool) -> the decode update takes the kind's kernel
    init: Callable  # (cfg, w, ones, layers of the kind) -> the kind's parameter stack


STATE_KINDS = {
    "mamba": StateKind(
        _mamba_mixer,
        lambda cfg, slots: mamba_ops.state_shapes(
            cfg.num_state_layers, slots, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_conv_dim,
        ),
        lambda cfg, state: mamba_ops.kernel_eligible(state, cfg.mamba_n_groups),
        _mamba_stack,
    ),
    "kda": StateKind(
        _kda_mixer,
        lambda cfg, slots: kda_ops.state_shapes(
            cfg.num_state_layers, slots, cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_conv
        ),
        lambda cfg, state: kda_ops.kernel_eligible(state),
        _kda_stack,
    ),
    "lightning": StateKind(
        _lightning_mixer,
        # no convolution: the second pool is empty unless sparse layers
        # keep their compressed keys there (state_shapes)
        lambda cfg, slots: (lightning_ops.state_shape(
            cfg.num_state_layers, slots, cfg.lightning_n_heads, cfg.lightning_d_head
        ), (cfg.num_state_layers, slots, 0)),
        lambda cfg, state: lightning_ops.kernel_eligible(state),
        _lightning_stack,
    ),
}


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    """cfg with the experts off: sends llama's MLP to its dense SwiGLU,
    for a layer of the dense prefix (as models/deepseek.py does)."""
    return dataclasses.replace(cfg, num_experts=0)


def _layer(lp, cfg: ModelConfig, x, valid, kind, mix, caches, dense=False):
    """ONE layer body for every kind: `mix(normed rows, caches) ->
    (mixer output, caches)` is the layer's mixer, of `kind`, over the
    carried pools; `dense`: a layer of the dense prefix, whose `lp` has
    the dense MLP's leaves and no router."""
    y, caches = mix(block_norm(x, lp["attn_norm"], cfg.rms_norm_eps), caches)
    with region(MIXER_REGIONS[kind]):
        x = _add(cfg, x, y)
    u = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    with region("ffn"):
        ffn = llama._mlp_block(lp, _dense_cfg(cfg) if dense else cfg, u, rows_valid=valid)
        return _add(cfg, x, ffn, _ffn_scale(cfg)), caches


def _run_layers(params, cfg: ModelConfig, x, k_caches, v_caches, valid,
                dec: Optional[_Dec], pf: Optional[_Pf]):
    """The stack over flat token rows x [T, E]: one scan a run of equal
    layer kind, every pool on every scan's carry."""

    def mixer(kind, lp, i):
        def mix(h, caches):
            # the second of each pair: the state kind's pools, or the
            # window layers' K and V stacks
            (K, S), (V, conv) = caches
            if kind == PARALLEL_KIND:
                # both mixers on the same normed rows, each branch scaled
                # by its multipliers, summed in float32 for ONE residual
                # add; all four pools move in this one body
                ca, cs = _branch_scales(cfg)
                a, K, V = _attn_mixer(lp, cfg, h, i, K, V, dec, pf)
                s, S, conv = _mamba_mixer(lp, cfg, h, i, S, conv, dec, pf)
                with region(MIXER_REGIONS[kind]):
                    y = a.astype(jnp.float32) * ca + s.astype(jnp.float32) * cs
            elif kind == "attention":
                y, K, V = _attn_mixer(lp, cfg, h, i, K, V, dec, pf)
            elif kind == "window":
                y, S, conv = _attn_mixer(lp, cfg, h, i, S, conv, dec, pf, kind)
            elif kind == "sparse":  # its compressed keys: the fourth pool
                y, K, V, conv = _sparse_mixer(lp, cfg, h, i, K, V, conv, dec, pf)
            else:
                y, S, conv = STATE_KINDS[kind].mixer(lp, cfg, h, i, S, conv, dec, pf)
            return y, ((K, S), (V, conv))

        return mix

    common = params["layers"]
    experts = {
        k: common[k] for k in llama.EXPERT_LEAVES
        if getattr(common[k], "ndim", 0) == 4
    }
    if len(experts) == len(llama.EXPERT_LEAVES):
        common = {k: v for k, v in common.items() if k not in experts}
    else:
        experts = None  # quantized: a layer at a time, like the rest
    # behind a dense prefix of kd layers the norms have an entry a layer
    # and the rest of `layers` one a ROUTED layer: layer l's is entry l - kd
    kd = cfg.first_k_dense_replace
    norms = {k: common[k] for k in ("attn_norm", "mlp_norm")}
    routed = {k: v for k, v in common.items() if k not in norms}

    def at(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
        )

    def layer_leaves(l, dense):
        """Layer l's leaves of `layers` (of `dense_layers` in the prefix)."""
        if not kd:  # every layer routes: one index for every leaf
            return at(common, l)
        if dense:
            return {**at(norms, l), **at(params["dense_layers"], l)}
        return {**at(norms, l), **at(routed, l - kd)}

    def run_segment(carry, seg: Segment, first, kind_first):
        """One scan over a run's layers: `first` the run's first layer of
        all layers, `kind_first` of the layers of its kind."""
        # the kind's mixer stacks: one, or the parallel kind's two (their
        # leaves' names are disjoint)
        stack = {k: v for mx in LAYER_MIXERS[seg.kind] for k, v in params[MIXER_STACKS[mx]].items()}

        def body(carry, i):
            x, kc, vc = carry
            lp = {**layer_leaves(first + i, seg.dense), **at(stack, kind_first + i)}
            if experts is not None and not seg.dense:
                lp["experts"] = (experts, first + i - kd if kd else first + i)
            with moe_ops.layer_stats() as stats:
                x, (kc, vc) = _layer(
                    lp, cfg, x, valid, seg.kind,
                    mixer(seg.kind, lp, kind_first + i), (kc, vc), seg.dense,
                )
            return (x, kc, vc), stats.total()

        with region("stack_slice"):  # as llama._scan_layers
            return jax.lax.scan(body, carry, jnp.arange(seg.n, dtype=jnp.int32))

    period, reps = _period(_segments(cfg))
    of_kind = {kind: sum(s.n for s in period if s.kind == kind) for kind in LAYER_MIXERS}
    layers = sum(of_kind.values())

    def run_period(carry, p):  # p: which repeat of the period (0 where none)
        counts = []
        for seg in period:
            carry, c = run_segment(
                carry, seg, p * layers + seg.first, p * of_kind[seg.kind] + seg.kind_first
            )
            counts.append(c)
        return carry, counts

    carry = (x, k_caches, v_caches)
    if reps == 1:
        carry, counts = run_period(carry, 0)
    else:
        with region("stack_slice"):
            carry, counts = jax.lax.scan(run_period, carry, jnp.arange(reps, dtype=jnp.int32))
    for c in counts:  # [n, 2X] a run, [reps, n, 2X] where the period repeats
        moe_ops.add_step(None if c is None else c.reshape(-1, c.shape[-1]))
    return carry


# ---------------------------------------------------------------- steps


def _takes_positions(cfg: ModelConfig) -> bool:
    """A mixer of the stack reads a row's position: rotary (an attention
    kind's or a lightning layer's) or a sparse layer's selection."""
    return bool(cfg.rotary_dim or cfg.num_sparse_layers or cfg.state_layer_kind == "lightning")


def _dec_half(cfg: ModelConfig, k_caches, positions, tables, active, use_kernel) -> _Dec:
    more = {}
    if _takes_positions(cfg):
        more["positions"] = positions
    if cfg.num_window_layers:
        tables, tables_w = _split_windows(tables)
        more.update(tables_w=tables_w, plan_w=kv_write_ops.write_plan(
            k_caches[1], tables_w, positions, active, 1
        ))
    return _Dec(
        positions.shape[0], tables, jnp.where(active, positions + 1, 0), active,
        kv_write_ops.write_plan(k_caches[0], tables, positions, active, 1), use_kernel,
        **more,
    )


def _pf_half(cfg: ModelConfig, k_caches, block_tables, start, length, P, Lpad
             ) -> Tuple[_Pf, jnp.ndarray]:
    lane = lambda: jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    more = {}
    if _takes_positions(cfg):
        more["positions"] = (start[:, None] + lane()).reshape(-1)
    if cfg.num_window_layers:  # two tables and no state slot
        tables, tables_w = _split_windows(block_tables)
        slots = None
        more.update(tables_w=tables_w, plan_w=kv_write_ops.write_plan(
            k_caches[1], tables_w, start, length, Lpad
        ))
    else:
        tables, slots = _split_tables(block_tables)
    pf = _Pf(
        P, Lpad, tables, slots, start, length,
        kv_write_ops.write_plan(k_caches[0], tables, start, length, Lpad), **more,
    )
    valid = lane() < length[:, None]
    return pf, valid.reshape(-1)


def decode_step(
    params: Params, cfg: ModelConfig, k_caches, v_caches,
    token_ids,  # [R] int32
    positions,  # [R] int32 (where the attention layers' K/V rows go)
    block_tables,  # [R, CB] int32: KV blocks (a row's state slot is the row;
    #                a stack with window layers: [R, 2 CB], their table second)
    active,  # [R] bool
    use_kernel: bool | None = None,
):
    """One generation step for R rows. Returns (logits [R, V], k', v')."""
    dec = _dec_half(cfg, k_caches, positions, block_tables, active, use_kernel)
    x, k_caches, v_caches = _run_layers(
        params, cfg, _embed(params, cfg, token_ids), k_caches, v_caches,
        active, dec, None,
    )
    return _unembed(params, cfg, x), k_caches, v_caches


def prefill_batch_step(
    params: Params, cfg: ModelConfig, k_caches, v_caches,
    token_ids,  # [P, Lpad] int32
    start_pos,  # [P] int32: tokens already in the state and the cache
    true_len,  # [P] int32 (0 = padding row)
    block_tables,  # [P, CB + 1] int32: KV blocks, then slot + 1 (a stack
    #                with window layers: [P, 2 CB], their table second)
    embed_overrides=None, override_positions=None,  # media: not built
    lora_idx=None, rope_positions=None,  # not built
):
    """One chunk per row against the row's carried state and cached
    context. Returns (last-token logits [P, V], k', v')."""
    if any(a is not None for a in (embed_overrides, lora_idx, rope_positions)):
        raise NotImplementedError(
            "granite: no media embeddings, no LoRA and no M-RoPE on this family"
        )
    P, Lpad = token_ids.shape
    pf, valid = _pf_half(cfg, k_caches, block_tables, start_pos, true_len, P, Lpad)
    x, k_caches, v_caches = _run_layers(
        params, cfg, _embed(params, cfg, token_ids.reshape(-1)), k_caches,
        v_caches, valid, None, pf,
    )
    last = llama._last_rows(x.reshape(P, Lpad, -1), true_len)
    return _unembed(params, cfg, last), k_caches, v_caches


def mixed_step(
    params: Params, cfg: ModelConfig, k_caches, v_caches,
    dec_tokens, dec_positions, dec_tables, dec_active,  # the decode rows
    pf_tokens, pf_start, pf_len, pf_tables,  # the due prefill chunks
    use_kernel: bool | None = None,
    lora_dec=None, lora_pf=None, rope_delta=None,
):
    """Decode rows and prefill chunks in ONE program and ONE batch of
    R + P*Lpad token rows for every matmul (the projections, the shared
    MLP and the expert product, which then streams a touched expert once
    a step); only the mixers' state ops are two, the rows' and the
    chunks'. A sequence is in one half only, so the halves touch disjoint
    slots and blocks. Returns (dec_logits [R, V], pf_logits [P, V] of
    each chunk's last valid position, k', v')."""
    if lora_dec is not None or lora_pf is not None or rope_delta is not None:
        raise NotImplementedError("granite: no LoRA and no M-RoPE on this family")
    R = dec_tokens.shape[0]
    P, Lpad = pf_tokens.shape
    dec = _dec_half(cfg, k_caches, dec_positions, dec_tables, dec_active, use_kernel)
    pf, pf_valid = _pf_half(cfg, k_caches, pf_tables, pf_start, pf_len, P, Lpad)
    x = _embed(params, cfg, jnp.concatenate([dec_tokens, pf_tokens.reshape(-1)]))
    x, k_caches, v_caches = _run_layers(
        params, cfg, x, k_caches, v_caches,
        jnp.concatenate([dec_active, pf_valid]), dec, pf,
    )
    last = llama._last_rows(x[R:].reshape(P, Lpad, -1), pf_len)
    return (
        _unembed(params, cfg, x[:R]), _unembed(params, cfg, last),
        k_caches, v_caches,
    )


# ---------------------------------------------------------------- oracle


def hidden_dense(params: Params, cfg: ModelConfig, token_ids, rows_valid=None):
    """Final-norm hidden states [B, L, E] of a plain causal forward: the
    Mamba-2 scan as ONE chunk from an empty state, the delta rule as its
    token-by-token recurrence, materialised attention, the
    all-experts combine (llama._mlp): the oracle of the step programs.
    No pool, no cache, no kernel."""
    if cfg.num_sparse_layers or cfg.state_layer_kind == "lightning":
        raise NotImplementedError(
            f"{cfg.name}: the dense forward (the embeddings endpoint's) has no block "
            f"selection and no lightning state: benchmarks/families/minicpm_sala.py is "
            f"this family's plain forward"
        )
    B, L = token_ids.shape
    f32 = jnp.float32
    H, Pd, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    d_in = cfg.mamba_d_inner
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    x = _embed(params, cfg, token_ids)

    def mamba(lp, h):  # [L, E]
        z, xbc, dt = _ssm_in(lp, cfg, h)
        c = mamba_ops.conv_dense(xbc, lp["conv_w"], lp["conv_b"])
        y = mamba_ops.chunk_form(
            c[:, :d_in].reshape(L, H, Pd), jax.nn.softplus(dt + lp["dt_bias"]),
            -jnp.exp(lp["A_log"].astype(f32)),
            c[:, d_in:d_in + G * N].reshape(L, G, N),
            c[:, d_in + G * N:].reshape(L, G, N), lp["D"],
        )
        return _gated_out(lp, cfg, y.reshape(L, d_in), z)

    def kda(lp, h):  # the recurrence, token by token
        qkv, g, beta, gate = _kda_inputs(lp, cfg, h)
        c = mamba_ops.conv_dense(qkv, lp["conv_w"], lp["conv_b"])
        heads = kda_ops.qkv_heads(c, cfg.kda_n_heads, cfg.kda_d_head)
        o, _ = kda_ops.recurrent_form(*heads, g, beta)
        return _kda_out(lp, cfg, o, gate)

    pos = jnp.arange(L, dtype=jnp.int32)

    def attention(lp, h, kind="attention"):
        q, k, v = (t.astype(f32) for t in _qkv(lp, cfg, h, kind, pos))
        Hkv = k.shape[1]
        s = jnp.einsum("qhgd,khd->hgqk", q.reshape(L, Hkv, -1, cfg.head_dim), k) * _scale(cfg)
        seen = causal
        if kind == "window":
            seen = seen & (pos[None, :] > pos[:, None] - cfg.sliding_window)
        s = jnp.where(seen[None, None], s, -1e30)
        if kind == "window" and cfg.window_sink:  # one logit more, its mass dropped
            sink = jnp.broadcast_to(lp["sink"].reshape(Hkv, -1, 1, 1), (*s.shape[:3], 1))
            p = jax.nn.softmax(jnp.concatenate([s, sink], axis=-1), axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(L, -1)
        return jnp.einsum("th,he->te", _gated(lp, cfg, h, o).astype(h.dtype), wt(lp["wo"]))

    def parallel(lp, h):  # both branches, scaled, summed in float32
        ca, cs = _branch_scales(cfg)
        return attention(lp, h).astype(f32) * ca + mamba(lp, h).astype(f32) * cs

    mixers = {"mamba": mamba, "kda": kda, "attention": attention,
              "window": lambda lp, h: attention(lp, h, "window"), PARALLEL_KIND: parallel}
    li = dict.fromkeys(mixers, 0)
    kd = cfg.first_k_dense_replace
    norms = ("attn_norm", "mlp_norm")
    for l, kind in enumerate(cfg.layer_types):
        lp = {k: params["layers"][k][l] for k in norms}
        if l < kd:
            lp.update({k: v[l] for k, v in params["dense_layers"].items()})
            mcfg = _dense_cfg(cfg)
        else:
            lp.update({k: v[l - kd] for k, v in params["layers"].items() if k not in norms})
            mcfg = cfg
        for mx in LAYER_MIXERS[kind]:
            lp.update({k: v[li[kind]] for k, v in params[MIXER_STACKS[mx]].items()})
        li[kind] += 1
        mix = mixers[kind]
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        x = _add(cfg, x, jax.vmap(lambda hx: mix(lp, hx))(h))
        u = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = _add(cfg, x, jax.vmap(lambda ux: llama._mlp(lp, mcfg, ux))(u), _ffn_scale(cfg))
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def forward_dense(params: Params, cfg: ModelConfig, token_ids) -> jnp.ndarray:
    return _head_scaled(cfg, llama._project(params, cfg, hidden_dense(params, cfg, token_ids)))
