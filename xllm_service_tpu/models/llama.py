"""Llama-family transformer, TPU-first.

Engine-tier model (reference delegates to the absent xLLM submodule;
SURVEY.md §2.3). Design choices:

  * Parameters are a plain pytree with per-layer tensors STACKED on a leading
    layer axis and the block applied with `lax.scan` — one compiled layer
    body regardless of depth (fast compiles, XLA-friendly).
  * Decode processes a fixed batch of R sequences against the paged KV cache
    (ops/attention.py); prefill processes one bucketed-length chunk for one
    sequence. Both scatter new K/V into the cache first, then attend over
    gathered context, which makes fresh prefill, chunked prefill, and
    prefix-cache-hit prefill the same code path.
  * GQA throughout; SwiGLU MLP; optional MoE block (top-k router). The
    steps run the routed experts through ONE grouped, ragged product over
    the experts held (ops/moe.py); `_mlp`'s all-experts einsum is the
    dense oracle of the tests.
  * Everything is shape-static: R, bucketed prefill lengths, max_blocks.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.models.configs import ModelConfig
from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kv_cache as kv_cache_ops
from xllm_service_tpu.ops import kv_write as kv_write_ops
from xllm_service_tpu.ops.attention import (
    attention_routes as pool_routes,
    mixed_attention,
    mixed_prefill_attention,
    paged_attention,
    prefill_attention,
)
from xllm_service_tpu.ops import collective_matmul as cm_ops
from xllm_service_tpu.ops.norms import block_norm, rms_norm
from xllm_service_tpu.ops import lora as lora_ops
from xllm_service_tpu.ops import moe as moe_ops
from xllm_service_tpu.ops.quant import wdtype, wt
from xllm_service_tpu.ops import rope as rope_ops

Params = Dict[str, Any]

NUM_CACHES = 2  # separate paged K and V caches

# Stacked matmul leaves eligible for int8 weight quantization (all are
# [L, in, out] / [L, X, in, out] with the contraction on axis -2 —
# ops/quant.py). Norms/biases/router stay high precision; embed/lm_head
# are gathers (dequant-at-use would materialize the full table).
QUANTIZABLE_WEIGHT_LEAVES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "w_sh_gate", "w_sh_up", "w_sh_down",
)


def cache_row_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, row_dim) of one paged-cache row. head_dim < 128 models
    pack P = 128/head_dim consecutive KV heads per row so the Pallas
    kernels' 128-lane DMA tiling holds (kv_cache.kv_pack_factor)."""
    P = (
        1 if cfg.kv_pack_disable
        else kv_cache_ops.kv_pack_factor(cfg.num_kv_heads, cfg.head_dim)
    )
    return cfg.num_kv_heads // P, cfg.head_dim * P


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init parameters (tests/bench; checkpoint loading replaces these
    values with the same pytree structure — runtime/weights.py)."""
    E, L = cfg.hidden_size, cfg.num_layers
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    F = cfg.intermediate_size
    keys = jax.random.split(key, 16)

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(
            dtype
        )

    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": norm_init((L, E)),
        "wq": w(keys[0], (L, E, Hq * D), E),
        "wk": w(keys[1], (L, E, Hkv * D), E),
        "wv": w(keys[2], (L, E, Hkv * D), E),
        "wo": w(keys[3], (L, Hq * D, E), Hq * D),
        "mlp_norm": norm_init((L, E)),
    }
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, Hq * D), dtype)
        layers["bk"] = jnp.zeros((L, Hkv * D), dtype)
        layers["bv"] = jnp.zeros((L, Hkv * D), dtype)
    if cfg.qk_norm:
        # Qwen3 QK-norm: one RMSNorm weight over head_dim, shared by all
        # q heads (q_head_norm) / kv heads (k_head_norm) of a layer.
        layers["q_head_norm"] = norm_init((L, D))
        layers["k_head_norm"] = norm_init((L, D))
    if cfg.is_moe:
        X, Fm = cfg.num_experts, cfg.moe_intermediate_size
        layers.update(
            {
                "router": w(keys[4], (L, E, X), E),
                "w_gate": w(keys[5], (L, X, E, Fm), E),
                "w_up": w(keys[6], (L, X, E, Fm), E),
                "w_down": w(keys[7], (L, X, Fm, E), Fm),
            }
        )
        if cfg.topk_method == "noaux_tc":
            layers["router_bias"] = jnp.zeros((L, X), jnp.float32)
        if cfg.n_shared_experts > 0:
            # Shared experts are family-agnostic (_mlp reads these for any
            # MoE config with n_shared_experts > 0).
            Fs = cfg.n_shared_experts * Fm
            layers.update(
                {
                    "w_sh_gate": w(keys[10], (L, E, Fs), E),
                    "w_sh_up": w(keys[11], (L, E, Fs), E),
                    "w_sh_down": w(keys[12], (L, Fs, E), Fs),
                }
            )
    else:
        layers.update(
            {
                "w_gate": w(keys[5], (L, E, F), E),
                "w_up": w(keys[6], (L, E, F), E),
                "w_down": w(keys[7], (L, F, E), F),
            }
        )

    params: Params = {
        "embed": w(keys[8], (cfg.vocab_size, E), E),
        "layers": layers,
        "final_norm": norm_init((E,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[9], (E, cfg.vocab_size), E)
    return params


@region("head")
def _last_rows(x: jnp.ndarray, true_len: jnp.ndarray) -> jnp.ndarray:
    """x [P, Lpad, E] -> each chunk's last valid row [P, E]."""
    return jnp.take_along_axis(
        x, jnp.maximum(true_len - 1, 0)[:, None, None], axis=1
    )[:, 0]


@region("head")
def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return _project(
        params, cfg, rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    )


@region("head")
def _project(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Vocab projection of ALREADY-final-normed hidden states."""
    if cfg.tie_word_embeddings:
        return jnp.einsum("...e,ve->...v", h.astype(jnp.float32),
                          params["embed"].astype(jnp.float32))
    return jnp.einsum("...e,ev->...v", h.astype(jnp.float32),
                      params["lm_head"].astype(jnp.float32))


def _row_parallel(eq: str, x: jnp.ndarray, w2d: jnp.ndarray) -> jnp.ndarray:
    """Row-parallel contraction over a tp-sharded axis (o-proj and the
    FFN down-proj): the ring collective-matmul pipeline when
    XLLM_OVERLAP_COLLECTIVES + a tp>1 shard context apply (the
    reduction rides under the next tile's compute instead of after it
    — ops/collective_matmul.py), else the caller's exact einsum, whose
    GSPMD lowering (local matmul + psum) is the serving default."""
    o = cm_ops.maybe_overlap_matmul(x, w2d)
    return o if o is not None else jnp.einsum(eq, x, w2d)


def _act(cfg: ModelConfig):
    """Gated-MLP activation: SwiGLU (default) or Gemma's GELU-tanh —
    delegated to the one shared selector (ops/moe.py) so the dense,
    oracle, and kernel MoE paths can never drift."""
    return moe_ops._act_fn(cfg.mlp_act)


@region("embed")
def _embed(params: Params, cfg: ModelConfig, token_ids, wd) -> jnp.ndarray:
    """Token embeddings in weight dtype; Gemma scales by sqrt(E) (HF
    computes the normalizer in model dtype)."""
    x = params["embed"][token_ids].astype(wd)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    return x


@region("ffn")
def _mlp(
    lp: Dict[str, jnp.ndarray], cfg: ModelConfig, x: jnp.ndarray,
    lora_idx=None,
) -> jnp.ndarray:
    """SwiGLU (dense) or top-k MoE block. x: [T, E]."""
    if not cfg.is_moe:
        gate = jnp.einsum("te,ef->tf", x, wt(lp["w_gate"]))
        up = jnp.einsum("te,ef->tf", x, wt(lp["w_up"]))
        d = lora_ops.maybe_apply(lp, "w_gate", x, lora_idx, 1.0)
        gate = gate + d if d is not None else gate
        d = lora_ops.maybe_apply(lp, "w_up", x, lora_idx, 1.0)
        up = up + d if d is not None else up
        if cfg.mlp_multipliers:  # muP (Falcon-H1): the gate's pre-activation
            gate = (gate.astype(jnp.float32) * cfg.mlp_multipliers[0]).astype(gate.dtype)
        h = _act(cfg)(gate) * up
        out = _row_parallel("tf,fe->te", h, wt(lp["w_down"]))
        d = lora_ops.maybe_apply(lp, "w_down", h, lora_idx, 1.0)
        return out + d if d is not None else out
    # MoE, the dense ORACLE: every held expert's FFN on every token,
    # combined by the router's weight (0 where a token did not choose the
    # expert). The serving steps go through _mlp_block's grouped product,
    # whose work follows the pairs; this form is what the tests and the
    # deepseek oracle hold it against (docs/MOE.md).
    topi, weights = moe_route(lp, cfg, x)
    T, X = x.shape[0], cfg.num_experts
    combine = jnp.zeros((T, X), jnp.float32)
    combine = combine.at[
        jnp.arange(T, dtype=jnp.int32)[:, None], topi
    ].set(weights)  # [T, X]: top-k combine weight or 0
    lo, n = cfg.held_experts
    combine = combine[:, lo:lo + n]  # the experts absent here add nothing
    gate = jnp.einsum("te,xef->txf", x, wt(lp["w_gate"]))
    up = jnp.einsum("te,xef->txf", x, wt(lp["w_up"]))
    expert_out = jnp.einsum(
        "txf,xfe->txe", _act(cfg)(gate) * up, wt(lp["w_down"])
    )
    out = jnp.einsum("txe,tx->te", expert_out, combine.astype(expert_out.dtype))
    if cfg.n_shared_experts > 0:
        out = out + _shared_experts(lp, x)
    return out


@region("moe_route")
def moe_route(lp, cfg: ModelConfig, x: jnp.ndarray):
    """Router top-k selection + combine weights, x [T, E] ->
    (topi [T, k] int32, weights [T, k] f32). THE routing semantics —
    shared verbatim by the dense all-experts combine (_mlp) and the
    grouped ragged dispatch (_moe_grouped), so flipping the dispatch
    strategy can never change which experts serve a token or at what
    weight."""
    logits = jnp.einsum(
        "te,ex->tx", x.astype(jnp.float32), lp["router"].astype(jnp.float32)
    )
    if cfg.scoring_func == "sigmoid":  # DeepSeek-V3
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    T, X = scores.shape
    # Selection scores may differ from COMBINE weights: V3's noaux_tc
    # adds a correction bias for selection only (HF DeepseekV3TopkRouter).
    sel = scores
    if lp.get("router_bias") is not None:
        sel = sel + lp["router_bias"].astype(jnp.float32)
    if cfg.n_group > 1 and cfg.topk_group > 0:
        # Group-limited routing: keep topk_group groups (scored by their
        # top-2 sum for noaux_tc, group max for group_limited_greedy),
        # zero the rest (scores are non-negative post-softmax/sigmoid).
        gs = sel.reshape(T, cfg.n_group, X // cfg.n_group)
        if cfg.topk_method == "noaux_tc":
            group_scores = jax.lax.top_k(gs, 2)[0].sum(-1)
        else:
            group_scores = gs.max(-1)
        _, gidx = jax.lax.top_k(group_scores, cfg.topk_group)
        gmask = jnp.zeros((T, cfg.n_group), jnp.float32)
        gmask = gmask.at[
            jnp.arange(T, dtype=jnp.int32)[:, None], gidx
        ].set(1.0)
        sel = (gs * gmask[..., None]).reshape(T, X)
    _, topi = jax.lax.top_k(sel, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, topi, axis=-1)  # [T, k]
    # Scaling placement differs between the HF gates: V2's MoEGate
    # applies routed_scaling_factor ONLY in its no-renorm branch, while
    # V3's TopkRouter (sigmoid / noaux_tc configs) renormalizes AND
    # scales. Mixtral/Qwen3 renorm unconditionally and never scale.
    # (Advisor finding, round 4.)
    v3_style = cfg.topk_method == "noaux_tc" or cfg.scoring_func == "sigmoid"
    if cfg.norm_topk_prob:
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20
        )
    if cfg.routed_scaling_factor != 1.0 and (
        v3_style or not cfg.norm_topk_prob
    ):
        weights = weights * cfg.routed_scaling_factor
    return topi, weights


def _shared_experts(lp, x: jnp.ndarray) -> jnp.ndarray:
    """DeepSeek-style always-active shared expert(s): a dense SwiGLU of
    n_shared * moe_intermediate width alongside the routed experts."""
    sg = jnp.einsum("te,ef->tf", x, wt(lp["w_sh_gate"]))
    su = jnp.einsum("te,ef->tf", x, wt(lp["w_sh_up"]))
    return jnp.einsum(
        "tf,fe->te", jax.nn.silu(sg) * su, wt(lp["w_sh_down"])
    )


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _moe_grouped(
    lp, cfg: ModelConfig, x: jnp.ndarray, row_mask=None
) -> jnp.ndarray:
    """MoE block over [T, E] rows: _mlp's routing (moe_route, over the
    published expert count), ONE grouped ragged product over the experts
    held (ops/moe.py; per ep shard under an executor shard context),
    dense shared-expert tail."""
    topi, weights = moe_route(lp, cfg, x)
    # A layer scan hands the experts as the layers' STACKED leaves and an
    # index (_scan_layers): the grouped kernels read the layer out of the
    # stack themselves, so no step slices 3 x Xh matrices out of it.
    leaves, layer = lp.get("experts") or (lp, None)
    out = moe_ops.grouped_moe(
        x, topi, weights,
        *(wt(leaves[k]) for k in EXPERT_LEAVES),
        act=cfg.mlp_act, layer=layer, first=cfg.held_experts[0],
        num_experts=cfg.num_experts, row_mask=row_mask,
    )
    if cfg.n_shared_experts > 0:
        out = out + _shared_experts(lp, x)
    return out


@region("ffn")
def _mlp_block(
    lp, cfg: ModelConfig, h: jnp.ndarray, lora_idx=None, rows_valid=None
) -> jnp.ndarray:
    """MLP over [T, E] or batched [P, L, E] activations: every step
    function's MLP entry point. Dense models run _mlp with exactly the
    split per-row shapes (direct for 2D, vmapped for 3D). An expert model
    flattens the leading axes into one token axis for the grouped product
    (the flatten is OUTSIDE any vmap, which is what lets it wrap in
    shard_map over ep) and the SAME flatten applies in every step family
    (decode, batched prefill, mixed, verify).

    `rows_valid` (h's leading shape, bool) marks LIVE rows (decode
    `active`, prefill/verify `valid`): padding lanes and inactive slots
    make no pair (ops.moe row_mask)."""
    if cfg.is_moe:
        lead = h.shape[:-1]
        mask = rows_valid.reshape(-1) if rows_valid is not None else None
        y = _moe_grouped(
            lp, cfg, h.reshape(-1, h.shape[-1]), row_mask=mask
        )
        return y.reshape(*lead, y.shape[-1])
    if h.ndim == 2:
        return _mlp(lp, cfg, h, lora_idx)
    li = (
        lora_idx if lora_idx is not None
        else jnp.zeros((h.shape[0],), jnp.int32)
    )
    return jax.vmap(
        lambda t, ai: _mlp(
            lp, cfg, t, ai if lora_idx is not None else None
        )
    )(h, li)


@region("attn_proj")
def _qkv(lp, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray,
         lora_idx=None):
    """x: [T, E] -> q [T, Hq, D], k/v [T, Hkv, D] with RoPE applied."""
    T = x.shape[0]
    q = jnp.einsum("te,eh->th", x, wt(lp["wq"]))
    k = jnp.einsum("te,eh->th", x, wt(lp["wk"]))
    v = jnp.einsum("te,eh->th", x, wt(lp["wv"]))
    d = lora_ops.maybe_apply(lp, "wq", x, lora_idx, 1.0)
    q = q + d if d is not None else q
    d = lora_ops.maybe_apply(lp, "wk", x, lora_idx, 1.0)
    k = k + d if d is not None else k
    d = lora_ops.maybe_apply(lp, "wv", x, lora_idx, 1.0)
    v = v + d if d is not None else v
    if cfg.attn_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # Qwen3: per-head RMSNorm over head_dim BEFORE RoPE (HF
        # Qwen3Attention ordering).
        q = rms_norm(q, lp["q_head_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_head_norm"], cfg.rms_norm_eps)
    if cfg.mrope_section and positions.ndim == 2:
        # Qwen2-VL M-RoPE: [3, T] (t, h, w) streams diverge inside image
        # spans. 1D positions (text-only prompts, every decode step) take
        # the standard path below — equal streams make them identical.
        q = rope_ops.apply_mrope(
            q, positions, cfg.rope_theta, cfg.mrope_section
        )
        k = rope_ops.apply_mrope(
            k, positions, cfg.rope_theta, cfg.mrope_section
        )
        return q, k, v
    q = rope_ops.apply_rope_scaled(q, positions, cfg)
    k = rope_ops.apply_rope_scaled(k, positions, cfg)
    return q, k, v


def _out_mlp_rows(lp, cfg: ModelConfig, x, attn, lora, li, valid):
    """The tail of a layer over batched rows x [P, L, E] (a prefill or
    verify half): x + W_o attn, then the MLP block. attn [P, L, Hq, D];
    `lora` the rows' adapters or None (`li` its stand-in for the vmap)."""
    with region("attn_proj"):
        attn_flat = attn.reshape(*x.shape[:2], -1)
        o = _row_parallel("plh,he->ple", attn_flat,
                          wt(lp["wo"]).reshape(-1, cfg.hidden_size))
        if lora is not None and lp.get("lora_wo_a") is not None:
            o = o + jax.vmap(
                lambda af, ai: lora_ops.apply(
                    af, lp["lora_wo_a"], lp["lora_wo_b"], ai
                )
            )(attn_flat, li)
        x = x + o
    h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    with region("ffn"):
        return x + _mlp_block(lp, cfg, h, lora, rows_valid=valid)


def _plain_product(y: jnp.ndarray) -> jnp.ndarray:
    """Fence `y`, the result of a `[T, in] x [in, out]` product on a stored
    weight leaf, so that XLA keeps that product a plain matmul and reads
    the leaf where it lies in its stack. Unfenced, XLA:TPU merges a
    product whose result is reshaped to heads and fed to a head-batched
    consumer (a per-head norm, `...hd,hkd->...hk`) into one head-major
    dot, and that dot wants the WEIGHT head-major: every layer of every
    step then copies the leaf out of the stack and transposes it (brumby
    wq/wk/wv, deepseek w_uq: 1.4-1.9 ms a step, PERF.md, PR 45). Behind
    the fence the consumer re-lays out the ACTIVATION instead, which is
    rows x out and not in x out (docs/MOE.md "A weight is read where it
    lies"; tests/test_tpu_compile.py holds the compiled text to it)."""
    return jax.lax.optimization_barrier(y)


def _scan_layers(layer_fn, x, params, k_caches, v_caches,
                 stack: str = "layers", first_layer: int = 0):
    """The cache-threading layer scan: the stacked caches ride the CARRY
    (never scanned inputs and stacked outputs, which made every layer of
    every step slice, re-tile and restack its whole pool slice: PERF.md,
    PR 29); the scanned inputs are the layer's parameters and its index.
    `layer_fn(x, lp, layer, k_caches, v_caches) -> (x, k_caches,
    v_caches)` lands its rows in place (ops/kv_write.py, by a plan made
    once outside the scan) and hands the whole stack plus `layer` to the
    attention ops. `stack` names the parameter stack scanned and
    `first_layer` the pool layer its first entry writes: a model whose
    stack splits (models/deepseek.py: a dense prefix, an expert suffix)
    runs one scan a stack over the SAME carried pool. What the layers'
    expert blocks recorded leaves as a scan output (ops.moe.layer_stats)."""

    leaves = params[stack]
    # Expert leaves [n, Xh, ...] stay whole (closed over, not scanned):
    # the grouped kernels take the stack and the layer's index. Quantized
    # leaves dequantize a layer at a time and are scanned like the rest.
    experts = {
        k: leaves[k] for k in EXPERT_LEAVES
        if k in leaves and getattr(leaves[k], "ndim", 0) == 4
    }
    if len(experts) == len(EXPERT_LEAVES):
        leaves = {k: v for k, v in leaves.items() if k not in experts}
    else:
        experts = None

    def body(carry, scanned):
        lp, i = scanned
        if experts is not None:
            lp = {**lp, "experts": (experts, i)}
        with moe_ops.layer_stats() as stats:
            out = layer_fn(carry[0], lp, first_layer + i, *carry[1:])
        return out, stats.total()

    n = jax.tree_util.tree_leaves(leaves)[0].shape[0]
    # what the scan does outside an inner region: the layer's leaves
    # sliced out of the stacks, the carry handed on
    with region("stack_slice"):
        (x, k_caches, v_caches), counts = jax.lax.scan(
            body,
            (x, k_caches, v_caches),
            (leaves, jnp.arange(n, dtype=jnp.int32)),
        )
    moe_ops.add_step(counts)
    return x, k_caches, v_caches


def decode_step(
    params: Params,
    cfg: ModelConfig,
    k_caches: jnp.ndarray,  # [L, num_blocks, Hkv, bs, D]
    v_caches: jnp.ndarray,
    token_ids: jnp.ndarray,  # [R] int32
    positions: jnp.ndarray,  # [R] int32 (0-based position of this token)
    block_tables: jnp.ndarray,  # [R, max_blocks] int32
    active: jnp.ndarray,  # [R] bool
    use_kernel: bool | None = None,
    lora_idx: jnp.ndarray | None = None,  # [R] per-slot adapter rows
    rope_delta: jnp.ndarray | None = None,  # [R] int32 (M-RoPE, <= 0)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One generation step for R sequences. Returns (logits [R, V],
    k_caches', v_caches')."""
    scale = cfg.head_dim**-0.5
    x = _embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))  # [R, E]

    # Rope positions may lag cache positions (Qwen2-VL M-RoPE compresses
    # image spans): rope_delta <= 0 shifts the ROTATION only — cache
    # slots, block lookup, and attention lengths stay token-count-based.
    rope_pos = positions + rope_delta if rope_delta is not None else positions
    # One row per active slot at its position; inactive slots write
    # nothing real (garbage block 0 on the scatter route).
    plan = kv_write_ops.write_plan(
        k_caches, block_tables, positions, active, 1
    )
    seq_lens = jnp.where(active, positions + 1, 0)

    def layer_fn(x, lp, layer, k_caches, v_caches):
        h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h, rope_pos, lora_idx)
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, plan, k, v, layer
        )
        attn = paged_attention(
            q, k_caches, v_caches, block_tables, seq_lens, scale,
            use_kernel=use_kernel, window=cfg.sliding_window, layer=layer,
        )
        with region("attn_proj"):
            attn_flat = attn.reshape(attn.shape[0], -1)
            o = _row_parallel("rh,he->re", attn_flat,
                              wt(lp["wo"]).reshape(-1, cfg.hidden_size))
            d = lora_ops.maybe_apply(lp, "wo", attn_flat, lora_idx, 1.0)
            x = x + (o + d if d is not None else o)
        h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        with region("ffn"):
            x = x + _mlp_block(lp, cfg, h, lora_idx, rows_valid=active)
        return x, k_caches, v_caches

    x, k_caches, v_caches = _scan_layers(
        layer_fn, x, params, k_caches, v_caches
    )
    logits = _unembed(params, cfg, x)  # [R, V]
    return logits, k_caches, v_caches


def attention_routes(
    cfg: ModelConfig, k_caches, tp: int = 1, prefill_rows: int = 0
):
    """The decisions (ops.attention.Routes) for the attention launches of
    this family's step programs, one a paged pool: here the K/V pools'.
    `prefill_rows` is the rows of a chunk of the prefill launch asked
    about (every family takes it; a latent pool's form depends on it)."""
    return (pool_routes(k_caches, cfg.num_heads, cfg.head_dim, tp=tp),)


def kernel_report(
    cfg: ModelConfig, k_caches, tp: int = 1, prefill_rows: int = 0
) -> dict:
    """What each launch kind over the family's pools runs as, by name."""
    return attention_routes(cfg, k_caches, tp)[0].report()


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    dec_tokens: jnp.ndarray,  # [R] int32 — decode-slot input tokens
    dec_positions: jnp.ndarray,  # [R] int32
    dec_tables: jnp.ndarray,  # [R, CBd] int32
    dec_active: jnp.ndarray,  # [R] bool
    pf_tokens: jnp.ndarray,  # [P, Lpad] int32 — due prefill chunks
    pf_start: jnp.ndarray,  # [P] int32 (cached tokens before each chunk)
    pf_len: jnp.ndarray,  # [P] int32 (valid tokens per chunk; 0 = pad row)
    pf_tables: jnp.ndarray,  # [P, CBp] int32
    lora_dec: jnp.ndarray | None = None,  # [R] adapter rows
    lora_pf: jnp.ndarray | None = None,  # [P] adapter rows
    rope_delta: jnp.ndarray | None = None,  # [R] M-RoPE lag (decode slots)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ONE compiled step for a MIXED batch: R decode slots and P chunked-
    prefill rows in a single dispatch, fused at the DISPATCH and
    ATTENTION level but NOT at the dense matmuls: each half runs with
    exactly the shapes decode_step ([R, E]) and prefill_batch_step
    (vmapped [P, Lpad]) would use, because matmul row values are only
    bit-stable under a fixed row count — flattening both halves into one
    [R + P*Lpad, E] buffer made mixed-step streams drift from split-step
    streams at bf16 ULP scale (docs/KERNELS.md pins this contract; the
    engine-level differential in tests/test_mixed_step.py enforces
    it). Attention runs through ops.attention.mixed_attention: the exact
    split-path decode and prefill attention ops, side by side.

    Returns (dec_logits [R, V], pf_logits [P, V] — each prefill row's
    LAST valid position — k', v')."""
    scale = cfg.head_dim**-0.5
    R = dec_tokens.shape[0]
    P, Lpad = pf_tokens.shape
    wd = wdtype(params["layers"]["wq"])
    x_dec = _embed(params, cfg, dec_tokens, wd)  # [R, E]
    x_pf = _embed(params, cfg, pf_tokens, wd)  # [P, Lpad, E]

    # Decode-half coordinates: verbatim decode_step (M-RoPE rope_delta
    # shifts the rotation only; inactive slots write nothing real).
    dec_rope = (
        dec_positions + rope_delta if rope_delta is not None
        else dec_positions
    )
    dec_plan = kv_write_ops.write_plan(
        k_caches, dec_tables, dec_positions, dec_active, 1
    )
    dec_seq_lens = jnp.where(dec_active, dec_positions + 1, 0)

    # Prefill-half coordinates: verbatim prefill_batch_step (rows past
    # pf_len write nothing real). Media prompts never ride the mixed
    # step, so positions are always the plain sequential streams.
    offsets = jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    pf_positions = pf_start[:, None] + offsets  # [P, Lpad]
    pf_valid = offsets < pf_len[:, None]
    pf_plan = kv_write_ops.write_plan(
        k_caches, pf_tables, pf_start, pf_len, Lpad
    )
    li = lora_pf if lora_pf is not None else jnp.zeros((P,), jnp.int32)

    def layer_fn(x, lp, layer, k_caches, v_caches):
        x_dec, x_pf = x
        # Decode half QKV: decode_step's [R, E] shapes.
        h_dec = block_norm(x_dec, lp["attn_norm"], cfg.rms_norm_eps)
        q_dec, k_dec, v_dec = _qkv(lp, cfg, h_dec, dec_rope, lora_dec)
        # Prefill half QKV: prefill_batch_step's vmapped [Lpad, E] rows.
        h_pf = block_norm(x_pf, lp["attn_norm"], cfg.rms_norm_eps)
        q_pf, k_pf, v_pf = jax.vmap(
            lambda hx, pos, ai: _qkv(
                lp, cfg, hx, pos, ai if lora_pf is not None else None
            )
        )(h_pf, pf_positions, li)  # q_pf [P, Lpad, Hq, D]
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, dec_plan, k_dec, v_dec, layer
        )
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, pf_plan,
            k_pf.reshape(P * Lpad, *k_pf.shape[2:]),
            v_pf.reshape(P * Lpad, *v_pf.shape[2:]),
            layer,
        )
        attn_dec, attn_pf = mixed_attention(
            q_dec, q_pf, k_caches, v_caches,
            dec_tables, dec_seq_lens,
            pf_tables, pf_start, pf_len,
            scale, window=cfg.sliding_window, layer=layer,
        )
        # Output projection + MLP, per half, split-step shapes.
        with region("attn_proj"):
            attn_dec_flat = attn_dec.reshape(attn_dec.shape[0], -1)
            o = _row_parallel("rh,he->re", attn_dec_flat,
                              wt(lp["wo"]).reshape(-1, cfg.hidden_size))
            d = lora_ops.maybe_apply(lp, "wo", attn_dec_flat, lora_dec, 1.0)
            x_dec = x_dec + (o + d if d is not None else o)
        h_dec = block_norm(x_dec, lp["mlp_norm"], cfg.rms_norm_eps)
        with region("ffn"):
            x_dec = x_dec + _mlp_block(
                lp, cfg, h_dec, lora_dec, rows_valid=dec_active
            )
        x_pf = _out_mlp_rows(lp, cfg, x_pf, attn_pf, lora_pf, li, pf_valid)
        return (x_dec, x_pf), k_caches, v_caches

    (x_dec, x_pf), k_caches, v_caches = _scan_layers(
        layer_fn, (x_dec, x_pf), params, k_caches, v_caches
    )
    dec_logits = _unembed(params, cfg, x_dec)  # [R, V]
    pf_logits = _unembed(params, cfg, _last_rows(x_pf, pf_len))  # [P, V]
    return dec_logits, pf_logits, k_caches, v_caches


def mixed_verify_step(
    params: Params,
    cfg: ModelConfig,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    ver_tokens: jnp.ndarray,  # [R, S] int32 — last accepted token + drafts
    ver_start: jnp.ndarray,  # [R] int32 — position of the first fed token
    ver_len: jnp.ndarray,  # [R] int32 — fed tokens per row (0 = inactive)
    ver_tables: jnp.ndarray,  # [R, CBv] int32
    pf_tokens: jnp.ndarray,  # [P, Lpad] int32 — due prefill chunks
    pf_start: jnp.ndarray,  # [P] int32
    pf_len: jnp.ndarray,  # [P] int32 (0 = pad row)
    pf_tables: jnp.ndarray,  # [P, CBp] int32
    lora_ver: jnp.ndarray | None = None,  # [R] adapter rows (verify rows)
    lora_pf: jnp.ndarray | None = None,  # [P] adapter rows (prefill rows)
    ver_rope_delta: jnp.ndarray | None = None,  # [R] M-RoPE lag (<= 0)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ONE compiled step for a speculative MIXED batch: R verify rows
    (q_len = k+1 — the multi-query speculative-verify half) and P
    chunked-prefill rows in a single dispatch. Same fusion contract as
    mixed_step: fused at the DISPATCH and ATTENTION level, while each
    half keeps exactly the matmul shapes its split program uses — the
    verify half IS prefill_batch_step's vmapped [R, S] program (the one
    executor.verify runs, with all_logits), the prefill half the
    [P, Lpad] one — because matmul row values are only bit-stable under
    a fixed row count (docs/KERNELS.md pins this; the composed
    differential in tests/test_spec_pipeline.py enforces it). Attention
    runs through ops.attention.mixed_prefill_attention: the exact split
    prefill dispatcher, once per half.

    Returns (ver_logits [R, S, V] — every position, the speculative
    verify contract — pf_logits [P, V], k', v')."""
    scale = cfg.head_dim**-0.5
    R, S = ver_tokens.shape
    P, Lpad = pf_tokens.shape
    wd = wdtype(params["layers"]["wq"])
    x_ver = _embed(params, cfg, ver_tokens, wd)  # [R, S, E]
    x_pf = _embed(params, cfg, pf_tokens, wd)  # [P, Lpad, E]

    ver_pos = ver_start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    pf_pos = pf_start[:, None] + jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    ver_plan = kv_write_ops.write_plan(
        k_caches, ver_tables, ver_start, ver_len, S
    )
    pf_plan = kv_write_ops.write_plan(
        k_caches, pf_tables, pf_start, pf_len, Lpad
    )
    # Live-row masks for the grouped-MoE dispatch (_mlp_block rows_valid
    # — padding lanes stay out of routing stats/capacity).
    ver_valid = (
        jnp.arange(S, dtype=jnp.int32)[None, :] < ver_len[:, None]
    )
    pf_valid = (
        jnp.arange(Lpad, dtype=jnp.int32)[None, :] < pf_len[:, None]
    )
    # M-RoPE verify rows (media sequences decoding under spec): the
    # generation streams are equal, only the lag vs cache positions
    # matters — exactly executor._verify_pipe_impl's broadcast.
    if ver_rope_delta is not None:
        base = (ver_start + ver_rope_delta)[:, None] + jnp.arange(
            S, dtype=jnp.int32
        )[None]
        ver_rp = jnp.broadcast_to(base[:, None, :], (R, 3, S))
    else:
        ver_rp = ver_pos
    li_ver = lora_ver if lora_ver is not None else jnp.zeros((R,), jnp.int32)
    li_pf = lora_pf if lora_pf is not None else jnp.zeros((P,), jnp.int32)

    def layer_fn(x, lp, layer, k_caches, v_caches):
        x_ver, x_pf = x
        h_ver = block_norm(x_ver, lp["attn_norm"], cfg.rms_norm_eps)
        q_ver, k_v, v_v = jax.vmap(
            lambda hx, pos, ai: _qkv(
                lp, cfg, hx, pos, ai if lora_ver is not None else None
            )
        )(h_ver, ver_rp, li_ver)  # q_ver [R, S, Hq, D]
        h_pf = block_norm(x_pf, lp["attn_norm"], cfg.rms_norm_eps)
        q_pf, k_p, v_p = jax.vmap(
            lambda hx, pos, ai: _qkv(
                lp, cfg, hx, pos, ai if lora_pf is not None else None
            )
        )(h_pf, pf_pos, li_pf)
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, ver_plan,
            k_v.reshape(R * S, *k_v.shape[2:]),
            v_v.reshape(R * S, *v_v.shape[2:]),
            layer,
        )
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, pf_plan,
            k_p.reshape(P * Lpad, *k_p.shape[2:]),
            v_p.reshape(P * Lpad, *v_p.shape[2:]),
            layer,
        )
        attn_ver, attn_pf = mixed_prefill_attention(
            q_ver, q_pf, k_caches, v_caches,
            ver_tables, ver_start, ver_len,
            pf_tables, pf_start, pf_len,
            scale, window=cfg.sliding_window, layer=layer,
        )

        x_ver = _out_mlp_rows(
            lp, cfg, x_ver, attn_ver, lora_ver, li_ver, ver_valid
        )
        x_pf = _out_mlp_rows(lp, cfg, x_pf, attn_pf, lora_pf, li_pf, pf_valid)
        return (x_ver, x_pf), k_caches, v_caches

    (x_ver, x_pf), k_caches, v_caches = _scan_layers(
        layer_fn, (x_ver, x_pf), params, k_caches, v_caches
    )
    ver_logits = _unembed(params, cfg, x_ver)  # [R, S, V]
    pf_logits = _unembed(params, cfg, _last_rows(x_pf, pf_len))  # [P, V]
    return ver_logits, pf_logits, k_caches, v_caches


def prefill_batch_step(
    params: Params,
    cfg: ModelConfig,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    token_ids: jnp.ndarray,  # [P, Lpad] int32 — per-seq chunks, padded
    start_pos: jnp.ndarray,  # [P] int32: cached tokens before each chunk
    true_len: jnp.ndarray,  # [P] int32: valid tokens per chunk
    block_tables: jnp.ndarray,  # [P, CB] int32 — SLICED to the group's
    # context-block bound, capping the per-layer gather (round-1 weak
    # item 4: gathering max_blocks*BS rows per chunk was O(L^2) with a
    # full-context materialization)
    embed_overrides: jnp.ndarray | None = None,  # [P, M, E] media tokens
    override_positions: jnp.ndarray | None = None,  # [P, M] chunk-relative;
    # padding entries point at Lpad (a dummy row, sliced off)
    all_logits: bool = False,  # speculative verify: unembed EVERY position
    lora_idx: jnp.ndarray | None = None,  # [P] per-sequence adapter rows
    rope_positions: jnp.ndarray | None = None,  # [P, 3, Lpad] M-RoPE streams
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill P sequences' chunks in ONE compiled step (batched admission).

    K/V rows for all P*Lpad tokens scatter into the paged cache in a single
    flattened write (invalid rows land in garbage block 0); attention is
    vmapped per sequence over its own sliced block table. Media embeddings
    (EPD encoder outputs) overwrite placeholder-token rows before the first
    layer. Returns (last-token logits [P, V] — or [P, Lpad, V] when
    `all_logits`, the speculative-decoding verify pass — k', v')."""
    scale = cfg.head_dim**-0.5
    P, Lpad = token_ids.shape
    x = _embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    if embed_overrides is not None and embed_overrides.shape[1] > 0:
        # Scatter into an extended buffer whose last row is a discard slot
        # so padded positions (== Lpad) never corrupt real rows.
        E = x.shape[-1]
        ext = jnp.concatenate([x, jnp.zeros((P, 1, E), x.dtype)], axis=1)
        ext = ext.at[
            jnp.arange(P, dtype=jnp.int32)[:, None], override_positions
        ].set(embed_overrides.astype(x.dtype))
        x = ext[:, :Lpad]

    offsets = jnp.arange(Lpad, dtype=jnp.int32)[None, :]  # [1, Lpad]
    positions = start_pos[:, None] + offsets  # [P, Lpad]
    valid = offsets < true_len[:, None]
    plan = kv_write_ops.write_plan(
        k_caches, block_tables, start_pos, true_len, Lpad
    )

    li = lora_idx if lora_idx is not None else jnp.zeros((P,), jnp.int32)
    # Cache slots/attention stay token-count positional; only the q/k
    # ROTATION takes the (t, h, w) streams when M-RoPE positions ride in.
    rp = rope_positions if rope_positions is not None else positions

    def layer_fn(x, lp, layer, k_caches, v_caches):
        h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = jax.vmap(
            lambda hx, pos, ai: _qkv(
                lp, cfg, hx, pos, ai if lora_idx is not None else None
            )
        )(h, rp, li)  # q [P, Lpad, Hq, D]
        k_caches, v_caches = kv_write_ops.write_kv(
            k_caches, v_caches, plan,
            k.reshape(P * Lpad, *k.shape[2:]),
            v.reshape(P * Lpad, *v.shape[2:]),
            layer,
        )
        attn = prefill_attention(
            q, k_caches, v_caches, block_tables, start_pos, true_len,
            scale, window=cfg.sliding_window, layer=layer,
        )  # [P, Lpad, Hq, D] — flash kernel on TPU, blockwise elsewhere
        x = _out_mlp_rows(lp, cfg, x, attn, lora_idx, li, valid)
        return x, k_caches, v_caches

    x, k_caches, v_caches = _scan_layers(
        layer_fn, x, params, k_caches, v_caches
    )
    if all_logits:
        return _unembed(params, cfg, x), k_caches, v_caches  # [P, Lpad, V]
    logits = _unembed(params, cfg, _last_rows(x, true_len))  # [P, V]
    return logits, k_caches, v_caches


def prefill_step(
    params: Params,
    cfg: ModelConfig,
    k_caches: jnp.ndarray,
    v_caches: jnp.ndarray,
    token_ids: jnp.ndarray,  # [Lpad] int32 — one sequence's chunk, padded
    start_pos: jnp.ndarray,  # scalar int32: cached tokens before this chunk
    true_len: jnp.ndarray,  # scalar int32: valid tokens in chunk
    block_table: jnp.ndarray,  # [max_blocks] int32
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Process one prefill chunk (P=1 wrapper over prefill_batch_step).
    Returns (last-token logits [V], k', v')."""
    logits, k_caches, v_caches = prefill_batch_step(
        params, cfg, k_caches, v_caches,
        token_ids[None],
        jnp.asarray(start_pos, jnp.int32)[None],
        jnp.asarray(true_len, jnp.int32)[None],
        block_table[None],
    )
    return logits[0], k_caches, v_caches


def prefill_sp_step(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [Lsp] int32 — padded to a multiple of sp
    true_len: jnp.ndarray,  # scalar int32
    mesh,
    sp_axis: str = "sp",
    tp_axis=None,  # compose with tensor parallelism on the same mesh:
    # params keep their Megatron tp sharding and the ring shards heads
    # over tp_axis too (ops/ring_attention.ring_attention)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel long-context prefill: the prompt's sequence axis is
    sharded over the `sp` mesh ring and every layer's attention is EXACT
    ring attention (ops/ring_attention.py — K/V shards rotate via ppermute,
    queries stay resident), so max prompt length scales linearly with the
    ring size instead of one device's HBM.

    Returns (last-token logits [V], k_all [layers, Lsp, Hkv, D],
    v_all [...]) — the caller scatters K/V into the paged cache
    (runtime/executor.py prefill_long) and decode proceeds normally.
    """
    from xllm_service_tpu.ops.ring_attention import ring_attention

    Lsp = token_ids.shape[0]
    positions = jnp.arange(Lsp, dtype=jnp.int32)
    x = _embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    x = x[None]  # [1, Lsp, E] — ring_attention is batched

    def layer_fn(x, lp):
        h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h[0], positions)
        attn = ring_attention(
            q[None], k[None], v[None], mesh, sp_axis=sp_axis,
            scale=cfg.head_dim**-0.5, causal=True, tp_axis=tp_axis,
        )
        x = x + jnp.einsum(
            "blh,he->ble",
            attn.reshape(1, Lsp, -1),
            wt(lp["wo"]).reshape(-1, cfg.hidden_size),
        )
        h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + _mlp_block(
            lp, cfg, h[0],
            rows_valid=jnp.arange(Lsp, dtype=jnp.int32) < true_len,
        )[None]
        return x, (k, v)

    x, (k_all, v_all) = jax.lax.scan(layer_fn, x, params["layers"])
    last = x[0, jnp.maximum(true_len - 1, 0)]
    logits = _unembed(params, cfg, last)
    return logits, k_all, v_all


def forward_dense(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [B, L] int32
) -> jnp.ndarray:
    """Plain causal forward without KV cache — the correctness oracle for
    prefill/decode and the body of the training step (__graft_entry__)."""
    return _project(params, cfg, hidden_dense(params, cfg, token_ids))


def hidden_dense(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [B, L] int32
    rows_valid: jnp.ndarray | None = None,  # [B, L] bool live-row mask
) -> jnp.ndarray:
    """Final-norm hidden states [B, L, E] of a plain causal forward —
    the /v1/embeddings path (pooling happens executor-side) and the body
    forward_dense unembeds. `rows_valid` marks real tokens when the
    caller bucket-padded (executor.embed_tokens) — the grouped-MoE
    dispatch keeps padding rows out of routing stats/capacity exactly
    like the serving steps (_mlp_block docstring)."""
    B, L = token_ids.shape
    scale = cfg.head_dim**-0.5
    x = _embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    positions = jnp.arange(L, dtype=jnp.int32)
    causal = jnp.tril(jnp.ones((L, L), dtype=bool))
    if cfg.sliding_window:
        # HF SWA semantics: position p attends [p-window+1, p].
        causal &= (
            positions[None, :] > positions[:, None] - cfg.sliding_window
        )

    def layer_fn(x, lp):
        h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)

        def one_seq(hx):
            q, k, v = _qkv(lp, cfg, hx, positions)
            Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            g = Hq // Hkv
            qf = q.astype(jnp.float32).reshape(L, Hkv, g, D)
            scores = jnp.einsum("qhgd,khd->hgqk", qf, k.astype(jnp.float32)) * scale
            scores = jnp.where(causal[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("hgqk,khd->qhgd", probs, v.astype(jnp.float32))
            return out.reshape(L, Hq * D).astype(hx.dtype)

        attn = jax.vmap(one_seq)(h)  # [B, L, Hq*D]
        x = x + jnp.einsum("blh,he->ble", attn, wt(lp["wo"]).reshape(-1, cfg.hidden_size))
        h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + _mlp_block(lp, cfg, h, rows_valid=rows_valid)
        return x, None

    x, _ = jax.lax.scan(layer_fn, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)  # [B, L, E]
