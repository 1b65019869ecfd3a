"""DeepSeek-V2/V3-family model: Multi-head Latent Attention + (optionally)
shared-expert MoE, over the same paged-cache runtime as Llama.

Engine-tier component (SURVEY.md §2.3 — the reference's engine submodule is
absent; BASELINE.json names "DeepSeek-V3 / Mixtral (MoE + expert-parallel
decode)" as north-star config 3). TPU-first design choices:

  * the paged cache stores ONE latent row per token
    (concat(c_kv[kv_lora_rank], k_pe[qk_rope_head_dim]) — e.g. 576 floats
    for V3 vs 2048 for a 70B-class GQA layout), so decode's HBM traffic —
    the bound resource — shrinks ~3.5x on top of any int8 win;
  * decode runs in ABSORBED form (q_nope @ W_UK into latent space; W_UV
    applied once to the attention-weighted latent), so per-head K/V for
    cached tokens is never materialized — scores are one [Hq, C] x [T, C]
    matmul per sequence, MXU-friendly;
  * a prefill chunk of many rows runs in the MATERIALISED form on the
    chip, as the published model does: one flash kernel makes each
    head's keys and values from the latent blocks in VMEM, which costs
    0.56x the absorbed form at 512 rows (the up-projection of a cached
    position is shared by the chunk's rows); ops.attention's
    attention_routes picks the form by the rows of a chunk;
  * the module exports the same function surface as models/llama.py
    (init_params / decode_step / mixed_step / prefill_batch_step /
    forward_dense), so the executor, engine, PD migration, and host tiers
    are unchanged; the latent cache rides the k_cache slot
    ([L, N, 1, BS, C]) and the v_cache slot is a 1-element dummy
    (models.get_module() reports num_caches=1);
  * the STACKED latent pool rides the layer scan's carry
    (llama._scan_layers) through the dense prefix AND the expert suffix:
    one carried stack, a layer index into it, rows written in place
    (ops/kv_write.py) by a plan made once a step; the attention ops take
    the stack and the layer. No scan takes the pool in or gives it back.

Interface contract mirrored from models/llama.py; MLA math follows the
DeepSeek-V2 paper (arxiv 2405.04434 §2.1) / V3 (arxiv 2412.19437).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.models.configs import ModelConfig
from xllm_service_tpu.models.llama import (
    _last_rows,
    _mlp,
    _mlp_block,
    _plain_product,
    _scan_layers,
    _unembed,
)
from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kv_write as kv_write_ops
from xllm_service_tpu.ops.attention import (
    attention_routes as pool_routes,
    mla_materialised_prefill_attention,
    mla_paged_attention,
    mla_prefill_attention,
)
from xllm_service_tpu.ops import rope as rope_ops
from xllm_service_tpu.ops.norms import block_norm, rms_norm
from xllm_service_tpu.ops.quant import is_quant, wdtype, wt

Params = Dict[str, Any]

NUM_CACHES = 1  # latent cache only — no separate V cache

# Stacked matmul leaves eligible for int8 weight quantization. Scales are
# per-axis(-1)-channel over axis -2 (ops/quant.py); for most leaves that
# is per-OUTPUT-channel over the contraction. Exception: w_uk's absorbed
# use (_absorb_q) contracts its LAST axis (dn), so its scales are
# per-contracting-channel there — numerically fine because leaves
# dequantize before the matmul, but don't assume the per-output invariant
# when adding leaves or changing the quantization axis.
QUANTIZABLE_WEIGHT_LEAVES = (
    "w_dkv", "w_uk", "w_uv", "wo", "w_dq", "w_uq", "w_q",
    "w_gate", "w_up", "w_down", "w_sh_gate", "w_sh_up", "w_sh_down",
)


def cache_row_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, row_dim) of one cache row: MLA caches one [C] latent per
    token (head axis 1), vs (Hkv, head_dim) for GQA models."""
    return 1, cfg.mla_cache_dim


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """Score scale for MLA attention: (dn + dr)^-0.5, times the yarn
    temperature correction real DeepSeek-V2/V3 checkpoints apply — HF
    DeepseekV2/V3Attention multiplies its softmax scale by
    yarn_get_mscale(factor, mscale_all_dim)^2 when rope_scaling carries
    mscale_all_dim."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling_type == "yarn" and cfg.rope_mscale_all_dim:
        m = rope_ops.yarn_mscale(
            cfg.rope_scaling_factor, cfg.rope_mscale_all_dim
        )
        scale *= m * m
    return scale


def _layer_stack(
    cfg: ModelConfig, key: jax.Array, dtype, n: int, moe: bool
) -> Dict[str, jnp.ndarray]:
    """One stacked-layer leaf dict of `n` layers: MLA attention plus either
    the MoE block (`moe=True`, dims from moe_intermediate_size) or a dense
    SwiGLU (`moe=False`, dims from intermediate_size)."""
    E, Hq = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    keys = jax.random.split(key, 14)

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def w(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": norm_init((n, E)),
        "mlp_norm": norm_init((n, E)),
        # KV down-projection to the shared latent + rope key.
        "w_dkv": w(keys[0], (n, E, kvr + dr), E),
        "kv_norm": norm_init((n, kvr)),
        # Per-head up-projections OUT of the latent space.
        "w_uk": w(keys[1], (n, Hq, kvr, dn), kvr),
        "w_uv": w(keys[2], (n, Hq, kvr, dv), kvr),
        "wo": w(keys[3], (n, Hq * dv, E), Hq * dv),
    }
    if qr > 0:
        layers["w_dq"] = w(keys[4], (n, E, qr), E)
        layers["q_norm"] = norm_init((n, qr))
        layers["w_uq"] = w(keys[5], (n, qr, Hq * (dn + dr)), qr)
    else:
        layers["w_q"] = w(keys[5], (n, E, Hq * (dn + dr)), E)
    if moe:
        # The router is as wide as the published expert count; the
        # expert leaves hold the span this deployment has (experts_held).
        X, Fm = cfg.num_experts, cfg.moe_intermediate_size
        Xh = cfg.held_experts[1]
        layers.update(
            {
                "router": w(keys[6], (n, E, X), E),
                "w_gate": w(keys[7], (n, Xh, E, Fm), E),
                "w_up": w(keys[8], (n, Xh, E, Fm), E),
                "w_down": w(keys[9], (n, Xh, Fm, E), Fm),
            }
        )
        if cfg.topk_method == "noaux_tc":
            layers["router_bias"] = jnp.zeros((n, X), jnp.float32)
        if cfg.n_shared_experts > 0:
            Fs = cfg.n_shared_experts * Fm
            layers.update(
                {
                    "w_sh_gate": w(keys[10], (n, E, Fs), E),
                    "w_sh_up": w(keys[11], (n, E, Fs), E),
                    "w_sh_down": w(keys[12], (n, Fs, E), Fs),
                }
            )
    else:
        F = cfg.intermediate_size
        layers.update(
            {
                "w_gate": w(keys[7], (n, E, F), E),
                "w_up": w(keys[8], (n, E, F), E),
                "w_down": w(keys[9], (n, F, E), F),
            }
        )
    return layers


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Param pytree. With first_k_dense_replace > 0 (real DeepSeek-V2/V3:
    HF config first_k_dense_replace, the first layers dense) the stack
    splits: `dense_layers` holds the k-layer dense prefix, `layers` the
    (L - k)-layer MoE suffix: one scan each, over the same carried pool
    (_run_layers)."""
    E, L = cfg.hidden_size, cfg.num_layers
    kd = cfg.first_k_dense_replace
    k_embed, k_lm, k_stack, k_dense = jax.random.split(key, 4)

    def w(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    params: Params = {
        "embed": w(k_embed, (cfg.vocab_size, E), E),
        "layers": _layer_stack(cfg, k_stack, dtype, L - kd, cfg.is_moe),
        "final_norm": jnp.ones((E,), jnp.float32),
    }
    if kd > 0:
        params["dense_layers"] = _layer_stack(cfg, k_dense, dtype, kd, False)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(k_lm, (E, cfg.vocab_size), E)
    return params


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    """cfg with MoE off — routes llama._mlp to its dense-SwiGLU branch for
    the dense-prefix stack (trace-time only)."""
    import dataclasses

    return dataclasses.replace(cfg, num_experts=0)


@region("attn_proj")
def _q_heads(lp, cfg: ModelConfig, h: jnp.ndarray, positions: jnp.ndarray,
             split: int = 0):
    """h [T, E] -> (q [T, Hq, dn + dr], q_pe [T, Hq, dr] roped): the
    heads as the projection wrote them, [q_nope | rope part] with the rope
    part NOT roped (a consumer reads q[..., :dn]), and the roped rope part
    on its own; the materialised prefill kernel reads both where they lie.
    `split`: the rows before it and the rows from it on go to different
    attention launches (a mixed step's decode rows and chunk rows), so
    each part is fenced and cut into heads on its own and XLA lays each
    out as ITS consumer reads it: one fence over both made all 576 rows
    take the layout the decode rows' head-batched absorb wants, and the
    prefill kernel's operand was transposed there and back (28 + 25 MB a
    layer, read in the compiled text)."""
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank > 0:
        cq = jnp.einsum("te,eq->tq", h, wt(lp["w_dq"]))
        cq = rms_norm(cq, lp["q_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("tq,qh->th", cq, wt(lp["w_uq"]))
    else:
        q = jnp.einsum("te,eh->th", h, wt(lp["w_q"]))

    def heads(q, positions):
        q = _plain_product(q).reshape(-1, cfg.num_heads, dn + dr)
        return q, rope_ops.apply_rope_scaled(q[..., dn:], positions, cfg)

    if not split:
        return heads(q, positions)
    parts = heads(q[:split], positions[:split]), heads(q[split:], positions[split:])
    return tuple(jnp.concatenate(part) for part in zip(*parts))


def _pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last dim to `width` (the cache's 128-aligned lane
    count, cfg.mla_cache_dim). Zeros on both q and cache rows keep the
    padded lanes out of every q·k score and tile[:, :kvr] context read."""
    if x.shape[-1] == width:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, pad)


@region("attn_proj")
def _latent_rows(lp, cfg: ModelConfig, h: jnp.ndarray, positions: jnp.ndarray):
    """h [T, E] -> cache rows [T, C]: concat(normed c_kv, roped k_pe),
    lane-padded to cfg.mla_cache_dim."""
    kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ckv = jnp.einsum("te,ec->tc", h, wt(lp["w_dkv"]))  # [T, kvr + dr]
    c, k_pe = ckv[..., :kvr], ckv[..., kvr:]
    c = rms_norm(c, lp["kv_norm"], cfg.rms_norm_eps)
    # Single shared rope key per token (head axis of 1 for apply_rope).
    k_pe = rope_ops.apply_rope_scaled(k_pe[:, None, :], positions, cfg)[:, 0]
    return _pad_lanes(
        jnp.concatenate([c, k_pe], axis=-1), cfg.mla_cache_dim
    )


@region("attn_proj")
def _absorb_q(lp, cfg: ModelConfig, q, q_pe) -> jnp.ndarray:
    """Project the heads' q_nope (q[..., :dn]) into the latent space and
    append the roped q_pe: [.., Hq, C] (lane-padded to match the cache
    rows)."""
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("...hd,hkd->...hk", q_nope, wt(lp["w_uk"]))
    return _pad_lanes(
        jnp.concatenate([q_lat, q_pe], axis=-1), cfg.mla_cache_dim
    )


@region("attn_proj")
def _up_v(lp, ctx_lat: jnp.ndarray) -> jnp.ndarray:
    """The absorbed form's context ctx_lat [..., Hq, kvr] -> the heads'
    value-space outputs [..., Hq, dv] via W_UV."""
    return jnp.einsum("...hk,hkv->...hv", ctx_lat, wt(lp["w_uv"]))


@region("attn_proj")
def _attn_out(lp, cfg: ModelConfig, o: jnp.ndarray) -> jnp.ndarray:
    """The heads' outputs o [..., Hq, dv] -> hidden [..., E] via W_O."""
    flat = o.reshape(*o.shape[:-2], cfg.num_heads * cfg.v_head_dim)
    return jnp.einsum("...h,he->...e", flat, wt(lp["wo"]))


def _up_projections(lp):
    """(W_UK, W_UV, w_layer) for a Pallas launch that makes keys and
    values from latents: plain leaves go as the layers' whole STACKS with
    this layer's index in them (`up_stacks`, _run_layers), which the
    launch reads where they lie, since a custom call cannot read through
    the scan's slice and XLA would copy the layer out (2 x 16 MB);
    quantized leaves dequantize a layer at a time, as everywhere
    (w_layer None: one layer's)."""
    w_uk, w_uv, i = lp["up_stacks"]
    if is_quant(w_uk) or is_quant(w_uv):
        return wt(lp["w_uk"]), wt(lp["w_uv"]), None
    return w_uk, w_uv, i


@region("embed")
def _embed_rows(params: Params, token_ids) -> jnp.ndarray:
    return params["embed"][token_ids].astype(wdtype(params["layers"]["w_dkv"]))


def _layer(lp, cfg, mcfg, x, positions, valid, attend, c, split=0):
    """One layer over a flat batch of token rows x [T, E] at `positions`
    [T]: the rows' latents, `attend(lp, q [T, Hq, dn + dr], q_pe [T, Hq,
    dr], rows [T, C], c) -> (o [T, Hq, dv], c)` (which lands the rows in
    the carried stack and attends over it, in whichever form its launch
    takes), the output projection and the MLP over the rows that are
    `valid` [T]. `split`: where `attend` cuts the rows into two launches
    (_q_heads)."""
    h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, q_pe = _q_heads(lp, cfg, h, positions, split)
    rows = _latent_rows(lp, cfg, h, positions)
    o, c = attend(lp, q, q_pe, rows, c)
    with region("attn_proj"):
        x = x + _attn_out(lp, cfg, o)
    h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    with region("ffn"):
        return x + _mlp_block(lp, mcfg, h, rows_valid=valid), c


def _write(plan, layer):
    """rows [T, C] -> the carried stack, in place, by the step's plan."""

    def write(rows, c):
        (c,) = kv_write_ops.write_rows((c,), plan, (rows[:, None, :],), layer)
        return c

    return write


def attention_routes(
    cfg: ModelConfig, c_caches, tp: int = 1, prefill_rows: int = 0
):
    """The decisions for this family's attention launches: one latent
    pool, with no head axis to split over a mesh; `prefill_rows` (the
    rows of a chunk) picks the form of the prefill launch."""
    return (pool_routes(c_caches, latent=True, prefill_rows=prefill_rows),)


def kernel_report(
    cfg: ModelConfig, c_caches, tp: int = 1, prefill_rows: int = 0
) -> dict:
    return attention_routes(cfg, c_caches, tp, prefill_rows)[0].report()


def _decode_attend(cfg, plan, tables, seq_lens, layer, use_kernel=None):
    """R decode rows: one latent row a sequence lands in the stack, then
    absorbed attention over its blocks. Returns (write, read)."""

    def read(lp, q, q_pe, c):
        ctx = mla_paged_attention(
            _absorb_q(lp, cfg, q, q_pe), c, tables, seq_lens,
            mla_softmax_scale(cfg), cfg.kv_lora_rank, use_kernel=use_kernel,
            layer=layer,
        )
        return _up_v(lp, ctx)

    return _write(plan, layer), read


def _prefill_attend(
    cfg, plan, tables, start, length, Lpad: int, layer, use_kernel=None
):
    """P chunks of Lpad rows each (flat [P*Lpad]): the chunks' latents
    land in the stack, then causal attention over each chunk's context,
    the chunk's own rows read back from the pool as latents like every
    cached position. The form is the pool's Routes' for a launch of Lpad
    rows: the materialised flash kernel takes the queries as they are
    and gives the heads' outputs; every other route (the absorbed flash
    kernel, the verify shapes, the blockwise scan) goes through the
    latent space, W_UK before it and W_UV after. Returns (write, read)."""
    scale = mla_softmax_scale(cfg)

    def chunks(a):
        return a.reshape(-1, Lpad, *a.shape[1:])

    def read(lp, q, q_pe, c):
        routes = pool_routes(
            c, latent=True, use_kernel=use_kernel, prefill_rows=Lpad
        )
        if routes.materialised:
            w_uk, w_uv, w_layer = _up_projections(lp)
            o = mla_materialised_prefill_attention(
                chunks(q), chunks(q_pe), w_uk, w_uv, c, tables, start,
                length, scale, cfg.kv_lora_rank, interpret=routes.interpret,
                layer=layer, w_layer=w_layer,
            )
        else:
            ctx = mla_prefill_attention(
                chunks(_absorb_q(lp, cfg, q, q_pe)), c, tables, start,
                length, scale, cfg.kv_lora_rank, use_kernel=use_kernel,
                layer=layer,
            )
            o = _up_v(lp, ctx)
        return o.reshape(-1, *o.shape[2:])

    return _write(plan, layer), read


def _attend(write, read):
    def attend(lp, q, q_pe, rows, c):
        c = write(rows, c)
        return read(lp, q, q_pe, c), c

    return attend


def _chunk_coords(start_pos, true_len, Lpad: int):
    """Flat positions and validity of P chunks of Lpad rows: [P*Lpad]."""
    offsets = jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    return (
        (start_pos[:, None] + offsets).reshape(-1),
        (offsets < true_len[:, None]).reshape(-1),
    )


def _run_layers(params, cfg, x, k_caches, v_caches, positions, valid,
                make_attend, split=0):
    """The layer stack over flat token rows x [T, E], over the CARRIED
    latent pool: one scan for a homogeneous model, or the dense-prefix scan
    (pool layers 0..k-1) followed by the expert-suffix scan (layers
    k..L-1) for first_k_dense_replace. Both carry the whole stack and
    index it by layer (llama._scan_layers): no scan slices the pool in or
    stacks it out, so no step copies it (what PR 29 took out of the llama
    family). `make_attend(layer)` gives the layer's attend closure (the
    plans and tables are the step's, made once outside the scans)."""

    def layer_fn(mcfg, stack, first):
        whole = params[stack]

        def fn(x, lp, layer, c, v):
            lp = {**lp, "up_stacks": (whole["w_uk"], whole["w_uv"], layer - first)}
            x, c = _layer(
                lp, cfg, mcfg, x, positions, valid, make_attend(layer), c,
                split,
            )
            return x, c, v

        return fn

    kd = cfg.first_k_dense_replace if "dense_layers" in params else 0
    if kd > 0:
        x, k_caches, v_caches = _scan_layers(
            layer_fn(_dense_cfg(cfg), "dense_layers", 0), x, params, k_caches,
            v_caches, stack="dense_layers",
        )
    return _scan_layers(
        layer_fn(cfg, "layers", kd), x, params, k_caches, v_caches,
        first_layer=kd,
    )


def decode_step(
    params: Params,
    cfg: ModelConfig,
    k_caches,  # latent stack [L, N, 1, BS, C] (plain or PagedKV)
    v_caches,  # unused dummy (NUM_CACHES = 1); returned untouched
    token_ids: jnp.ndarray,  # [R]
    positions: jnp.ndarray,  # [R]
    block_tables: jnp.ndarray,  # [R, MB]
    active: jnp.ndarray,  # [R] bool
    use_kernel: bool | None = None,
):
    """One generation step for R sequences; mirrors llama.decode_step."""
    plan = kv_write_ops.write_plan(k_caches, block_tables, positions, active, 1)
    seq_lens = jnp.where(active, positions + 1, 0)
    x, k_caches, v_caches = _run_layers(
        params, cfg, _embed_rows(params, token_ids), k_caches, v_caches,
        positions, active,
        lambda layer: _attend(*_decode_attend(
            cfg, plan, block_tables, seq_lens, layer, use_kernel
        )),
    )
    return _unembed(params, cfg, x), k_caches, v_caches


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    k_caches,
    v_caches,
    dec_tokens: jnp.ndarray,  # [R] int32 — decode-slot input tokens
    dec_positions: jnp.ndarray,  # [R] int32
    dec_tables: jnp.ndarray,  # [R, CBd] int32
    dec_active: jnp.ndarray,  # [R] bool
    pf_tokens: jnp.ndarray,  # [P, Lpad] int32 — due prefill chunks
    pf_start: jnp.ndarray,  # [P] int32 (cached tokens before each chunk)
    pf_len: jnp.ndarray,  # [P] int32 (valid tokens per chunk; 0 = pad row)
    pf_tables: jnp.ndarray,  # [P, CBp] int32
    use_kernel: bool | None = None,
    lora_dec=None,
    lora_pf=None,
    rope_delta=None,
):
    """ONE compiled step for a MIXED batch: R decode slots and P chunked-
    prefill rows in a single dispatch over the one carried latent stack,
    with llama.mixed_step's signature and outputs. Unlike the llama
    family the two halves are ONE batch of R + P*Lpad token rows for every
    matmul (the projections, the shared and the dense MLP, and above all
    the expert product, which then streams each touched expert's weights
    once a step and not once a half); only the attention is two ops, the
    decode rows' and the chunks' (their block
    tables are disjoint, so both halves' rows are written first).

    Returns (dec_logits [R, V], pf_logits [P, V] of each chunk's LAST
    valid position, k', v')."""
    if lora_dec is not None or lora_pf is not None or rope_delta is not None:
        raise NotImplementedError("MLA family: no adapters, no M-RoPE")
    R = dec_tokens.shape[0]
    P, Lpad = pf_tokens.shape
    dec_plan = kv_write_ops.write_plan(
        k_caches, dec_tables, dec_positions, dec_active, 1
    )
    dec_seq_lens = jnp.where(dec_active, dec_positions + 1, 0)
    pf_positions, pf_valid = _chunk_coords(pf_start, pf_len, Lpad)
    pf_plan = kv_write_ops.write_plan(
        k_caches, pf_tables, pf_start, pf_len, Lpad
    )

    def make_attend(layer):
        dec_write, dec_read = _decode_attend(
            cfg, dec_plan, dec_tables, dec_seq_lens, layer, use_kernel
        )
        pf_write, pf_read = _prefill_attend(
            cfg, pf_plan, pf_tables, pf_start, pf_len, Lpad, layer,
            use_kernel,
        )

        def attend(lp, q, q_pe, rows, c):
            # Both writes, then both reads of the pool as it then is (as
            # llama.mixed_step orders them): a read between the writes
            # would make the compiler keep a copy of the stack.
            c = pf_write(rows[R:], dec_write(rows[:R], c))
            with region("attn"):  # the halves cut apart and joined again
                o = [
                    dec_read(lp, q[:R], q_pe[:R], c),
                    pf_read(lp, q[R:], q_pe[R:], c),
                ]
                return jnp.concatenate(o, axis=0), c

        return attend

    x = _embed_rows(
        params, jnp.concatenate([dec_tokens, pf_tokens.reshape(-1)])
    )
    x, k_caches, v_caches = _run_layers(
        params, cfg, x, k_caches, v_caches,
        jnp.concatenate([dec_positions, pf_positions]),
        jnp.concatenate([dec_active, pf_valid]),
        make_attend, split=R,
    )
    last = _last_rows(x[R:].reshape(P, Lpad, -1), pf_len)
    return (
        _unembed(params, cfg, x[:R]),
        _unembed(params, cfg, last),
        k_caches,
        v_caches,
    )


def prefill_batch_step(
    params: Params,
    cfg: ModelConfig,
    k_caches,
    v_caches,
    token_ids: jnp.ndarray,  # [P, Lpad]
    start_pos: jnp.ndarray,  # [P]
    true_len: jnp.ndarray,  # [P]
    block_tables: jnp.ndarray,  # [P, CB]
    embed_overrides: jnp.ndarray | None = None,
    override_positions: jnp.ndarray | None = None,
    all_logits: bool = False,  # speculative verify: unembed EVERY position
):
    """Batched chunked prefill; mirrors llama.prefill_batch_step (media
    embedding injection included — the EPD encoder stage is model-family
    agnostic)."""
    P, Lpad = token_ids.shape
    x = _embed_rows(params, token_ids)
    if embed_overrides is not None and embed_overrides.shape[1] > 0:
        E = x.shape[-1]
        ext = jnp.concatenate([x, jnp.zeros((P, 1, E), x.dtype)], axis=1)
        ext = ext.at[
            jnp.arange(P, dtype=jnp.int32)[:, None], override_positions
        ].set(embed_overrides.astype(x.dtype))
        x = ext[:, :Lpad]
    positions, valid = _chunk_coords(start_pos, true_len, Lpad)
    plan = kv_write_ops.write_plan(
        k_caches, block_tables, start_pos, true_len, Lpad
    )
    x, k_caches, v_caches = _run_layers(
        params, cfg, x.reshape(P * Lpad, -1), k_caches, v_caches,
        positions, valid,
        lambda layer: _attend(*_prefill_attend(
            cfg, plan, block_tables, start_pos, true_len, Lpad, layer
        )),
    )
    x = x.reshape(P, Lpad, -1)
    if all_logits:
        return _unembed(params, cfg, x), k_caches, v_caches  # [P, Lpad, V]
    return _unembed(params, cfg, _last_rows(x, true_len)), k_caches, v_caches


def forward_dense(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [B, L]
) -> jnp.ndarray:
    """NAIVE (non-absorbed) causal forward — the correctness oracle for the
    absorbed paged paths: materializes per-head K = concat(c_kv @ W_UK,
    broadcast k_pe) and V = c_kv @ W_UV, then standard MHA."""
    from xllm_service_tpu.models.llama import _project

    return _project(params, cfg, hidden_dense(params, cfg, token_ids))


def hidden_dense(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [B, L]
    rows_valid: jnp.ndarray | None = None,  # accepted for surface parity
) -> jnp.ndarray:
    """Final-norm hidden states [B, L, E] (the /v1/embeddings path).
    `rows_valid` is accepted for function-surface parity with
    models/llama.py but unused: this naive forward is the program's
    ORACLE for the CPU tests (materialised attention, the dense
    all-experts combine over the experts held, llama._mlp), sharing
    nothing with the step functions below the projections."""
    B, L = token_ids.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr = cfg.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    positions = jnp.arange(L, dtype=jnp.int32)
    x = _embed_rows(params, token_ids)
    causal = (
        jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    )  # [L, L] True = attend

    def make_layer_fn(moe: bool):
        mcfg = cfg if moe else _dense_cfg(cfg)

        def layer_fn(x, lp):
            def one_seq(hx):
                h = rms_norm(hx, lp["attn_norm"], cfg.rms_norm_eps)
                q, q_pe = _q_heads(lp, cfg, h, positions)
                q_nope = q[..., :dn]
                rows = _latent_rows(lp, cfg, h, positions)  # [L, C]
                # rows are lane-padded past kvr + dr; slice the true spans.
                c, k_pe = rows[..., :kvr], rows[..., kvr:kvr + dr]
                k_nope = jnp.einsum(
                    "tk,hkd->thd", c, wt(lp["w_uk"])
                )  # [L,Hq,dn]
                v = jnp.einsum("tk,hkv->thv", c, wt(lp["w_uv"]))  # [L,Hq,dv]
                k_pe_b = jnp.broadcast_to(
                    k_pe[:, None, :], (L, cfg.num_heads, dr)
                )
                q = jnp.concatenate([q_nope, q_pe], axis=-1).astype(jnp.float32)
                k = jnp.concatenate([k_nope, k_pe_b], axis=-1).astype(jnp.float32)
                scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
                scores = jnp.where(causal[None], scores, -1e30)
                p = jax.nn.softmax(scores, axis=-1)
                # v is ALREADY up-projected per head — apply only wo here
                # (_attn_out would apply W_UV a second time; caught by the
                # paged-vs-dense parity test once tiny dims were made
                # pairwise distinct).
                o = jnp.einsum("hqk,khv->qhv", p, v.astype(jnp.float32))
                flat = o.reshape(L, cfg.num_heads * cfg.v_head_dim)
                attn = jnp.einsum(
                    "qf,fe->qe", flat.astype(hx.dtype), wt(lp["wo"])
                )
                hx = hx + attn
                h2 = rms_norm(hx, lp["mlp_norm"], cfg.rms_norm_eps)
                return hx + _mlp(lp, mcfg, h2)

            return jax.vmap(one_seq)(x), None

        return layer_fn

    if cfg.first_k_dense_replace > 0 and "dense_layers" in params:
        x, _ = jax.lax.scan(make_layer_fn(False), x, params["dense_layers"])
    x, _ = jax.lax.scan(make_layer_fn(cfg.is_moe), x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
