"""Brumby family: a Qwen3 decoder block whose attention is power retention.

Per layer (ops/retention.py has the equations and the state's layout):
`hn = RMSNorm(x)`; `q = RoPE(RMSNorm_head(W_q hn))`, `k` likewise,
`v = W_v hn` (as llama.py's `_qkv`: explicit head_dim, no QKV bias, QK-norm;
kept in float32 here);
`gamma = logsigmoid(hn . w_ret_gate + b_ret_gate)`, one log decay a token
and KV head; `y` = power retention of degree `cfg.retention_degree` over
(q, k, v, gamma); `x' = x + W_o y`; then llama.py's MLP block.

The sequence state is not paged K/V but ONE slot of the executor's state
pool (`S`, `z`), whatever the context's length. The pool rides the layer
scan's carry (llama.py `_scan_layers`, as the K/V stacks do since PR 29)
and is updated in place by layer index. A row's slot is its block id
less one: the engine gives such a family blocks as long as `max_seq_len`,
so a sequence owns exactly one for its life, and column 0 of a block
table is the slot (0 = no slot: a dead row).

Same step surface as llama.py; the block-table and cache arguments keep
their places so the executor's step programs are the same for both.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import ModelConfig
from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import retention as retention_ops
from xllm_service_tpu.ops.norms import block_norm, rms_norm
from xllm_service_tpu.ops import rope as rope_ops
from xllm_service_tpu.ops.quant import wdtype, wt

Params = Dict

NUM_CACHES = 2  # the state S and its normaliser z
QUANTIZABLE_WEIGHT_LEAVES = llama.QUANTIZABLE_WEIGHT_LEAVES
# Initial gate bias: sigmoid(5) = 0.993, a slow decay (a zero bias would
# halve the state every token and make it a 3-token window).
GATE_BIAS_INIT = 5.0


def cache_row_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, row_dim) of one STATE row: there is no paged cache row."""
    return cfg.num_kv_heads, cfg.head_dim


def state_shapes(cfg: ModelConfig, slots: int):
    return retention_ops.state_shapes(
        cfg.num_layers, slots, cfg.num_kv_heads, cfg.head_dim
    )


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """llama.py's tree (QK-norm on) plus the retention gate."""
    if cfg.retention_degree != 2:
        raise ValueError(
            f"retention_degree={cfg.retention_degree}: only degree 2 is built"
        )
    params = llama.init_params(cfg, key, dtype)
    L, E, Hkv = cfg.num_layers, cfg.hidden_size, cfg.num_kv_heads
    gk = jax.random.fold_in(key, 0x6A7E)
    params["layers"]["w_ret_gate"] = (
        jax.random.normal(gk, (L, E, Hkv), jnp.float32) / jnp.sqrt(E)
    ).astype(dtype)
    params["layers"]["b_ret_gate"] = jnp.full((L, Hkv), GATE_BIAS_INIT, jnp.float32)
    return params


@region("state_mixer")
def _gate(lp, h: jnp.ndarray) -> jnp.ndarray:
    """h [..., E] -> log decay [..., Hkv] float32, always <= 0."""
    logit = jnp.einsum(
        "...e,eh->...h", h, lp["w_ret_gate"], preferred_element_type=jnp.float32
    ) + lp["b_ret_gate"]
    return jax.nn.log_sigmoid(logit)


@region("attn_proj")
def _qkv(lp, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """llama.py's `_qkv` for this family (no bias, QK-norm, RoPE) with
    float32 results: x [T, E] -> q [T, Hq, D], k, v [T, Hkv, D]. The
    retention weights are SQUARES of q.k, which double a rounding of q or
    k, and the state is float32 anyway, so the projections' outputs are
    not rounded to the weights' dtype on the way."""
    T = x.shape[0]

    def proj(name, heads):
        y = jnp.einsum(
            "te,eh->th", x, wt(lp[name]), preferred_element_type=jnp.float32
        )
        return llama._plain_product(y).reshape(T, heads, cfg.head_dim)

    q, k, v = proj("wq", cfg.num_heads), proj("wk", cfg.num_kv_heads), proj("wv", cfg.num_kv_heads)
    q = rms_norm(q, lp["q_head_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, lp["k_head_norm"], cfg.rms_norm_eps)
    return (
        rope_ops.apply_rope_scaled(q, positions, cfg),
        rope_ops.apply_rope_scaled(k, positions, cfg),
        v,
    )


def _out_mlp(lp, cfg: ModelConfig, x, y, rows_valid):
    """x + W_o y, then the MLP block; y [..., Hq, D] float32."""
    with region("attn_proj"):
        flat = y.reshape(*y.shape[:-2], -1).astype(x.dtype)
        x = x + jnp.einsum("...h,he->...e", flat, wt(lp["wo"]).reshape(-1, cfg.hidden_size))
    h = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    with region("ffn"):
        return x + llama._mlp_block(lp, cfg, h, rows_valid=rows_valid)


def _slots(block_tables: jnp.ndarray) -> jnp.ndarray:
    return block_tables[:, 0].astype(jnp.int32) - 1


def _dec_layer(cfg, lp, layer, S, z, x, positions, slots, active, use_kernel):
    h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(lp, cfg, h, positions)
    y, S, z = retention_ops.decode_update(
        S, z, layer, slots, active, q, k, v, _gate(lp, h), use_kernel=use_kernel,
    )
    return _out_mlp(lp, cfg, x, y, active), S, z


def _pf_layer(cfg, lp, layer, S, z, x, positions, slots, start, length, valid,
              use_kernel):
    h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = jax.vmap(lambda hx, pos: _qkv(lp, cfg, hx, pos))(h, positions)
    y, S, z = retention_ops.chunk_update(
        S, z, layer, slots, start, length, q, k, v, _gate(lp, h),
        use_kernel=use_kernel,
    )
    return _out_mlp(lp, cfg, x, y, valid), S, z


def decode_step(
    params: Params, cfg: ModelConfig, S, z,
    token_ids,  # [R] int32
    positions,  # [R] int32 (RoPE only: the state has no positions)
    block_tables,  # [R, 1] int32: column 0 = slot + 1
    active,  # [R] bool
    use_kernel: bool | None = None,
):
    """One generation step for R rows. Returns (logits [R, V], S', z')."""
    x = llama._embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    slots = _slots(block_tables)

    def layer_fn(x, lp, layer, S, z):
        return _dec_layer(cfg, lp, layer, S, z, x, positions, slots, active, use_kernel)

    x, S, z = llama._scan_layers(layer_fn, x, params, S, z)
    return llama._unembed(params, cfg, x), S, z


def prefill_batch_step(
    params: Params, cfg: ModelConfig, S, z,
    token_ids,  # [P, Lpad] int32
    start_pos,  # [P] int32: tokens already in the state (0 = a fresh slot)
    true_len,  # [P] int32 (0 = padding row)
    block_tables,  # [P, 1] int32
    embed_overrides=None, override_positions=None,  # media: not built
    lora_idx=None, rope_positions=None,  # not built
    use_kernel: bool | None = None,
):
    """One chunk per row against the row's carried state. Returns
    (last-token logits [P, V], S', z')."""
    if any(a is not None for a in (embed_overrides, lora_idx, rope_positions)):
        raise NotImplementedError(
            "brumby: no media embeddings, no LoRA and no M-RoPE on this family"
        )
    P, Lpad = token_ids.shape
    x = llama._embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    offsets = jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    positions = start_pos[:, None] + offsets
    valid = offsets < true_len[:, None]
    slots = _slots(block_tables)

    def layer_fn(x, lp, layer, S, z):
        return _pf_layer(cfg, lp, layer, S, z, x, positions, slots, start_pos,
                         true_len, valid, use_kernel)

    x, S, z = llama._scan_layers(layer_fn, x, params, S, z)
    return llama._unembed(params, cfg, llama._last_rows(x, true_len)), S, z


def attention_routes(cfg: ModelConfig, S, tp: int = 1, prefill_rows: int = 0):
    """No paged pool, no attention launch: the state pool alone."""
    return ()


def kernel_report(
    cfg: ModelConfig, S, tp: int = 1, prefill_rows: int = 0
) -> dict:
    """Which route the retention updates take, under every step's name."""
    on = retention_ops.use_kernels(cfg.head_dim)
    route = f"retention-{'pallas' if on else 'xla'}"
    return {"decode": route, "prefill": route, "mixed": route}


def mixed_step(
    params: Params, cfg: ModelConfig, S, z,
    dec_tokens, dec_positions, dec_tables, dec_active,  # the decode rows
    pf_tokens, pf_start, pf_len, pf_tables,  # the due prefill chunks
    use_kernel: bool | None = None,
    lora_dec=None, lora_pf=None, rope_delta=None,
):
    """Decode rows and prefill chunks in ONE program, each half with the
    shapes of its split program. A row is in one half only (a sequence in
    prefill is not active in decode), so the halves touch disjoint slots.
    Returns (dec_logits [R, V], pf_logits [P, V], S', z')."""
    if lora_dec is not None or lora_pf is not None or rope_delta is not None:
        raise NotImplementedError("brumby: no LoRA and no M-RoPE on this family")
    P, Lpad = pf_tokens.shape
    wd = wdtype(params["layers"]["wq"])
    x_dec = llama._embed(params, cfg, dec_tokens, wd)
    x_pf = llama._embed(params, cfg, pf_tokens, wd)
    offsets = jnp.arange(Lpad, dtype=jnp.int32)[None, :]
    pf_positions = pf_start[:, None] + offsets
    pf_valid = offsets < pf_len[:, None]
    dec_slots, pf_slots = _slots(dec_tables), _slots(pf_tables)

    def layer_fn(x, lp, layer, S, z):
        x_dec, x_pf = x
        x_dec, S, z = _dec_layer(cfg, lp, layer, S, z, x_dec, dec_positions,
                                 dec_slots, dec_active, use_kernel)
        x_pf, S, z = _pf_layer(cfg, lp, layer, S, z, x_pf, pf_positions, pf_slots,
                               pf_start, pf_len, pf_valid, use_kernel)
        return (x_dec, x_pf), S, z

    (x_dec, x_pf), S, z = llama._scan_layers(layer_fn, (x_dec, x_pf), params, S, z)
    last = llama._last_rows(x_pf, pf_len)
    return llama._unembed(params, cfg, x_dec), llama._unembed(params, cfg, last), S, z


def hidden_dense(params: Params, cfg: ModelConfig, token_ids, rows_valid=None):
    """Final-norm hidden states [B, L, E] of a plain causal forward in
    attention form: no state, no chunks (the oracle of the step programs)."""
    B, L = token_ids.shape
    x = llama._embed(params, cfg, token_ids, wdtype(params["layers"]["wq"]))
    positions = jnp.arange(L, dtype=jnp.int32)

    def layer_fn(x, lp):
        h = block_norm(x, lp["attn_norm"], cfg.rms_norm_eps)

        def one_seq(hx):
            q, k, v = _qkv(lp, cfg, hx, positions)
            return retention_ops.attention_form(q, k, v, _gate(lp, hx))

        return _out_mlp(lp, cfg, x, jax.vmap(one_seq)(h), rows_valid), None

    x, _ = jax.lax.scan(layer_fn, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def forward_dense(params: Params, cfg: ModelConfig, token_ids) -> jnp.ndarray:
    return llama._project(params, cfg, hidden_dense(params, cfg, token_ids))
