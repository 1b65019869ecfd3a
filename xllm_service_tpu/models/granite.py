"""Granite 4.0-H family (HF `model_type: granitemoehybrid`): Mamba-2
state-space layers and GQA layers in ONE stack, a top-k expert block
beside a shared MLP in every layer, over the same runtime as the other
families.

With `h` the residual stream, `rms` RMSNorm with a learned gain, and the
four multipliers of the configuration:

    h0 = embedding_multiplier * E[token]
    every layer l:  h <- h + residual_multiplier * mixer_l(rms_1(h))
                    u  = rms_2(h)
                    h <- h + residual_multiplier * (moe(u) + shared(u))
    logits = (rms_f(h_L) @ E^T) / logits_scaling          (tied head)

`mixer_l` is what `cfg.layer_types[l]` names; the pattern is DATA.

* "mamba": `[z | xBC | dt] = W_in u` (d_inner | d_inner + 2 G N | H); the
  depthwise causal convolution and silu over xBC, the selective scan over
  `[x | B | C]` (ops/mamba.py has both, the equations and the pools'
  layout); gate THEN norm, `y = rms_g(y * silu(z))` over each group's
  d_inner / G lanes; out `= W_out y`. No projection bias, a convolution
  bias.
* "attention": GQA, no bias, NO rotary (the family is NoPE by construction),
  scores scaled by `attention_multiplier` (not head_dim**-0.5), causal,
  full, over the paged K/V pool.
* experts: `llama.moe_route` (softmax, top-k, renormalised: the softmax
  over the chosen logits) and the grouped product over the experts HELD
  (`cfg.experts_held`), the shared MLP always on (`llama._mlp_block`).

**A sequence's two kinds of memory.** The carried caches are a pair of
pairs, `k_caches = (K, S)` and `v_caches = (V, conv)`: K and V stacks
`[La, N, Hkv, BS, D]` over the ATTENTION layers only, in paged blocks that
grow with the context, and the SSM and convolution state pools over the
MAMBA layers, one slot a sequence for its life (`state_shapes`). All four
ride the carry of every segment's scan (llama.py `_scan_layers`' rule: no
scan slices a pool in or stacks it out). A decode row's slot is its row
index; a prefill row names its slot in the LAST column of its block table
(slot + 1; 0 = a padding row), which the executor appends for a family
that has both kinds (runtime/executor.py `slot_column`).

Runs of equal layer kind are one scan each (`_segments`); the parameter
tree keeps what every layer has under `layers` (norms, router, experts,
shared MLP: L entries), the mixers under `mamba` (Lm entries) and `attn`
(La entries), each scan indexing the stacks it needs.

Same step surface as llama.py. The mixed step runs ONE batch of
R + P*Lpad token rows through every matmul (as models/deepseek.py), so a
touched expert streams once a step.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import ModelConfig
from xllm_service_tpu.obs.spans import region
from xllm_service_tpu.ops import kv_write as kv_write_ops
from xllm_service_tpu.ops import mamba as mamba_ops
from xllm_service_tpu.ops import moe as moe_ops
from xllm_service_tpu.ops.attention import (
    mixed_attention,
    paged_attention,
    prefill_attention,
)
from xllm_service_tpu.ops.norms import block_norm, rms_norm
from xllm_service_tpu.ops.quant import wdtype, wt

Params = Dict

NUM_CACHES = 2  # K and V (each paired with a state pool on the carry)
QUANTIZABLE_WEIGHT_LEAVES = llama.QUANTIZABLE_WEIGHT_LEAVES + ("w_in", "w_out")
MIXER_STACKS = {"mamba": "mamba", "attention": "attn"}
# the device region of a mixer's residual add (obs.spans.DEVICE_REGIONS)
MIXER_REGIONS = {"mamba": "state_mixer", "attention": "attn_proj"}


def cache_row_dims(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, row_dim) of one paged-cache row of an attention layer."""
    return cfg.num_kv_heads, cfg.head_dim


def state_shapes(cfg: ModelConfig, slots: int):
    """(SSM pool shape, convolution pool shape): ops/mamba.py's layout."""
    return mamba_ops.state_shapes(
        cfg.num_mamba_layers, slots, cfg.mamba_n_heads, cfg.mamba_d_head,
        cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_conv_dim,
    )


class Segment(NamedTuple):
    kind: str  # "mamba" | "attention"
    first: int  # the run's first layer, of all layers
    kind_first: int  # ... and of the layers of its kind
    n: int


def _segments(cfg: ModelConfig) -> List[Segment]:
    out: List[Segment] = []
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(cfg.layer_types):
        if kind not in seen:
            raise ValueError(f"layer_types[{l}] = {kind!r}: 'mamba' or 'attention'")
        if out and out[-1].kind == kind:
            out[-1] = out[-1]._replace(n=out[-1].n + 1)
        else:
            out.append(Segment(kind, l, seen[kind], 1))
        seen[kind] += 1
    return out


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    if len(cfg.layer_types) != cfg.num_layers or not cfg.tie_word_embeddings:
        raise ValueError("granite: one layer type a layer and a tied head")
    if not cfg.is_moe or cfg.n_shared_experts <= 0:
        raise ValueError("granite: every layer routes beside a shared MLP")
    E, L = cfg.hidden_size, cfg.num_layers
    Lm, La = cfg.num_mamba_layers, cfg.num_attention_layers
    H, d_in, conv = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    X, Xh, Fm = cfg.num_experts, cfg.held_experts[1], cfg.moe_intermediate_size
    Fs = cfg.n_shared_experts * Fm
    keys = iter(jax.random.split(key, 20))

    def w(shape, fan_in):
        z = jax.random.normal(next(keys), shape, jnp.float32)
        return (z / jnp.sqrt(fan_in)).astype(dtype)

    ones = lambda shape: jnp.ones(shape, jnp.float32)
    return {
        "embed": w((cfg.vocab_size, E), E),
        "final_norm": ones((E,)),
        "layers": {
            "attn_norm": ones((L, E)), "mlp_norm": ones((L, E)),
            "router": w((L, E, X), E),
            "w_gate": w((L, Xh, E, Fm), E), "w_up": w((L, Xh, E, Fm), E),
            "w_down": w((L, Xh, Fm, E), Fm),
            "w_sh_gate": w((L, E, Fs), E), "w_sh_up": w((L, E, Fs), E),
            "w_sh_down": w((L, Fs, E), Fs),
        },
        "mamba": {
            "w_in": w((Lm, E, d_in + conv + H), E),
            "conv_w": w((Lm, cfg.mamba_d_conv, conv), cfg.mamba_d_conv).astype(jnp.float32),
            "conv_b": jnp.zeros((Lm, conv), jnp.float32),
            # softplus(dt_bias) about 0.01-0.1 and A = -exp(A_log) in
            # -1..-16 (Mamba-2's own init): slow decays
            "dt_bias": jnp.full((Lm, H), -3.0, jnp.float32),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)), (Lm, H)
            ),
            "D": ones((Lm, H)),
            "gate_norm": ones((Lm, d_in)),
            "w_out": w((Lm, d_in, E), d_in),
        },
        "attn": {
            "wq": w((La, E, Hq * D), E), "wk": w((La, E, Hkv * D), E),
            "wv": w((La, E, Hkv * D), E), "wo": w((La, Hq * D, E), Hq * D),
        },
    }


def _wd(params: Params):
    return wdtype(params["layers"]["w_sh_gate"])


@region("embed")
def _embed(params: Params, cfg: ModelConfig, token_ids) -> jnp.ndarray:
    x = params["embed"][token_ids].astype(jnp.float32) * cfg.embedding_multiplier
    return x.astype(_wd(params))


@region("head")
def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return llama._unembed(params, cfg, x) / cfg.logits_scaling


def _scale(cfg: ModelConfig) -> float:
    return cfg.attention_multiplier or cfg.head_dim ** -0.5


def _add(cfg: ModelConfig, x, y):
    """x + residual_multiplier * y, the product in float32."""
    return x + (y.astype(jnp.float32) * cfg.residual_multiplier).astype(x.dtype)


# ------------------------------------------------------------ the halves


class _Dec(NamedTuple):
    """The decode rows of a step: flat rows [0, R)."""

    R: int
    tables: jnp.ndarray  # [R, CB]
    seq_lens: jnp.ndarray  # [R]: context with this token; 0 = inactive
    active: jnp.ndarray  # [R] bool
    plan: object  # where the rows' K/V go
    use_kernel: Optional[bool]


class _Pf(NamedTuple):
    """The prefill chunks of a step: flat rows [R, R + P*Lpad)."""

    P: int
    Lpad: int
    tables: jnp.ndarray  # [P, CB]: the KV blocks' columns alone
    slots: jnp.ndarray  # [P]: state slot (-1 = a padding row)
    start: jnp.ndarray  # [P]
    length: jnp.ndarray  # [P]
    plan: object


def _split_tables(block_tables):
    """A prefill row's table -> (its KV blocks' columns, its state slot)."""
    return block_tables[:, :-1], block_tables[:, -1].astype(jnp.int32) - 1


@region("state_mixer")
def _mamba_mixer(lp, cfg: ModelConfig, h, m, S, conv, dec: Optional[_Dec],
                 pf: Optional[_Pf]):
    """The Mamba-2 mixer over flat rows h [T, E] (decode rows first, then
    the chunks' rows), Mamba layer `m`: returns (out [T, E], S', conv')."""
    H, Pd, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    d_in, Cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    f32 = jnp.float32
    zxd = jnp.einsum("te,ef->tf", h, wt(lp["w_in"]), preferred_element_type=f32)
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + Cd], zxd[:, d_in + Cd:]
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


@region("attn_proj")
def _qkv(lp, cfg: ModelConfig, h):
    """h [T, E] -> q [T, Hq, D], k, v [T, Hkv, D]: no bias, no rotary."""
    T = h.shape[0]
    q = jnp.einsum("te,eh->th", h, wt(lp["wq"])).reshape(T, cfg.num_heads, cfg.head_dim)
    k = jnp.einsum("te,eh->th", h, wt(lp["wk"])).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = jnp.einsum("te,eh->th", h, wt(lp["wv"])).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _attn_mixer(lp, cfg: ModelConfig, h, a, K, V, dec: Optional[_Dec],
                pf: Optional[_Pf], use_ragged=None, interpret=False):
    """The GQA mixer over flat rows h [T, E], attention layer `a`: every
    row's K/V lands in the stacks first, then each half attends."""
    q, k, v = _qkv(lp, cfg, h)
    R = dec.R if dec is not None else 0
    scale = _scale(cfg)
    if dec is not None:
        K, V = kv_write_ops.write_kv(K, V, dec.plan, k[:R], v[:R], a)
    if pf is not None:
        K, V = kv_write_ops.write_kv(K, V, pf.plan, k[R:], v[R:], a)
        q_pf = q[R:].reshape(pf.P, pf.Lpad, *q.shape[1:])
    if dec is not None and pf is not None:
        o_dec, o_pf = mixed_attention(
            q[:R], q_pf, K, V, dec.tables, dec.seq_lens, pf.tables, pf.start,
            pf.length, scale, use_ragged=use_ragged, interpret=interpret, layer=a,
        )
        o = jnp.concatenate([o_dec, o_pf.reshape(-1, *o_pf.shape[2:])], axis=0)
    elif dec is not None:
        o = paged_attention(
            q, K, V, dec.tables, dec.seq_lens, scale,
            use_kernel=dec.use_kernel, layer=a,
        )
    else:
        o = prefill_attention(
            q_pf, K, V, pf.tables, pf.start, pf.length, scale, layer=a,
        )
        o = o.reshape(-1, *o.shape[2:])
    with region("attn_proj"):
        flat = o.reshape(o.shape[0], -1).astype(h.dtype)
        return jnp.einsum("th,he->te", flat, wt(lp["wo"])), K, V


def _layer(lp, cfg: ModelConfig, x, valid, kind, mix, caches):
    """ONE layer body for both kinds: `mix(normed rows, caches) ->
    (mixer output, caches)` is the layer's mixer, of `kind`, over the
    carried pools."""
    y, caches = mix(block_norm(x, lp["attn_norm"], cfg.rms_norm_eps), caches)
    with region(MIXER_REGIONS[kind]):
        x = _add(cfg, x, y)
    u = block_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    with region("ffn"):
        return _add(cfg, x, llama._mlp_block(lp, cfg, u, rows_valid=valid)), caches


def _run_layers(params, cfg: ModelConfig, x, k_caches, v_caches, valid,
                dec: Optional[_Dec], pf: Optional[_Pf], use_ragged=None,
                interpret=False):
    """The stack over flat token rows x [T, E]: one scan a run of equal
    layer kind, every pool on every scan's carry."""

    def mixer(kind, lp, i):
        def mix(h, caches):
            (K, S), (V, conv) = caches
            if kind == "mamba":
                y, S, conv = _mamba_mixer(lp, cfg, h, i, S, conv, dec, pf)
            else:
                y, K, V = _attn_mixer(lp, cfg, h, i, K, V, dec, pf, use_ragged, interpret)
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

    def at(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
        )

    carry = (x, k_caches, v_caches)
    for seg in _segments(cfg):
        stack = params[MIXER_STACKS[seg.kind]]

        def body(carry, i, seg=seg, stack=stack):
            x, kc, vc = carry
            lp = {**at(common, seg.first + i), **at(stack, seg.kind_first + i)}
            if experts is not None:
                lp["experts"] = (experts, seg.first + i)
            with moe_ops.layer_stats() as stats:
                x, (kc, vc) = _layer(
                    lp, cfg, x, valid, seg.kind,
                    mixer(seg.kind, lp, seg.kind_first + i), (kc, vc),
                )
            return (x, kc, vc), stats.total()

        with region("stack_slice"):  # as llama._scan_layers
            carry, counts = jax.lax.scan(body, carry, jnp.arange(seg.n, dtype=jnp.int32))
        moe_ops.add_step(counts)
    return carry


# ---------------------------------------------------------------- steps


def _dec_half(k_caches, positions, tables, active, use_kernel) -> _Dec:
    return _Dec(
        positions.shape[0], tables, jnp.where(active, positions + 1, 0), active,
        kv_write_ops.write_plan(k_caches[0], tables, positions, active, 1), use_kernel,
    )


def _pf_half(k_caches, block_tables, start, length, P, Lpad) -> Tuple[_Pf, jnp.ndarray]:
    tables, slots = _split_tables(block_tables)
    pf = _Pf(
        P, Lpad, tables, slots, start, length,
        kv_write_ops.write_plan(k_caches[0], tables, start, length, Lpad),
    )
    valid = jnp.arange(Lpad, dtype=jnp.int32)[None, :] < length[:, None]
    return pf, valid.reshape(-1)


def decode_step(
    params: Params, cfg: ModelConfig, k_caches, v_caches,
    token_ids,  # [R] int32
    positions,  # [R] int32 (where the attention layers' K/V rows go)
    block_tables,  # [R, CB] int32: KV blocks (a row's state slot is the row)
    active,  # [R] bool
    use_kernel: bool | None = None,
):
    """One generation step for R rows. Returns (logits [R, V], k', v')."""
    dec = _dec_half(k_caches, positions, block_tables, active, use_kernel)
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
    block_tables,  # [P, CB + 1] int32: KV blocks, then slot + 1
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
    pf, valid = _pf_half(k_caches, block_tables, start_pos, true_len, P, Lpad)
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
    use_ragged: bool | None = None,
    lora_dec=None, lora_pf=None, rope_delta=None, interpret: bool = False,
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
    dec = _dec_half(k_caches, dec_positions, dec_tables, dec_active, None)
    pf, pf_valid = _pf_half(k_caches, pf_tables, pf_start, pf_len, P, Lpad)
    x = _embed(params, cfg, jnp.concatenate([dec_tokens, pf_tokens.reshape(-1)]))
    x, k_caches, v_caches = _run_layers(
        params, cfg, x, k_caches, v_caches,
        jnp.concatenate([dec_active, pf_valid]), dec, pf, use_ragged, interpret,
    )
    last = llama._last_rows(x[R:].reshape(P, Lpad, -1), pf_len)
    return (
        _unembed(params, cfg, x[:R]), _unembed(params, cfg, last),
        k_caches, v_caches,
    )


# ---------------------------------------------------------------- oracle


def hidden_dense(params: Params, cfg: ModelConfig, token_ids, rows_valid=None):
    """Final-norm hidden states [B, L, E] of a plain causal forward: the
    scan as ONE chunk from an empty state, materialised attention, the
    all-experts combine (llama._mlp): the oracle of the step programs.
    No pool, no cache, no kernel."""
    B, L = token_ids.shape
    f32 = jnp.float32
    H, Pd, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    d_in, Cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    x = _embed(params, cfg, token_ids)

    def mamba(lp, h):  # [L, E]
        zxd = jnp.einsum("te,ef->tf", h, wt(lp["w_in"]), preferred_element_type=f32)
        z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + Cd], zxd[:, d_in + Cd:]
        c = mamba_ops.conv_dense(xbc, lp["conv_w"], lp["conv_b"])
        y = mamba_ops.chunk_form(
            c[:, :d_in].reshape(L, H, Pd), jax.nn.softplus(dt + lp["dt_bias"]),
            -jnp.exp(lp["A_log"].astype(f32)),
            c[:, d_in:d_in + G * N].reshape(L, G, N),
            c[:, d_in + G * N:].reshape(L, G, N), lp["D"],
        )
        return _gated_out(lp, cfg, y.reshape(L, d_in), z)

    def attention(lp, h):
        q, k, v = (t.astype(f32) for t in _qkv(lp, cfg, h))
        g = cfg.num_heads // cfg.num_kv_heads
        s = jnp.einsum("qhgd,khd->hgqk", q.reshape(L, -1, g, cfg.head_dim), k) * _scale(cfg)
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(L, -1)
        return jnp.einsum("th,he->te", o.astype(h.dtype), wt(lp["wo"]))

    li = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(cfg.layer_types):
        lp = {k: v[l] for k, v in params["layers"].items()}
        lp.update({k: v[li[kind]] for k, v in params[MIXER_STACKS[kind]].items()})
        li[kind] += 1
        mix = mamba if kind == "mamba" else attention
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        x = _add(cfg, x, jax.vmap(lambda hx: mix(lp, hx))(h))
        u = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = _add(cfg, x, jax.vmap(lambda ux: llama._mlp(lp, cfg, ux))(u))
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def forward_dense(params: Params, cfg: ModelConfig, token_ids) -> jnp.ndarray:
    return llama._project(
        params, cfg, hidden_dense(params, cfg, token_ids)
    ) / cfg.logits_scaling
