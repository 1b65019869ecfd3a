"""Model architecture configs + registry.

The engine tier the reference delegates to a non-vendored CUDA submodule
(SURVEY.md §2.3) is first-class here. Configs cover the north-star families
(BASELINE.json): Llama-3 dense, Qwen2, and Mixtral/DeepSeek-style MoE.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional


# Layer kinds of a hybrid stack whose sequence memory is a state slot.
STATE_LAYER_KINDS = ("mamba", "kda", "lightning")
# A block with BOTH mixers: a Mamba-2 mixer and a GQA mixer over the same
# normed input, their outputs summed into one residual add (Falcon-H1).
PARALLEL_KIND = "parallel"
# The mixers a layer of each kind runs: what memory a layer holds (a state
# slot, rows of the paged pool, rows of the window pool), how many layers
# each pool has and what the mixers weigh all follow from this table.
LAYER_MIXERS = {
    "mamba": ("mamba",), "kda": ("kda",), "attention": ("attention",),
    "window": ("window",), PARALLEL_KIND: ("mamba", "attention"),
    # a decayed outer-product state with rotary (ops/lightning.py), and GQA
    # over the pages a query group selects (ops/sparse_attention.py): rows
    # of the paged pool and of the compressed-key pool beside it
    "lightning": ("lightning",), "sparse": ("attention",),
}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 500000.0
    # HF `rope_scaling` (ops/rope.rope_parameters implements the math;
    # runtime/weights.config_from_hf parses it and LOUDLY rejects types
    # not listed there). "" = plain theta. Tuples keep the frozen config
    # hashable for jit static args.
    # "linear" | "dynamic" | "llama3" | "longrope" | "yarn"
    rope_scaling_type: str = ""
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 0  # 0 = max_position_embeddings
    rope_low_freq_factor: float = 1.0  # llama3
    rope_high_freq_factor: float = 4.0  # llama3
    rope_short_factor: tuple = ()  # longrope per-band tables [head_dim/2]
    rope_long_factor: tuple = ()
    rope_attention_factor: float = 0.0  # longrope/yarn; 0 = HF formula
    rope_beta_fast: float = 32.0  # yarn correction-range bounds
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0  # yarn (DeepSeek): attention-factor numerator
    rope_mscale_all_dim: float = 0.0  # ...and denominator / softmax scale
    rope_scaling_truncate: bool = True  # yarn: floor/ceil the range
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    # MoE (0 experts = dense).
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    # Router semantics. "softmax" scoring + norm_topk_prob covers
    # Mixtral/Qwen3 (top-k renormalized full-softmax probs — identical
    # to softmaxing the top-k logits); DeepSeek adds "sigmoid" scoring
    # (V3), group-limited selection (n_group/topk_group; "noaux_tc"
    # scores groups by top-2 sums with a selection-only correction bias,
    # "group_limited_greedy" by group max), optional non-normalized
    # weights, and routed_scaling_factor.
    scoring_func: str = "softmax"
    topk_method: str = "plain"
    n_group: int = 0
    topk_group: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Sliding-window attention (0 = full).
    sliding_window: int = 0
    # Gemma-family deltas: GELU-tanh gated MLP (vs SwiGLU), embeddings
    # scaled by sqrt(hidden_size), and zero-centered RMSNorm weights in
    # the CHECKPOINT (the loader adds 1 so rms_norm stays uniform).
    mlp_act: str = "silu"
    embed_scale: bool = False
    norm_zero_centered: bool = False
    # Qwen2-VL M-RoPE half-dim sections ((t, h, w) streams; empty =
    # standard 1D RoPE). Equal streams reduce M-RoPE to standard RoPE,
    # so text tokens and decode steps need no special handling; image
    # spans inside a prompt carry [3, L] positions (models/llama.py).
    mrope_section: tuple = ()
    # Disable head_dim<128 packed cache rows (kv_cache.kv_pack_factor).
    # Set by the executor (sharding.resolve_kv_packing) when tp doesn't
    # divide the packed head count — the unpacked layout keeps every
    # tp that divides num_kv_heads functional via the gather path.
    kv_pack_disable: bool = False
    # QKV projection bias (Qwen2-style).
    attn_bias: bool = False
    # Per-head RMSNorm on q and k before RoPE (Qwen3-style QK-norm).
    qk_norm: bool = False
    # Multi-head Latent Attention (DeepSeek-V2/V3). kv_lora_rank > 0 turns
    # MLA on: the paged cache stores ONE compressed latent row per token
    # (kv_lora_rank + qk_rope_head_dim floats) instead of per-head K/V —
    # e.g. 576 vs 2048 floats/token for a 70B-class GQA layout, a ~3.5x
    # HBM/bandwidth win for long contexts.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0  # 0 = direct q projection (V2-Lite style)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE shared experts (DeepSeek style): dense FFN of
    # n_shared_experts * moe_intermediate_size always active.
    n_shared_experts: int = 0
    # The span of the `num_experts` routed experts this deployment HOLDS,
    # (first, count); () = all of them. The router stays `num_experts`
    # wide and selects over all of them; the expert leaves hold `count`
    # experts and the layer computes the part of its result they give
    # (the shared experts and the dense path are whole). What the absent
    # experts would add is another holder's to compute and to add.
    experts_held: tuple = ()
    # DeepSeek-V2/V3 heterogeneous stack: the first k layers use a dense
    # SwiGLU of `intermediate_size` instead of the MoE block (HF config
    # first_k_dense_replace). The param pytree splits into a `dense_layers`
    # prefix stack and the MoE `layers` suffix stack: one lax.scan each,
    # over the same carried latent pool (models/deepseek.py _run_layers).
    first_k_dense_replace: int = 0
    # Power retention (arXiv:2507.04239; models/brumby.py): 0 = softmax
    # attention over a paged cache; p > 0 replaces it by retention of
    # degree p, whose sequence state is ONE fixed slot of the executor's
    # state pool (ops/retention.py), not blocks that grow with the context.
    retention_degree: int = 0
    # A hybrid stack (models/granite.py): the mixer of every layer, in
    # order: a STATE-LAYER kind, whose sequence memory is one slot of a
    # state pool ("mamba": a Mamba-2 state-space mixer, ops/mamba.py;
    # "kda": a gated delta rule with a decay per channel, ops/kda.py), or
    # "attention" (GQA over the paged K/V pool, which then holds the
    # attention layers alone), or "parallel": a block that runs a Mamba-2
    # mixer AND a GQA mixer on one normed input and adds both to the
    # residual stream at once, so it holds a state slot and K/V rows
    # (LAYER_MIXERS). A stack has one state-layer kind at most.
    # () = every layer attends. The pattern is data of the configuration.
    layer_types: tuple = ()
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    # Kimi Delta Attention layers ("kda"): heads of one key and value
    # width, a causal convolution of `kda_d_conv` taps over q, k and v,
    # the decay's and the output gate's projections low-rank pairs of
    # rank `kda_gate_rank`, beta in (0, 2) where
    # `kda_neg_eigval` (the transition's eigenvalues may be negative)
    # and in (0, 1) where not.
    kda_n_heads: int = 0
    kda_d_head: int = 0
    kda_d_conv: int = 0
    kda_gate_rank: int = 0
    kda_neg_eigval: bool = True
    # A hybrid stack's attention layers gate their output per lane,
    # `W_o [sigmoid(W_gate x) * attn]` (`W_gate` [E, Hq D]), or with
    # `attn_gate_per_head` per HEAD (`W_gate` [E, Hq]: one sigmoid a
    # query head on all of its lanes).
    attn_gate: bool = False
    attn_gate_per_head: bool = False
    # A hybrid stack's WINDOW layers ("window" in layer_types: GQA over
    # the last `sliding_window` positions, in a paged pool of their own
    # whose blocks are freed behind the sequence) have their own KV head
    # count and rope theta and, with `window_sink`, a learned logit a
    # query head in the softmax's denominator whose mass is dropped. For
    # both attention kinds of the stack: `attn_v_head_dim` is the value
    # head's width (0 = head_dim), `rotary_dim` the lanes of a head,
    # from lane 0, that rotate (0 = no rotary: both older families are
    # NoPE), `attn_value_scale` multiplies the values.
    # The two kinds may differ in QUERY heads and in rotary LANES too
    # (`window_num_heads`, `window_rotary_dim`: 0 = as the full layers),
    # and in the rotary TABLE: the full layers' follows `rope_scaling_*`
    # over `rotary_dim` lanes (ops/rope.rope_parameters: YaRN's
    # frequencies, its attention factor on cos and sin), the window
    # layers' is plain at `window_rope_theta`.
    window_kv_heads: int = 0
    window_rope_theta: float = 10000.0
    window_sink: bool = False
    window_num_heads: int = 0
    window_rotary_dim: int = 0
    attn_v_head_dim: int = 0
    rotary_dim: int = 0
    attn_value_scale: float = 1.0
    # Granite's four multipliers (HF GraniteMoeHybridConfig): the token
    # embedding is scaled by `embedding_multiplier`, attention scores by
    # `attention_multiplier` (0 = head_dim**-0.5), each block's addition
    # to the residual stream by `residual_multiplier`, and the logits are
    # DIVIDED by `logits_scaling`.
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # Falcon-H1's muP multipliers (HF FalconH1Config), each 1 by default
    # (an empty tuple: every entry 1): the logits are multiplied by
    # `lm_head_multiplier`; an attention mixer's input by
    # `attention_in_multiplier`, its keys by `key_multiplier`, its output
    # by `attention_out_multiplier`; a Mamba-2 mixer's input by
    # `ssm_in_multiplier`, the z, x, B, C and dt lanes of its input
    # projection's result by `ssm_multipliers[0..4]`, its output by
    # `ssm_out_multiplier`; a dense MLP's gate pre-activation by
    # `mlp_multipliers[0]` and its down product by `mlp_multipliers[1]`.
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = ()
    mlp_multipliers: tuple = ()
    # Lightning linear-attention layers ("lightning" in layer_types:
    # `S <- lambda_h S + k^T v`, `o = q S / sqrt(d)`, QK-norm, rotary on
    # every lane of q and k at `rope_theta`, an output norm and a per-lane
    # sigmoid gate): heads of one key and value width. The decay is a
    # CONSTANT of the head and of the layer's PUBLISHED index
    # (`layer_ids[l]` of `published_layers`; ops/lightning.py), so a cut
    # of the published depth keeps its layers' own decays.
    lightning_n_heads: int = 0
    lightning_d_head: int = 0
    layer_ids: tuple = ()  # the published index of every layer (() = 0..L-1)
    published_layers: int = 0  # the published depth (0 = num_layers)
    # Block-sparse attention layers ("sparse" in layer_types, InfLLM-V2:
    # ops/sparse_attention.py): a query past `sparse_dense_len` tokens of
    # context attends `sparse_topk` blocks of `sparse_block_size` tokens a
    # KV head: the first `sparse_init_blocks`, the blocks that cover the
    # last `sparse_window` tokens, and the best of the rest by the query
    # group's softmax over compressed keys (the mean of
    # `sparse_kernel_size` keys every `sparse_kernel_stride`).
    sparse_block_size: int = 0
    sparse_topk: int = 0
    sparse_kernel_size: int = 0
    sparse_kernel_stride: int = 0
    sparse_init_blocks: int = 0
    sparse_window: int = 0
    sparse_dense_len: int = 0

    @property
    def is_retention(self) -> bool:
        return self.retention_degree > 0

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_types)

    def mixer_layers(self, mixer: str) -> int:
        """Layers of the stack that run `mixer` (alone or beside another)."""
        return sum(mixer in LAYER_MIXERS.get(k, ()) for k in self.layer_types)

    @property
    def state_layer_kind(self) -> str:
        """The stack's state mixer, whose layers hold a state slot ("" = none)."""
        kinds = [k for k in STATE_LAYER_KINDS if self.mixer_layers(k)]
        if len(kinds) > 1:
            raise ValueError(f"layer_types holds two state-layer kinds: {kinds}")
        return kinds[0] if kinds else ""

    @property
    def num_state_layers(self) -> int:
        """Layers whose sequence memory is a slot of the state pool."""
        return sum(self.mixer_layers(k) for k in STATE_LAYER_KINDS)

    @property
    def has_state_pool(self) -> bool:
        """A sequence owns one fixed slot of a state pool for its life."""
        return self.is_retention or self.num_state_layers > 0

    @property
    def has_paged_cache(self) -> bool:
        """A sequence owns blocks of a paged pool that grow with it."""
        return not self.is_retention and (
            not self.layer_types or self.mixer_layers("attention") > 0
        )

    @property
    def num_window_layers(self) -> int:
        """Layers that hold rows of the window pool, a second paged pool
        whose blocks a sequence frees once they are `sliding_window`
        behind it."""
        return self.layer_types.count("window")

    def attn_heads(self, kind: str = "attention") -> int:
        """Query heads of a hybrid stack's attention layers of `kind`."""
        return (self.window_num_heads if kind == "window" else 0) or self.num_heads

    def attn_rotary_dim(self, kind: str = "attention") -> int:
        """Lanes of a head, from lane 0, that rotate in layers of `kind`."""
        return (self.window_rotary_dim if kind == "window" else 0) or self.rotary_dim

    @property
    def num_sparse_layers(self) -> int:
        """Layers whose queries select their pages (they hold rows of the
        paged pool AND of the compressed-key pool)."""
        return self.layer_types.count("sparse")

    @property
    def sparse_keys_per_block(self) -> int:
        """Compressed keys that START in one block of the paged pool."""
        return self.sparse_block_size // self.sparse_kernel_stride

    @property
    def value_head_dim(self) -> int:
        """Width of a value head of a hybrid stack's attention layers."""
        return self.attn_v_head_dim or self.head_dim

    @property
    def num_attention_layers(self) -> int:
        """Layers that hold rows of the paged pool."""
        if self.layer_types:
            return self.mixer_layers("attention")
        return 0 if self.is_retention else self.num_layers

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Lanes of the convolution: x, B and C side by side."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kda_d_inner(self) -> int:
        return self.kda_n_heads * self.kda_d_head

    @property
    def kda_conv_dim(self) -> int:
        """Lanes of the convolution: q, k and v side by side."""
        return 3 * self.kda_d_inner

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> tuple:
        """(first, count) of the routed experts held (experts_held)."""
        return tuple(self.experts_held) or (0, self.num_experts)

    @property
    def expert_layers(self) -> int:
        """Layers with routed experts: all but the dense prefix."""
        if self.num_experts <= 0:
            return 0
        return self.num_layers - self.first_k_dense_replace

    @property
    def mla_row_dim(self) -> int:
        """True latent floats per token: c_kv + shared RoPE key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mla_cache_dim(self) -> int:
        """Latent cache lanes per token: mla_row_dim padded to a multiple
        of 128. Mosaic DMA slices need 128-aligned lane extents on real
        hardware (chip finding, round 3), so the pool stores zero-padded
        rows; q_lat pads with zeros too, making the extra lanes inert in
        every score/context contraction."""
        return (self.mla_row_dim + 127) // 128 * 128


def approx_param_count(cfg: ModelConfig) -> int:
    """Analytic parameter count (norm weights omitted — noise at scale).
    Single source for HBM budgeting: runtime/executor._decide_num_blocks
    sizes the KV pool with it and __graft_entry__'s dress rehearsal
    checks serving layouts against it."""
    E, L = cfg.hidden_size, cfg.num_layers
    if cfg.is_hybrid:
        attn = _hybrid_mixer_params(cfg) / L  # the pattern's mean a layer
    elif cfg.is_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        kvr, qr, Hq = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.num_heads
        attn = (
            E * (kvr + dr)
            + Hq * kvr * (dn + dv)
            + Hq * dv * E
            + (E * qr + qr * Hq * (dn + dr) if qr else E * Hq * (dn + dr))
        )
    else:
        attn = (
            E * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
            + cfg.num_heads * cfg.head_dim * E
        )
    if cfg.is_moe:
        moe_mlp = 3 * E * (
            cfg.moe_intermediate_size * cfg.held_experts[1]
            + cfg.n_shared_experts * cfg.moe_intermediate_size
        ) + E * cfg.num_experts  # router
    else:
        moe_mlp = 3 * E * cfg.intermediate_size
    kd = cfg.first_k_dense_replace
    mlp_total = (L - kd) * moe_mlp + kd * 3 * E * cfg.intermediate_size
    return (
        cfg.vocab_size * E * (1 if cfg.tie_word_embeddings else 2)
        + L * attn
        + mlp_total
    )


def _hybrid_mixer_params(cfg: ModelConfig) -> int:
    """Mixer matrices of a hybrid stack over all its layers."""
    E, D, Dv = cfg.hidden_size, cfg.head_dim, cfg.value_head_dim

    def gqa(kind, kv_heads):  # q and k at D lanes a head, v and o at Dv; the gate
        heads = cfg.attn_heads(kind)
        gate = heads * (1 if cfg.attn_gate_per_head else D) if cfg.attn_gate else 0
        return E * ((heads + kv_heads) * (D + Dv) + gate)

    full = gqa("attention", cfg.num_kv_heads)
    kind = cfg.state_layer_kind
    state = _STATE_MIXER_PARAMS[kind](cfg) if kind else 0
    return (
        cfg.num_state_layers * state + cfg.num_attention_layers * full
        + cfg.num_window_layers * gqa("window", cfg.window_kv_heads)
    )


def _mamba_mixer_params(cfg: ModelConfig) -> int:
    E, d_in, conv = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_conv_dim
    return E * (d_in + conv + cfg.mamba_n_heads) + d_in * E + conv * cfg.mamba_d_conv


def _kda_mixer_params(cfg: ModelConfig) -> int:
    E, d_in, r = cfg.hidden_size, cfg.kda_d_inner, cfg.kda_gate_rank
    return (
        4 * E * d_in + 2 * (E * r + r * d_in) + E * cfg.kda_n_heads
        + cfg.kda_conv_dim * cfg.kda_d_conv
    )


# matrices of one state layer's mixer, by kind
def _lightning_mixer_params(cfg: ModelConfig) -> int:
    # q, k, v, the gate and o, each hidden x (heads x d_head)
    return 5 * cfg.hidden_size * cfg.lightning_n_heads * cfg.lightning_d_head


_STATE_MIXER_PARAMS = {"mamba": _mamba_mixer_params, "kda": _kda_mixer_params,
                       "lightning": _lightning_mixer_params}


_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_model_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model config '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_model_configs():
    return sorted(_REGISTRY)


# --- Test-scale configs (CPU-runnable CI; SURVEY.md §4) ---------------------

register(
    ModelConfig(
        name="llama3-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        name="moe-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=128,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        name="gemma-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        mlp_act="gelu_tanh",
        embed_scale=True,
        norm_zero_centered=True,
        max_position_embeddings=1024,
    )
)

# --- Production configs -----------------------------------------------------

register(
    ModelConfig(
        name="llama3-1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        tie_word_embeddings=True,
    )
)

register(
    ModelConfig(
        name="llama3-3b",
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        tie_word_embeddings=True,
    )
)

register(
    ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
    )
)

register(
    ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
    )
)

register(
    ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        attn_bias=True,
    )
)

register(
    ModelConfig(
        name="qwen3-8b",
        vocab_size=151936,
        hidden_size=4096,
        intermediate_size=12288,
        num_layers=36,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
    )
)

register(
    # Qwen3-30B-A3B: 128-expert top-8 MoE, no shared experts; router
    # weighting is softmax over the selected experts' logits, which the
    # shared _mlp already computes (identical to renormalized-top-k).
    ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=6144,
        num_layers=48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        num_experts=128,
        num_experts_per_tok=8,
        moe_intermediate_size=768,
    )
)

register(
    ModelConfig(
        name="qwen3-tiny",
        vocab_size=512,
        hidden_size=96,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=24,
        rope_theta=10000.0,
        qk_norm=True,
    )
)

register(
    # head_dim 64 with 2 kv heads: exercises the packed-pair KV layout
    # (kv_cache.kv_pack_factor P=2 -> one 128-lane cache row per pair).
    ModelConfig(
        name="llama3-packed-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        max_position_embeddings=1024,
    )
)

register(
    # The sharded-engine differential geometry (docs/SHARDING.md): 8 KV
    # heads so tp ∈ {2, 4, 8} all divide (llama3-tiny's Hkv=2 caps at
    # tp=2), head_dim 128 so every Pallas path is kernel-eligible
    # per-shard down to 1 head/shard (interpret mode on the virtual
    # mesh), and GQA ratio 2 so per-shard query packing still exercises
    # grouping. CPU-runnable; the same shape class as the llama3-70b
    # tp=8 serving layout (BASELINE round 3), just tiny.
    ModelConfig(
        name="llama3-shard-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        max_position_embeddings=1024,
    )
)

register(
    # The MoE serving differential geometry (docs/MOE.md): llama3-
    # shard-tiny's kernel-eligible attention dims (Hkv=8, D=128 — every
    # tp ∈ {1, 2, 4, 8} divides, every Pallas path eligible per-shard)
    # plus an 8-expert top-2 MoE whose dims keep every tp×ep
    # combination eligible too: X=8 divides ep ∈ {1, 2, 4, 8},
    # E=128 and Fm=256 are 128-lane multiples (the grouped-dispatch
    # kernel gate), and Fm%tp holds through tp=2. CPU-runnable; the
    # same shape class as the qwen3-30b-a3b / deepseek-v3 EP serving
    # layouts, just tiny.
    ModelConfig(
        name="moe-shard-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=256,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        name="qwen3-moe-tiny",
        vocab_size=512,
        hidden_size=96,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=24,
        rope_theta=10000.0,
        qk_norm=True,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
    )
)

register(
    # Power retention at test scale (models/brumby.py): GQA group of 2,
    # head_dim 16 (9 feature rows of 16 lanes: one kernel tile).
    ModelConfig(
        name="brumby-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        retention_degree=2,
        max_position_embeddings=4096,
    )
)

register(
    ModelConfig(
        name="deepseek-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,  # MLA is effectively MHA over latents
        head_dim=32,  # unused by MLA paths (qk dims below rule)
        # Pairwise-DISTINCT dims (kvr != dn != dv) so any transposed or
        # double-applied projection fails shape checks instead of silently
        # computing garbage.
        kv_lora_rank=40,
        q_lora_rank=48,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=24,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        name="deepseek-moe-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        kv_lora_rank=40,
        q_lora_rank=0,  # V2-Lite-style direct q projection
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=24,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
        n_shared_experts=2,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        # Real-V2/V3 shape: dense first layer + MoE suffix (HF
        # first_k_dense_replace) — drives the split-stack pytree paths.
        name="deepseek-hetero-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        kv_lora_rank=40,
        q_lora_rank=48,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=24,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
        n_shared_experts=2,
        first_k_dense_replace=1,
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        # deepseek-hetero-tiny as one of four holders sees it: group-
        # limited routing over 16 experts in 4 groups, experts 4-7 held
        # (the share test walks the four spans, tests/test_deepseek_mla.py).
        name="deepseek-held-tiny",
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=32,
        kv_lora_rank=40,
        q_lora_rank=48,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=24,
        num_experts=16,
        num_experts_per_tok=3,
        moe_intermediate_size=64,
        n_shared_experts=2,
        first_k_dense_replace=1,
        topk_method="group_limited_greedy",
        n_group=4,
        topk_group=2,
        norm_topk_prob=False,
        routed_scaling_factor=4.0,
        experts_held=(4, 4),
        max_position_embeddings=1024,
    )
)

register(
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=14336,
    )
)

register(
    ModelConfig(
        name="deepseek-v3",
        # arxiv 2412.19437 table 1 / HF config.json of DeepSeek-V3:
        # 671B total, 37B active, MLA + 256-expert MoE with 1 shared expert.
        vocab_size=129280,
        hidden_size=7168,
        intermediate_size=18432,
        num_layers=61,
        num_heads=128,
        num_kv_heads=128,
        head_dim=128,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        num_experts=256,
        num_experts_per_tok=8,
        moe_intermediate_size=2048,
        n_shared_experts=1,
        first_k_dense_replace=3,  # V3: first 3 layers dense
        rms_norm_eps=1e-6,
        # Real V3 ships yarn (config.json rope_scaling): 4k pretraining
        # context extended 40x; mscale_all_dim also scales the MLA
        # softmax temperature (models/deepseek.mla_softmax_scale).
        max_position_embeddings=163840,
        rope_scaling_type="yarn",
        rope_scaling_factor=40.0,
        rope_original_max_position=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
    )
)

register(
    ModelConfig(
        name="deepseek-v2",
        # https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json
        # (arXiv:2405.04434): 236B total, 21B active; MLA + 160 routed
        # experts in 8 groups (top 6 of the best 3 groups), 2 shared, the
        # first layer dense. Published sizes: no chip holds them, a cell
        # cuts them (benchmarks/configs/deepseek-v2.json).
        vocab_size=102400,
        hidden_size=5120,
        intermediate_size=12288,
        num_layers=60,
        num_heads=128,
        num_kv_heads=128,
        head_dim=128,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        num_experts=160,
        num_experts_per_tok=6,
        moe_intermediate_size=1536,
        n_shared_experts=2,
        first_k_dense_replace=1,
        topk_method="group_limited_greedy",
        n_group=8,
        topk_group=3,
        norm_topk_prob=False,
        routed_scaling_factor=16.0,
        rms_norm_eps=1e-6,
        max_position_embeddings=163840,
        rope_scaling_type="yarn",
        rope_scaling_factor=40.0,
        rope_original_max_position=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
    )
)

_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

register(
    # A hybrid stack at test scale (models/granite.py): two runs of Mamba-2
    # layers around one NoPE GQA layer, 8 heads of 16 lanes (one 128-lane
    # row of the state pool), 4 of 8 experts held beside a shared MLP.
    ModelConfig(
        name="granite-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        num_experts=8,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        n_shared_experts=2,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_n_heads=8,
        mamba_d_head=16,
        embedding_multiplier=12.0,
        attention_multiplier=1.0 / 16,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
    # (model_type granitemoehybrid), 32B-A9B, as ONE CHIP'S SHARE of it at
    # every published width: the first period of the layer pattern (10 of
    # 40 layers: 9 Mamba-2, 1 NoPE GQA), experts 0-35 of the 72 a layer
    # (top 10; the router stays 72 wide), vocabulary rows 0-50,175 of
    # 100,352 (benchmarks/configs/granite-4.0-h-small.json has the
    # deployment). Random weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="granite-4.0-h-small",
        vocab_size=50176,
        hidden_size=4096,
        intermediate_size=768,
        num_layers=10,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        num_experts=72,
        experts_held=(0, 36),
        num_experts_per_tok=10,
        moe_intermediate_size=768,
        n_shared_experts=2,  # one shared MLP of 1536 = 2 x 768
        layer_types=_GRANITE_PERIOD,
        mamba_d_state=128,
        mamba_d_conv=4,
        mamba_n_heads=128,
        mamba_d_head=64,
        mamba_n_groups=1,
        embedding_multiplier=12.0,
        attention_multiplier=0.0078125,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        max_position_embeddings=131072,
    )
)

_SOLAR_PERIOD = ("attention", "kda", "kda", "kda")

register(
    # The other hybrid at test scale (models/granite.py): one period of a
    # gated NoPE GQA layer and three KDA layers (4 heads of 16 x 16 state,
    # a convolution of 4 taps, gate rank 8), 4 of 8 experts held beside one
    # shared expert, an untied head, every multiplier 1.
    ModelConfig(
        name="solar-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rms_norm_eps=1e-5,
        num_experts=8,
        experts_held=(0, 4),
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        n_shared_experts=1,
        layer_types=_SOLAR_PERIOD,
        kda_n_heads=4,
        kda_d_head=16,
        kda_d_conv=4,
        kda_gate_rank=8,
        attn_gate=True,
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
    # (model_type solar_open2), 250B-A15B, as ONE CHIP'S SHARE of it at
    # every published width: two periods of the layer pattern (8 of 48
    # layers: 2 gated NoPE GQA, 6 KDA), experts 0-19 of the 320 a layer
    # (top 8; the router stays 320 wide), vocabulary rows 0-24,575 of
    # 196,608 (benchmarks/configs/solar-open2-250b.json has the
    # deployment). Random weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="solar-open2-250b",
        vocab_size=24576,
        hidden_size=4096,
        intermediate_size=1280,
        num_layers=8,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rms_norm_eps=1e-5,
        num_experts=320,
        experts_held=(0, 20),
        num_experts_per_tok=8,
        moe_intermediate_size=1280,
        n_shared_experts=1,
        layer_types=_SOLAR_PERIOD * 2,
        kda_n_heads=64,
        kda_d_head=128,
        kda_d_conv=4,
        kda_gate_rank=128,
        attn_gate=True,
        max_position_embeddings=1048576,
    )
)

register(
    # MiMo-V2-Flash's stack at a test's size (tests/test_mimo.py): full
    # GQA layers (1 KV head) beside window layers (2 KV heads, the last 8
    # positions, a sink logit a head) in the hybrid stack, key heads of
    # 24 lanes (the first 8 rotate, theta by layer kind) and value heads
    # of 16, a dense first layer, then 4 of 8 sigmoid top-2 experts and
    # no shared one, an untied head.
    ModelConfig(
        name="mimo-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=96,
        num_layers=6,
        num_heads=4,
        num_kv_heads=1,
        head_dim=24,
        rope_theta=5000000.0,
        rms_norm_eps=1e-5,
        num_experts=8,
        experts_held=(0, 4),
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        first_k_dense_replace=1,
        layer_types=("attention", "window", "window") * 2,
        sliding_window=8,
        window_kv_heads=2,
        window_rope_theta=10000.0,
        window_sink=True,
        attn_v_head_dim=16,
        rotary_dim=8,
        attn_value_scale=0.707,
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
    # (model_type mimo_v2_flash), 309B-A15B, as ONE CHIP'S SHARE of it at
    # every published width: published layer 0 (full attention, a dense
    # MLP) and one period of the layer pattern, published layers 6-11
    # (five window layers, one full), experts 0-15 of the 256 a layer
    # (sigmoid scores, top 8; the router stays 256 wide), vocabulary rows
    # 0-19,071 of 152,576 (benchmarks/configs/mimo-v2-flash.json has the
    # deployment). Random weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="mimo-v2-flash",
        vocab_size=19072,
        hidden_size=4096,
        intermediate_size=16384,
        num_layers=7,
        num_heads=64,
        num_kv_heads=4,
        head_dim=192,
        rope_theta=5000000.0,
        rms_norm_eps=1e-5,
        num_experts=256,
        experts_held=(0, 16),
        num_experts_per_tok=8,
        moe_intermediate_size=2048,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        first_k_dense_replace=1,
        layer_types=("attention",) + ("window",) * 5 + ("attention",),
        sliding_window=128,
        window_kv_heads=8,
        window_rope_theta=10000.0,
        window_sink=True,
        attn_v_head_dim=128,
        rotary_dim=64,
        attn_value_scale=0.707,
        max_position_embeddings=262144,
    )
)

_LAGUNA_PERIOD = ("attention", "window", "window", "window")

register(
    # Laguna-XS.2's stack at a test's size (tests/test_laguna.py): full
    # GQA layers of 6 query heads beside window layers of 8, both over 2
    # KV heads (query groups of 3 and 4), rotary on half a head with YaRN's
    # table on the full layers and on the whole head with a plain table on
    # the window layers (the last 24 positions: three blocks of 8), a gate
    # per head on both kinds, a dense first layer, then 32 sigmoid-scored
    # experts, all held, top 4 renormalised times 2.5, beside a shared one.
    ModelConfig(
        name="laguna-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=96,
        num_layers=5,
        num_heads=6,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        rope_scaling_type="yarn",
        rope_scaling_factor=16.0,
        rope_original_max_position=64,
        rope_beta_fast=4.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.27726,
        rms_norm_eps=1e-6,
        num_experts=32,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        n_shared_experts=1,
        scoring_func="sigmoid",
        routed_scaling_factor=2.5,
        first_k_dense_replace=1,
        layer_types=_LAGUNA_PERIOD + ("attention",),
        sliding_window=24,
        window_kv_heads=2,
        window_num_heads=8,
        window_rope_theta=10000.0,
        rotary_dim=8,
        window_rotary_dim=16,
        attn_gate=True,
        attn_gate_per_head=True,
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
    # (model_type laguna), 33.4B-A3B, as ONE PIPELINE STAGE of it at every
    # published width: published layers 0-4 of 40 (layer 0 full attention
    # and a dense MLP, then window, window, window, full with experts), ALL
    # 256 experts a layer (sigmoid scores, top 8 renormalised times 2.5)
    # beside a shared one, the whole vocabulary: 3,869.8 M parameters, 7.74
    # GB (benchmarks/configs/laguna-xs.2.json has the deployment). Random
    # weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="laguna-xs.2",
        vocab_size=100352,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=5,
        num_heads=48,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        rope_scaling_type="yarn",
        rope_scaling_factor=64.0,
        rope_original_max_position=4096,
        rope_beta_fast=64.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.4158883083359672,
        rms_norm_eps=1e-6,
        num_experts=256,
        num_experts_per_tok=8,
        moe_intermediate_size=512,
        n_shared_experts=1,
        scoring_func="sigmoid",
        routed_scaling_factor=2.5,
        first_k_dense_replace=1,
        layer_types=_LAGUNA_PERIOD + ("attention",),
        sliding_window=512,
        window_kv_heads=8,
        window_num_heads=64,
        window_rope_theta=10000.0,
        rotary_dim=64,
        window_rotary_dim=128,
        attn_gate=True,
        attn_gate_per_head=True,
        max_position_embeddings=262144,
    )
)

register(
    # Falcon-H1's block at a test's size (tests/test_falcon_h1.py): EVERY
    # layer the parallel kind (a Mamba-2 mixer and a GQA mixer on one
    # normed input), two B/C groups, a state wider than a head (32 > 16),
    # a query group of 3 (not a power of two), full rotary, a dense MLP,
    # an untied head, every multiplier different from 1 and from the
    # others.
    ModelConfig(
        name="falcon-h1-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=96,
        num_layers=3,
        num_heads=6,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=1e11,
        rms_norm_eps=1e-5,
        layer_types=("parallel",) * 3,
        rotary_dim=16,
        mamba_d_state=32,
        mamba_d_conv=4,
        mamba_n_heads=16,
        mamba_d_head=16,
        mamba_n_groups=2,
        embedding_multiplier=5.5,
        lm_head_multiplier=0.3,
        attention_in_multiplier=1.3,
        attention_out_multiplier=0.45,
        key_multiplier=0.6,
        ssm_in_multiplier=1.6,
        ssm_out_multiplier=0.35,
        ssm_multipliers=(0.7, 0.9, 0.5, 1.2, 0.8),
        mlp_multipliers=(1.4, 0.55),
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
    # (model_type falcon_h1), as ONE CHIP'S SHARE of it at every published
    # width: 9 of the 72 blocks (one pipeline stage of eight), every one a
    # GQA mixer (20 / 4 heads of 128, full rotary, theta 1e11) in parallel
    # with a Mamba-2 mixer (32 heads of 128 lanes, 2 groups, state 256)
    # and a dense SwiGLU of 21,504, vocabulary rows 0-32,639 of 261,120
    # (benchmarks/configs/falcon-h1-34b.json has the deployment). Random
    # weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="falcon-h1-34b",
        vocab_size=32640,
        hidden_size=5120,
        intermediate_size=21504,
        num_layers=9,
        num_heads=20,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1e11,
        rms_norm_eps=1e-5,
        layer_types=("parallel",) * 9,
        rotary_dim=128,
        mamba_d_state=256,
        mamba_d_conv=4,
        mamba_n_heads=32,
        mamba_d_head=128,
        mamba_n_groups=2,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        max_position_embeddings=262144,
    )
)

_SALA_SCALE_DEPTH, _SALA_DEPTH = 1.4, 32  # MiniCPM's muP: scale_depth / sqrt(published depth)

register(
    # MiniCPM-SALA's two mixers at a test's size (tests/test_minicpm_sala.py):
    # sparse layers (a query group of 2, QK-norm, NoPE, a gate; blocks of 8,
    # top-4 of them past 64 tokens: the first, the two that cover the last
    # 16 tokens, and one chosen) around lightning layers (4 heads of 16,
    # rotary, decays of published layers 10 and 11 of 32), a dense MLP, an
    # untied head, the three muP scalars.
    ModelConfig(
        name="minicpm-sala-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=96,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        attn_gate=True,
        layer_types=("sparse", "lightning", "lightning", "sparse"),
        lightning_n_heads=4,
        lightning_d_head=16,
        layer_ids=(9, 10, 11, 16),
        published_layers=_SALA_DEPTH,
        sparse_block_size=8,
        sparse_topk=4,
        sparse_kernel_size=4,
        sparse_kernel_stride=2,
        sparse_init_blocks=1,
        sparse_window=16,
        sparse_dense_len=64,
        embedding_multiplier=12.0,
        residual_multiplier=_SALA_SCALE_DEPTH / _SALA_DEPTH ** 0.5,
        logits_scaling=2.0,
        max_position_embeddings=4096,
    )
)

register(
    # https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
    # (model_type minicpm_sala), as ONE CHIP'S SHARE of it at every
    # published width: published layers 9 to 16 of 32 (the second of four
    # pipeline stages): a block-sparse layer (32 query heads over 2 KV
    # heads of 128, QK-norm, NoPE, a gate; InfLLM-V2's selection: blocks of
    # 64, top-64, past 8,192 tokens), six Lightning layers (32 heads of
    # 128, rotary, the decays of layers 10 to 15), a block-sparse layer; a
    # dense SwiGLU of 16,384; the whole vocabulary, untied
    # (benchmarks/configs/minicpm-sala.json has the deployment and what
    # is assumed). Random weights only: runtime/weights.py has no loader.
    ModelConfig(
        name="minicpm-sala",
        vocab_size=73448,
        hidden_size=4096,
        intermediate_size=16384,
        num_layers=8,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        attn_gate=True,
        layer_types=("sparse",) + ("lightning",) * 6 + ("sparse",),
        lightning_n_heads=32,
        lightning_d_head=128,
        layer_ids=tuple(range(9, 17)),
        published_layers=_SALA_DEPTH,
        sparse_block_size=64,
        sparse_topk=64,
        sparse_kernel_size=32,
        sparse_kernel_stride=16,
        sparse_init_blocks=1,
        sparse_window=2048,
        sparse_dense_len=8192,
        embedding_multiplier=12.0,
        residual_multiplier=_SALA_SCALE_DEPTH / _SALA_DEPTH ** 0.5,
        logits_scaling=16.0,
        max_position_embeddings=524288,
    )
)

register(
    # https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json:
    # Qwen3-14B's block with power-retention layers (degree 2 assumed, as
    # the retention keys are not in the published file). Random weights
    # only: runtime/weights.py has no loader for its checkpoint.
    ModelConfig(
        name="brumby-14b",
        vocab_size=151936,
        hidden_size=5120,
        intermediate_size=17408,
        num_layers=40,
        num_heads=40,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        qk_norm=True,
        retention_degree=2,
        max_position_embeddings=32768,
    )
)
