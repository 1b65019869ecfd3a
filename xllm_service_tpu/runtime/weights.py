"""Checkpoint loading: HuggingFace safetensors → the stacked-layer pytree.

Engine-tier component. The reference's engine (the absent xLLM submodule —
SURVEY.md §2.3) loads real HF checkpoints and relays `model_name` in
InstanceMetaInfo (reference xllm_service/common/types.h:169-171 analog);
here the executor (runtime/executor.py) calls `load_checkpoint` when
`EngineConfig.checkpoint_path` is set.

Design:
  * Self-contained safetensors parser (the format: u64 header length +
    JSON header + raw little-endian tensor data). mmap'd reads — no copy
    until the dtype cast — and bfloat16 via ml_dtypes, which the
    `safetensors` pip package's numpy API can't always represent.
  * HF Llama/Qwen2/Mixtral name mapping → per-layer tensors STACKED on a
    leading layer axis (models/llama.py contract). torch `nn.Linear`
    stores [out, in]; our einsum contracts [in, out], so every projection
    transposes on load.
  * RoPE: ops/rope.py applies split-half rotation — the same convention HF
    checkpoints are stored in — so q/k weights load with NO head
    permutation (only the transpose).
  * Each stacked leaf is `jax.device_put` with its NamedSharding from
    parallel/sharding.py, so a tp>1 mesh receives only its shard per
    device; host RAM briefly holds the full stacked array per leaf.
  * `save_hf_checkpoint` writes the inverse mapping (HF names, HF layouts)
    — round-trip tested in tests/test_weights.py and usable for exporting.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import re
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from xllm_service_tpu.models.configs import ModelConfig

Params = Dict[str, Any]

_ST_DTYPES = {
    "F64": np.float64,
    "F32": np.float32,
    "F16": np.float16,
    "BF16": ml_dtypes.bfloat16,
    "I64": np.int64,
    "I32": np.int32,
    "I16": np.int16,
    "I8": np.int8,
    "U8": np.uint8,
    "BOOL": np.bool_,
}
_ST_NAMES = {np.dtype(v): k for k, v in _ST_DTYPES.items()}


# ------------------------------------------------------------- safetensors IO


def read_safetensors(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (name, array) from one .safetensors file, zero-copy via mmap.

    Arrays are views into the mapping — cast or copy before the file goes
    away (load_checkpoint always casts into the staging buffer).
    """
    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen))
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        base = 8 + hlen
        for name, meta in header.items():
            if name == "__metadata__":
                continue
            dtype = _ST_DTYPES[meta["dtype"]]
            begin, end = meta["data_offsets"]
            arr = np.frombuffer(
                mm, dtype=dtype, count=int(np.prod(meta["shape"], dtype=np.int64)),
                offset=base + begin,
            ).reshape(meta["shape"])
            assert arr.nbytes == end - begin, f"{name}: size mismatch"
            yield name, arr


def write_safetensors(path: str, tensors: Dict[str, np.ndarray]) -> None:
    header: Dict[str, Any] = {}
    offset = 0
    arrays = {}
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr)
        arrays[name] = arr
        header[name] = {
            "dtype": _ST_NAMES[arr.dtype],
            "shape": list(arr.shape),
            "data_offsets": [offset, offset + arr.nbytes],
        }
        offset += arr.nbytes
    blob = json.dumps(header).encode()
    # Pad header to 8-byte alignment (spec allows trailing spaces).
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for arr in arrays.values():
            f.write(arr.tobytes())


def _shard_files(path: str) -> list:
    """All .safetensors files of a checkpoint dir, index-aware."""
    index = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        return sorted({os.path.join(path, v) for v in weight_map.values()})
    files = sorted(
        os.path.join(path, f)
        for f in os.listdir(path)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    return files


# ----------------------------------------------------------------- HF config


def _hf_sliding_window(hf: dict) -> int:
    """SWA window from an HF config, honoring the gates HF applies.

    Qwen2/Qwen3 configs carry a sliding_window VALUE but disable it via
    use_sliding_window=false. HF's max_window_layers semantics (Qwen2
    modeling: layer i slides iff i >= max_window_layers, i.e. the FIRST
    mwl layers use full attention): mwl == 0 means every layer slides —
    exactly our uniform-window stack; mwl >= num_layers means zero SWA
    layers — full attention, exactly HF. A genuinely MIXED stack
    (0 < mwl < num_layers with use_sliding_window=true) can't be
    represented by the scanned uniform layers and serving it as full
    attention would diverge from HF beyond the window — fail LOUDLY
    instead (same principle as the unsupported-rope_scaling reject).

    What runs and what loads are two things since the hybrid stack has a
    window kind of layer (models/granite.py: `layer_types` mixes "window"
    and "attention", each kind with a paged pool of its own): a mixed
    stack RUNS there on random weights (the presets `mimo-v2-flash`,
    `mimo-tiny`), but no checkpoint of one LOADS, because this loader maps
    a checkpoint onto the uniform llama tree alone and the tensor names of
    a `hybrid_layer_pattern` checkpoint are unconfirmed offline."""
    window = int(hf.get("sliding_window") or 0)
    if not window:
        return 0
    if not hf.get("use_sliding_window", True):
        return 0
    mwl = hf.get("max_window_layers")
    if mwl is None or int(mwl) == 0:
        return window
    if int(mwl) >= int(hf["num_hidden_layers"]):
        return 0
    raise NotImplementedError(
        f"mixed sliding-window stack (max_window_layers={mwl} of "
        f"{hf['num_hidden_layers']} layers, use_sliding_window=true) is "
        "not representable by the uniform scanned stack this loader "
        "fills; refusing to serve it as full attention. Window and full "
        "layers in one stack run on the hybrid stack (models/granite.py, "
        "layer_types with \"window\": random weights only); a checkpoint "
        "loader for that tree is not built"
    )


def falcon_h1_name_map(cfg: ModelConfig) -> Dict[str, str]:
    """Which tensor of an HF `falcon_h1` checkpoint each leaf of the hybrid
    stack's tree (models/granite.py, every layer the parallel kind) would
    load from, `{i}` the block: what WOULD load, because no loader is
    built (`checkpoint_path` is refused by name for a state-pool family,
    runtime/executor.py; the presets run on random weights) and the names
    are the released modelling code's as known offline, unconfirmed
    against a checkpoint. A loader would also transpose every projection
    (HF keeps [out, in]), lay `conv1d.weight` [lanes, 1, K] out as
    [K, lanes], and read `in_proj`'s rows in the order z | x | B | C | dt;
    the muP multipliers are the configuration's and touch no tensor."""
    if "parallel" not in cfg.layer_types:
        raise ValueError(f"{cfg.name}: not a stack of parallel blocks")
    block = "model.layers.{i}."
    return {
        "embed": "model.embed_tokens.weight",
        "final_norm": "model.final_layernorm.weight",
        "lm_head": "lm_head.weight",
        "layers.attn_norm": block + "input_layernorm.weight",
        "layers.mlp_norm": block + "pre_ff_layernorm.weight",
        **{f"layers.w_{k}": block + f"feed_forward.{k}_proj.weight" for k in ("gate", "up", "down")},
        **{f"attn.w{k}": block + f"self_attn.{k}_proj.weight" for k in "qkvo"},
        "mamba.w_in": block + "mamba.in_proj.weight",
        "mamba.conv_w": block + "mamba.conv1d.weight",
        "mamba.conv_b": block + "mamba.conv1d.bias",
        "mamba.dt_bias": block + "mamba.dt_bias",
        "mamba.A_log": block + "mamba.A_log",
        "mamba.D": block + "mamba.D",
        "mamba.gate_norm": block + "mamba.norm.weight",
        "mamba.w_out": block + "mamba.out_proj.weight",
    }


def _hf_rope_scaling(hf: dict) -> dict:
    """ModelConfig rope_scaling_* fields from an HF config dict.

    Implemented types (ops/rope.rope_parameters does the math): linear,
    dynamic NTK, llama3 (Llama-3.1/3.2), longrope (Phi-3, incl. the older
    "su" spelling), and yarn (real DeepSeek-V2/V3, incl. their
    mscale/mscale_all_dim attention scaling). "default"/mrope-only
    entries are no-ops. ANY other type raises — the one silent failure
    mode this loader refuses is a checkpoint that loads cleanly and
    serves diverging logits."""
    rs = hf.get("rope_scaling")
    if not rs or rs.get("mrope_section"):
        # mrope_section-only configs (Qwen2-VL) declare type "default"/
        # "mrope" — M-RoPE is handled by the _mrope_section path.
        return {}
    rtype = str(rs.get("rope_type") or rs.get("type") or "default")
    if rtype == "default":
        return {}
    if rtype == "linear":
        return dict(
            rope_scaling_type="linear",
            rope_scaling_factor=float(rs["factor"]),
        )
    if rtype == "dynamic":
        return dict(
            rope_scaling_type="dynamic",
            rope_scaling_factor=float(rs["factor"]),
            rope_original_max_position=int(
                rs.get("original_max_position_embeddings") or 0
            ),
        )
    if rtype == "llama3":
        return dict(
            rope_scaling_type="llama3",
            rope_scaling_factor=float(rs["factor"]),
            rope_low_freq_factor=float(rs["low_freq_factor"]),
            rope_high_freq_factor=float(rs["high_freq_factor"]),
            rope_original_max_position=int(
                rs["original_max_position_embeddings"]
            ),
        )
    if rtype == "yarn":
        return dict(
            rope_scaling_type="yarn",
            rope_scaling_factor=float(rs["factor"]),
            rope_original_max_position=int(
                rs.get("original_max_position_embeddings") or 0
            ),
            rope_beta_fast=float(rs.get("beta_fast") or 32.0),
            rope_beta_slow=float(rs.get("beta_slow") or 1.0),
            rope_mscale=float(rs.get("mscale") or 0.0),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim") or 0.0),
            rope_attention_factor=float(rs.get("attention_factor") or 0.0),
            rope_scaling_truncate=bool(rs.get("truncate", True)),
        )
    if rtype in ("longrope", "su"):
        # Phi-3 keeps original_max_position_embeddings at the TOP level
        # of config.json; newer HF layouts put it inside rope_scaling.
        orig = int(
            rs.get("original_max_position_embeddings")
            or hf.get("original_max_position_embeddings")
            or 0
        )
        if not orig:
            raise ValueError(
                "longrope rope_scaling needs original_max_position_"
                "embeddings (in rope_scaling or at the config top level)"
            )
        return dict(
            rope_scaling_type="longrope",
            rope_short_factor=tuple(
                float(v) for v in rs["short_factor"]
            ),
            rope_long_factor=tuple(float(v) for v in rs["long_factor"]),
            rope_original_max_position=orig,
            rope_attention_factor=float(rs.get("attention_factor") or 0.0),
        )
    raise NotImplementedError(
        f"rope_scaling type {rtype!r} is not supported (implemented: "
        "linear, dynamic, llama3, longrope, yarn); refusing to load a "
        "checkpoint that would serve silently diverging logits"
    )


def config_from_hf(path: str, name: Optional[str] = None) -> ModelConfig:
    """Build a ModelConfig from an HF checkpoint dir's config.json.

    Covers the registered families: Llama (LlamaForCausalLM), Qwen2
    (Qwen2ForCausalLM: adds QKV bias), Mixtral (MixtralForCausalLM: MoE).
    """
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    archs = hf.get("architectures") or ["LlamaForCausalLM"]
    arch = archs[0]
    if hf.get("model_type") == "brumby" or arch.startswith("Brumby"):
        # Its config.json carries Qwen3's keys and none for the power
        # retention; read as Qwen3 it would load and serve softmax
        # attention over weights trained for something else.
        raise ValueError(
            "model_type 'brumby' (power retention, models/brumby.py): no "
            "checkpoint loader: the checkpoint's tensor names are not "
            "confirmed; the presets brumby-14b / brumby-tiny serve random "
            "weights only"
        )
    if arch in (
        "Qwen2VLForConditionalGeneration",
        "Qwen2_5_VLForConditionalGeneration",
    ):
        # Qwen2-VL / Qwen2.5-VL: the text tower is a plain Qwen2 stack
        # (the `visual.*` tensors load separately via
        # load_vision_checkpoint); newer HF configs nest the text fields
        # under text_config. mrope_section feeds the full M-RoPE path
        # (ops/rope.apply_mrope + engine position streams).
        hf = {**hf, **(hf.get("text_config") or {})}
        arch = "Qwen2ForCausalLM"
        rs = hf.get("rope_scaling") or {}
        if rs.get("mrope_section"):
            hf["_mrope_section"] = tuple(int(v) for v in rs["mrope_section"])
    num_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads
    common = dict(
        name=name or hf.get("model_type", "hf-model"),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_position_embeddings=hf.get("max_position_embeddings", 8192),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        sliding_window=_hf_sliding_window(hf),
        mrope_section=tuple(hf.get("_mrope_section") or ()),
    )
    common.update(_hf_rope_scaling(hf))
    if arch == "GemmaForCausalLM":
        # Gemma: Llama tensor layout + GELU-tanh gated MLP, sqrt(E)
        # embedding scale, zero-centered RMSNorm weights (the loader
        # adds 1 below so ops/norms.rms_norm stays uniform). Real Gemma
        # config.json files OMIT tie_word_embeddings (HF's GemmaConfig
        # defaults it True and drops default-valued keys), so the
        # absent-key default flips to True here — False would demand an
        # lm_head tensor no Gemma checkpoint ships.
        common.update(
            mlp_act="gelu_tanh", embed_scale=True,
            norm_zero_centered=True,
            tie_word_embeddings=bool(
                hf.get("tie_word_embeddings", True)
            ),
        )
        arch = "LlamaForCausalLM"
    if arch == "Qwen2ForCausalLM":
        common["attn_bias"] = True
    elif arch == "Qwen3ForCausalLM":
        common["qk_norm"] = True
    elif arch == "Qwen3MoeForCausalLM":
        # Non-uniform sparsity (dense layers interleaved mid-stack) has no
        # stacked-leaf layout here — same scope rule as DeepSeek's
        # moe_layer_freq guard below.
        if int(hf.get("decoder_sparse_step") or 1) != 1 or hf.get(
            "mlp_only_layers"
        ):
            raise NotImplementedError(
                "Qwen3-MoE checkpoints with decoder_sparse_step != 1 or "
                "mlp_only_layers interleave dense layers mid-stack; only "
                "uniformly-sparse stacks are supported"
            )
        common.update(
            qk_norm=True,
            num_experts=hf["num_local_experts"]
            if "num_local_experts" in hf
            else hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            # HF Qwen3MoeSparseMoeBlock honors this key (skips the
            # top-k renorm when false)
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )
    elif arch == "MixtralForCausalLM":
        common.update(
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["intermediate_size"],
        )
    elif arch in ("DeepseekV2ForCausalLM", "DeepseekV3ForCausalLM"):
        # MLA family. first_k_dense_replace (real V2/V3: first layers
        # dense) maps to the split dense-prefix/MoE-suffix stack; a
        # non-unit moe_layer_freq (interleaved dense layers mid-stack)
        # remains out of scope for the two-scan layout.
        if int(hf.get("moe_layer_freq") or 1) != 1:
            raise NotImplementedError(
                "DeepSeek checkpoints with moe_layer_freq != 1 interleave "
                "dense and MoE layers mid-stack; only a dense PREFIX "
                "(first_k_dense_replace) is supported"
            )
        common["first_k_dense_replace"] = int(
            hf.get("first_k_dense_replace") or 0
        )
        common.update(
            kv_lora_rank=hf["kv_lora_rank"],
            q_lora_rank=int(hf.get("q_lora_rank") or 0),
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
        )
        if int(hf.get("n_routed_experts") or 0) > 0:
            common.update(
                num_experts=hf["n_routed_experts"],
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                n_shared_experts=int(hf.get("n_shared_experts") or 0),
                # DeepSeek routing semantics (V2: softmax +
                # group_limited_greedy, no renorm, scaling 16; V3:
                # sigmoid + noaux_tc with correction bias, renorm,
                # scaling 2.5) — models/llama._mlp implements them all.
                scoring_func=str(hf.get("scoring_func") or "softmax"),
                topk_method=str(hf.get("topk_method") or "plain"),
                n_group=int(hf.get("n_group") or 0),
                topk_group=int(hf.get("topk_group") or 0),
                norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor") or 1.0
                ),
            )
    elif arch == "Phi3ForCausalLM":
        # Phi-3's fused tensors split on load. longrope-scaled variants
        # (128k) are handled by _hf_rope_scaling above (per-band
        # short/long factor tables + HF attention factor).
        pass
    elif arch not in ("LlamaForCausalLM", "MistralForCausalLM"):
        # Mistral is architecturally Llama (same tensor names, bias-free
        # QKV) + sliding-window attention, which _hf_sliding_window
        # already picked up from the config. Phi-3 is Llama with FUSED
        # qkv_proj / gate_up_proj tensors, split on load by the config's
        # head/intermediate geometry (load_checkpoint).
        raise ValueError(f"unsupported architecture {arch!r}")
    return ModelConfig(**common)


# ------------------------------------------------------------- name mapping

# Leaf spec: (pytree path, transpose). Layer leaves live under "layers" and
# get a layer index from the HF name; expert leaves also get an expert index.


def _hf_leaf(cfg: ModelConfig, hf_name: str):
    """Map one HF tensor name → (leaf_key, layer, expert, transpose) or None.

    leaf_key is a top-level key ("embed", "final_norm", "lm_head") or a
    "layers.<name>" key; transpose flips torch's [out, in] Linear layout to
    our [in, out] einsum layout.
    """
    if hf_name == "model.embed_tokens.weight":
        return ("embed", None, None, False)
    if hf_name == "model.norm.weight":
        return ("final_norm", None, None, False)
    if hf_name == "lm_head.weight":
        if cfg.tie_word_embeddings:
            return None  # tied: unembed reads params["embed"]
        return ("lm_head", None, None, True)
    if not hf_name.startswith("model.layers."):
        return None
    rest = hf_name[len("model.layers."):]
    layer_s, _, tail = rest.partition(".")
    layer = int(layer_s)
    simple = {
        "input_layernorm.weight": ("layers.attn_norm", False),
        "self_attn.q_proj.weight": ("layers.wq", True),
        "self_attn.k_proj.weight": ("layers.wk", True),
        "self_attn.v_proj.weight": ("layers.wv", True),
        "self_attn.q_proj.bias": ("layers.bq", False),
        "self_attn.k_proj.bias": ("layers.bk", False),
        "self_attn.v_proj.bias": ("layers.bv", False),
        "self_attn.o_proj.weight": ("layers.wo", True),
        # Qwen3 QK-norm (per-head RMSNorm weights over head_dim).
        "self_attn.q_norm.weight": ("layers.q_head_norm", False),
        "self_attn.k_norm.weight": ("layers.k_head_norm", False),
        "post_attention_layernorm.weight": ("layers.mlp_norm", False),
        "mlp.gate_proj.weight": ("layers.w_gate", True),
        "mlp.up_proj.weight": ("layers.w_up", True),
        "mlp.down_proj.weight": ("layers.w_down", True),
        "block_sparse_moe.gate.weight": ("layers.router", True),
        "mlp.gate.weight": ("layers.router", True),
        "mlp.gate.e_score_correction_bias": ("layers.router_bias", False),
    }
    if cfg.is_mla:
        # DeepSeek-V2/V3 MLA projections. q_proj is the direct-q (V2-Lite)
        # form and maps to w_q; kv_b_proj carries the per-head k_nope AND v
        # up-projections interleaved per head — staged whole under a pseudo
        # leaf and split into w_uk/w_uv after all shards land.
        simple.update(
            {
                "self_attn.q_proj.weight": ("layers.w_q", True),
                "self_attn.q_a_proj.weight": ("layers.w_dq", True),
                "self_attn.q_a_layernorm.weight": ("layers.q_norm", False),
                "self_attn.q_b_proj.weight": ("layers.w_uq", True),
                "self_attn.kv_a_proj_with_mqa.weight": ("layers.w_dkv", True),
                "self_attn.kv_a_layernorm.weight": ("layers.kv_norm", False),
                "self_attn.kv_b_proj.weight": ("layers._w_ukv", True),
                "mlp.shared_experts.gate_proj.weight": ("layers.w_sh_gate", True),
                "mlp.shared_experts.up_proj.weight": ("layers.w_sh_up", True),
                "mlp.shared_experts.down_proj.weight": ("layers.w_sh_down", True),
            }
        )
    if tail in simple:
        key, transpose = simple[tail]
        key, layer = _route_stack(cfg, key, layer)
        return (key, layer, None, transpose)
    for prefix in ("block_sparse_moe.experts.", "mlp.experts."):
        if tail.startswith(prefix):
            sub = tail[len(prefix):]
            expert_s, _, w = sub.partition(".")
            expert = int(expert_s)
            moe = {
                "w1.weight": "layers.w_gate",  # gate_proj (mixtral names)
                "w3.weight": "layers.w_up",  # up_proj
                "w2.weight": "layers.w_down",  # down_proj
                "gate_proj.weight": "layers.w_gate",  # deepseek names
                "up_proj.weight": "layers.w_up",
                "down_proj.weight": "layers.w_down",
            }
            if w in moe:
                key, layer = _route_stack(cfg, moe[w], layer)
                return (key, layer, expert, True)
    return None


def _route_stack(cfg: ModelConfig, key: str, layer: int) -> Tuple[str, int]:
    """Heterogeneous DeepSeek stacks: HF layer i < first_k_dense_replace
    lands in the `dense_layers` prefix stack (same leaf names, dense MLP
    dims); later layers land in `layers` re-indexed from 0."""
    kd = cfg.first_k_dense_replace
    if kd == 0 or not key.startswith("layers."):
        return key, layer
    if layer < kd:
        return "dense_layers." + key[len("layers."):], layer
    return key, layer - kd


def _stack_shapes(
    cfg: ModelConfig, pre: str, L: int, moe: bool
) -> Dict[str, Tuple[int, ...]]:
    """Shapes of one stacked-layer leaf set (`pre` is "layers." or
    "dense_layers."), mirroring the family module's _layer_stack/init."""
    E = cfg.hidden_size
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes: Dict[str, Tuple[int, ...]] = {
        pre + "attn_norm": (L, E),
        pre + "mlp_norm": (L, E),
    }
    if cfg.is_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        kvr, qr = cfg.kv_lora_rank, cfg.q_lora_rank
        shapes.update(
            {
                pre + "w_dkv": (L, E, kvr + dr),
                pre + "kv_norm": (L, kvr),
                pre + "_w_ukv": (L, kvr, Hq * (dn + dv)),
                pre + "wo": (L, Hq * dv, E),
            }
        )
        if qr > 0:
            shapes.update(
                {
                    pre + "w_dq": (L, E, qr),
                    pre + "q_norm": (L, qr),
                    pre + "w_uq": (L, qr, Hq * (dn + dr)),
                }
            )
        else:
            shapes[pre + "w_q"] = (L, E, Hq * (dn + dr))
    else:
        shapes.update(
            {
                pre + "wq": (L, E, Hq * D),
                pre + "wk": (L, E, Hkv * D),
                pre + "wv": (L, E, Hkv * D),
                pre + "wo": (L, Hq * D, E),
            }
        )
        if cfg.attn_bias:
            shapes.update(
                {
                    pre + "bq": (L, Hq * D),
                    pre + "bk": (L, Hkv * D),
                    pre + "bv": (L, Hkv * D),
                }
            )
        if cfg.qk_norm:
            shapes.update(
                {
                    pre + "q_head_norm": (L, D),
                    pre + "k_head_norm": (L, D),
                }
            )
    if moe:
        X, Fm = cfg.num_experts, cfg.moe_intermediate_size
        shapes.update(
            {
                pre + "router": (L, E, X),
                pre + "w_gate": (L, X, E, Fm),
                pre + "w_up": (L, X, E, Fm),
                pre + "w_down": (L, X, Fm, E),
            }
        )
        if cfg.topk_method == "noaux_tc":
            shapes[pre + "router_bias"] = (L, X)
        if cfg.n_shared_experts > 0:
            Fs = cfg.n_shared_experts * Fm
            shapes.update(
                {
                    pre + "w_sh_gate": (L, E, Fs),
                    pre + "w_sh_up": (L, E, Fs),
                    pre + "w_sh_down": (L, Fs, E),
                }
            )
    else:
        F = cfg.intermediate_size
        shapes.update(
            {
                pre + "w_gate": (L, E, F),
                pre + "w_up": (L, E, F),
                pre + "w_down": (L, F, E),
            }
        )
    return shapes


def _leaf_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """Target (host staging) shape per leaf key — mirrors the family
    module's init_params. For MLA, the kv_b up-projection stages under the
    pseudo leaf `layers._w_ukv` (HF interleaves k_nope and v per head in
    one tensor); load_checkpoint splits it into w_uk/w_uv afterwards.
    Heterogeneous DeepSeek stacks add a `dense_layers.` prefix set."""
    E = cfg.hidden_size
    kd = cfg.first_k_dense_replace
    shapes: Dict[str, Tuple[int, ...]] = {
        "embed": (cfg.vocab_size, E),
        "final_norm": (E,),
    }
    shapes.update(
        _stack_shapes(cfg, "layers.", cfg.num_layers - kd, cfg.is_moe)
    )
    if kd > 0:
        shapes.update(_stack_shapes(cfg, "dense_layers.", kd, False))
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (E, cfg.vocab_size)
    return shapes


_NORM_SUFFIXES = (
    "final_norm",
    "attn_norm",
    "mlp_norm",
    "kv_norm",
    "q_norm",
    "router_bias",  # V3 selection bias: f32 like HF's buffer
)


def _is_norm_leaf(key: str) -> bool:
    return key.rsplit(".", 1)[-1] in _NORM_SUFFIXES


def load_checkpoint(
    path: str,
    cfg: ModelConfig,
    dtype=jnp.bfloat16,
    shardings: Optional[Dict[str, Any]] = None,
) -> Params:
    """Load an HF safetensors checkpoint dir into the stacked param pytree.

    Norm weights stage as float32 (matching init_params — rms_norm computes
    in f32); everything else as `dtype`. When `shardings` (the pytree from
    parallel/sharding.param_shardings) is given, each finished leaf is
    device_put with its NamedSharding so devices receive only their shard.
    """
    if not os.path.isdir(path):
        raise FileNotFoundError(f"checkpoint dir {path!r} does not exist")
    np_dtype = ml_dtypes.bfloat16 if dtype == jnp.bfloat16 else np.dtype(dtype)
    shapes = _leaf_shapes(cfg)
    expert_leaves = (
        {"layers.w_gate", "layers.w_up", "layers.w_down"} if cfg.is_moe else set()
    )
    staging: Dict[str, np.ndarray] = {}
    # Completeness tracking: [stack_len] per layer leaf (leading dim of the
    # leaf's shape — the stacks differ in length for heterogeneous models),
    # [stack_len, X] per expert leaf (every expert must land — a missing
    # expert must raise, not serve np.empty garbage), [1] per top-level.
    filled: Dict[str, np.ndarray] = {}
    for k, s in shapes.items():
        if k in expert_leaves:
            filled[k] = np.zeros((s[0], cfg.num_experts), bool)
        elif "." in k:
            filled[k] = np.zeros(s[0], bool)
        else:
            filled[k] = np.zeros(1, bool)

    def stage(key: str) -> np.ndarray:
        if key not in staging:
            want = np.float32 if _is_norm_leaf(key) else np_dtype
            staging[key] = np.empty(shapes[key], dtype=want)
        return staging[key]

    for file in _shard_files(path):
        for name, arr in read_safetensors(file):
            # Phi-3 fuses QKV and gate/up into single tensors; split by
            # the config's head/intermediate geometry (row order q,k,v /
            # gate,up — HF Phi3Attention/Phi3MLP slicing).
            mfused = re.match(
                r"model\.layers\.(\d+)\.self_attn\.qkv_proj\.weight$", name
            )
            if mfused:
                li = int(mfused.group(1))
                qd = cfg.num_heads * cfg.head_dim
                kd = cfg.num_kv_heads * cfg.head_dim
                if arr.shape[0] != qd + 2 * kd:
                    raise ValueError(
                        f"{name}: fused qkv has {arr.shape[0]} rows, "
                        f"config geometry needs {qd + 2 * kd}"
                    )
                for key, chunk in (
                    ("layers.wq", arr[:qd]),
                    ("layers.wk", arr[qd:qd + kd]),
                    ("layers.wv", arr[qd + kd:qd + 2 * kd]),
                ):
                    np.copyto(stage(key)[li], chunk.T, casting="unsafe")
                    filled[key][li] = True
                continue
            mfused = re.match(
                r"model\.layers\.(\d+)\.mlp\.gate_up_proj\.weight$", name
            )
            if mfused:
                li = int(mfused.group(1))
                F = cfg.intermediate_size
                if arr.shape[0] != 2 * F:
                    raise ValueError(
                        f"{name}: fused gate_up has {arr.shape[0]} rows, "
                        f"config geometry needs {2 * F}"
                    )
                for key, chunk in (
                    ("layers.w_gate", arr[:F]),
                    ("layers.w_up", arr[F:2 * F]),
                ):
                    np.copyto(stage(key)[li], chunk.T, casting="unsafe")
                    filled[key][li] = True
                continue
            spec = _hf_leaf(cfg, name)
            if spec is None:
                continue
            key, layer, expert, transpose = spec
            if key not in shapes:
                raise ValueError(
                    f"{name} maps to {key!r} which this config lacks "
                    f"(attn_bias={cfg.attn_bias}, is_moe={cfg.is_moe})"
                )
            buf = stage(key)
            src = arr.T if transpose else arr
            if layer is None:
                np.copyto(buf, src, casting="unsafe")
                filled[key][0] = True
            elif expert is None:
                np.copyto(buf[layer], src, casting="unsafe")
                filled[key][layer] = True
            else:
                np.copyto(buf[layer, expert], src, casting="unsafe")
                filled[key][layer, expert] = True

    missing = [k for k, f in filled.items() if not f.all()]
    if missing:
        raise ValueError(f"checkpoint {path} is missing tensors for {missing}")

    if cfg.is_mla:
        # Split HF's interleaved kv_b up-projection into the absorbed-form
        # tensors the model consumes: [n, kvr, Hq*(dn+dv)] ->
        # w_uk [n, Hq, kvr, dn] + w_uv [n, Hq, kvr, dv] — per stack.
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        for pre in ("layers.", "dense_layers."):
            if pre + "_w_ukv" not in staging:
                continue
            raw = staging.pop(pre + "_w_ukv")
            raw = raw.reshape(
                raw.shape[0], cfg.kv_lora_rank, cfg.num_heads, dn + dv
            )
            staging[pre + "w_uk"] = np.ascontiguousarray(
                np.transpose(raw[..., :dn], (0, 2, 1, 3))
            )
            staging[pre + "w_uv"] = np.ascontiguousarray(
                np.transpose(raw[..., dn:], (0, 2, 1, 3))
            )

    if cfg.norm_zero_centered:
        # Gemma convention: checkpoint stores w, computation uses (1+w).
        for key, buf in staging.items():
            if _is_norm_leaf(key):
                buf += 1.0
    params: Params = {"layers": {}}
    if cfg.first_k_dense_replace > 0:
        params["dense_layers"] = {}
    for key, buf in staging.items():
        leaf = jnp.asarray(buf)
        stack, _, sub = key.partition(".")
        if shardings is not None:
            sh = shardings[stack][sub] if sub else shardings[key]
            leaf = jax.device_put(leaf, sh)
        if sub:
            params[stack][sub] = leaf
        else:
            params[key] = leaf
    return params


# ------------------------------------------------------------ vision towers


def vision_config_from_hf(path: str, out_dim: int = 0):
    """VisionConfig from an HF checkpoint dir carrying a SigLIP-layout
    vision tower (config.json `vision_config`, or a bare vision-model
    config). `out_dim` overrides the projector target (defaults to the
    tower hidden size when the checkpoint has no projector). CLIP-style
    class-token towers are rejected at load (see load_vision_checkpoint)."""
    from xllm_service_tpu.models.vision import VisionConfig

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    vc = hf.get("vision_config", hf)
    if (
        vc.get("model_type") == "qwen2_5_vl"
        or "fullatt_block_indexes" in vc
    ):
        return _qwen25vl_vision_config(hf, vc, out_dim)
    if vc.get("model_type") == "qwen2_vl" or "embed_dim" in vc:
        return _qwen2vl_vision_config(hf, vc, out_dim)
    image_size = int(vc["image_size"])
    patch = int(vc["patch_size"])
    if image_size % patch:
        raise ValueError(
            f"image_size {image_size} not divisible by patch_size {patch} "
            f"(conv-with-remainder towers are not supported)"
        )
    n_patches = (image_size // patch) ** 2
    return VisionConfig(
        name=hf.get("model_type", "siglip") + "-vision",
        image_size=image_size,
        patch_size=patch,
        hidden_size=int(vc["hidden_size"]),
        intermediate_size=int(vc["intermediate_size"]),
        num_layers=int(vc["num_hidden_layers"]),
        num_heads=int(vc["num_attention_heads"]),
        out_tokens=n_patches,  # no pooling: LLaVA-style full patch grid
        out_dim=out_dim or int(vc["hidden_size"]),
        rms_norm_eps=float(vc.get("layer_norm_eps", 1e-6)),
        arch="siglip",
    )


def _qwen2vl_vision_config(hf: dict, vc: dict, out_dim: int = 0):
    """VisionConfig for an HF Qwen2VLVisionConfig dict (embed_dim is the
    tower width; vision_config.hidden_size is the LLM dim the PatchMerger
    projects into). The HF processor's dynamic resolution maps to
    per-request grids; this serving path fixes a square input size
    (image_size keyword in vision_config, else 448 — 32x32 patches)."""
    from xllm_service_tpu.models.vision import VisionConfig

    E = int(vc["embed_dim"])
    merge = int(vc.get("spatial_merge_size", 2))
    image_size = int(vc.get("image_size", 448))
    patch = int(vc["patch_size"])
    if image_size % patch:
        raise ValueError(
            f"image_size {image_size} not divisible by patch_size {patch}"
        )
    grid = image_size // patch
    if grid % merge:
        raise ValueError(
            f"image_size {image_size} / patch {patch} not divisible by "
            f"spatial_merge_size {merge}"
        )
    return VisionConfig(
        name="qwen2_vl-visual",
        image_size=image_size,
        patch_size=patch,
        hidden_size=E,
        intermediate_size=int(E * float(vc.get("mlp_ratio", 4))),
        num_layers=int(vc["depth"]),
        num_heads=int(vc["num_heads"]),
        out_tokens=grid * grid // (merge * merge),
        out_dim=out_dim or int(vc.get("hidden_size") or E),
        rms_norm_eps=1e-6,  # HF hardcodes LayerNorm(eps=1e-6)
        arch="qwen2vl",
        spatial_merge_size=merge,
        temporal_patch_size=int(vc.get("temporal_patch_size", 2)),
    )


def _qwen25vl_vision_config(hf: dict, vc: dict, out_dim: int = 0):
    """VisionConfig for an HF Qwen2_5_VLVisionConfig dict (hidden_size is
    the TOWER width here, out_hidden_size the LLM dim — the names moved
    between the two generations)."""
    from xllm_service_tpu.models.vision import VisionConfig

    E = int(vc["hidden_size"])
    merge = int(vc.get("spatial_merge_size", 2))
    image_size = int(vc.get("image_size", 448))
    patch = int(vc["patch_size"])
    if image_size % patch:
        raise ValueError(
            f"image_size {image_size} not divisible by patch_size {patch}"
        )
    grid = image_size // patch
    if grid % merge:
        raise ValueError(
            f"image_size {image_size} / patch {patch} not divisible by "
            f"spatial_merge_size {merge}"
        )
    return VisionConfig(
        name="qwen2_5_vl-visual",
        image_size=image_size,
        patch_size=patch,
        hidden_size=E,
        intermediate_size=int(vc["intermediate_size"]),
        num_layers=int(vc["depth"]),
        num_heads=int(vc["num_heads"]),
        out_tokens=grid * grid // (merge * merge),
        out_dim=out_dim or int(vc.get("out_hidden_size") or E),
        rms_norm_eps=1e-6,
        arch="qwen25vl",
        spatial_merge_size=merge,
        temporal_patch_size=int(vc.get("temporal_patch_size", 2)),
        window_size=int(vc.get("window_size", 112)),
        fullatt_block_indexes=tuple(
            int(i) for i in (vc.get("fullatt_block_indexes") or ())
        ),
    )


# HF Qwen2_5_VisionTransformer layer tensor name -> (leaf key, transpose).
_QWEN25VL_LAYER = {
    "norm1.weight": ("ln1_w", False),
    "attn.qkv.weight": ("wqkv", True),
    "attn.qkv.bias": ("bqkv", False),
    "attn.proj.weight": ("wo", True),
    "attn.proj.bias": ("bo", False),
    "norm2.weight": ("ln2_w", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.gate_proj.bias": ("b_gate", False),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.up_proj.bias": ("b_up", False),
    "mlp.down_proj.weight": ("w_down", True),
    "mlp.down_proj.bias": ("b_down", False),
}
_QWEN25VL_SIMPLE = {
    "visual.merger.ln_q.weight": ("merger_ln_w", False, np.float32),
    "visual.merger.mlp.0.weight": ("merger_fc1", True, None),
    "visual.merger.mlp.0.bias": ("merger_b1", False, None),
    "visual.merger.mlp.2.weight": ("merger_fc2", True, None),
    "visual.merger.mlp.2.bias": ("merger_b2", False, None),
}


# HF Qwen2VisionTransformer layer tensor name -> (leaf key, transpose).
_QWEN2VL_LAYER = {
    "norm1.weight": ("ln1_w", False),
    "norm1.bias": ("ln1_b", False),
    "attn.qkv.weight": ("wqkv", True),
    "attn.qkv.bias": ("bqkv", False),
    "attn.proj.weight": ("wo", True),
    "attn.proj.bias": ("bo", False),
    "norm2.weight": ("ln2_w", False),
    "norm2.bias": ("ln2_b", False),
    "mlp.fc1.weight": ("fc1", True),
    "mlp.fc1.bias": ("b1", False),
    "mlp.fc2.weight": ("fc2", True),
    "mlp.fc2.bias": ("b2", False),
}
_QWEN2VL_SIMPLE = {
    "visual.merger.ln_q.weight": ("merger_ln_w", False, np.float32),
    "visual.merger.ln_q.bias": ("merger_ln_b", False, np.float32),
    "visual.merger.mlp.0.weight": ("merger_fc1", True, None),
    "visual.merger.mlp.0.bias": ("merger_b1", False, None),
    "visual.merger.mlp.2.weight": ("merger_fc2", True, None),
    "visual.merger.mlp.2.bias": ("merger_b2", False, None),
}


def _load_qwen2vl_visual(path: str, cfg, dtype, np_dtype):
    """Qwen2-VL `visual.*` tower -> the models/vision.py qwen2vl pytree.
    Conv3d patch embed [E, C, T, P, P] flattens to the [(C, T, Ph, Pw), E]
    matmul layout (_qwen2vl_patch_rows builds rows in exactly that
    order)."""
    from xllm_service_tpu.models.vision import init_vision_params

    E, L, P = cfg.hidden_size, cfg.num_layers, cfg.patch_size
    T = cfg.temporal_patch_size
    layer_map = (
        _QWEN25VL_LAYER if cfg.arch == "qwen25vl" else _QWEN2VL_LAYER
    )
    simple_map = (
        _QWEN25VL_SIMPLE if cfg.arch == "qwen25vl" else _QWEN2VL_SIMPLE
    )
    # Stage over EMPTY buffers shaped by init (no random generation —
    # unlike the SigLIP path, every tensor must land or this raises, so
    # values are always overwritten; a 675M-param tower shouldn't pay a
    # full random init to be discarded).
    params = jax.tree.map(
        lambda x: np.empty(x.shape, x.dtype),
        jax.eval_shape(
            lambda: init_vision_params(cfg, jax.random.key(0), dtype)
        ),
    )
    needed = {"patch_embed"} | {k for k, _, _ in simple_map.values()}
    needed |= {f"layers.{k}" for k, _ in layer_map.values()}
    landed = set()
    layer_seen = {
        f"layers.{k}": np.zeros(L, bool) for k, _ in layer_map.values()
    }
    for file in _shard_files(path):
        for name, arr in read_safetensors(file):
            if not name.startswith("visual."):
                continue
            if name == "visual.patch_embed.proj.weight":
                w = np.asarray(arr).reshape(E, 3 * T * P * P).T
                params["patch_embed"] = w.astype(np_dtype)
                landed.add("patch_embed")
            elif name in simple_map:
                key, transpose, want = simple_map[name]
                src = np.asarray(arr).T if transpose else np.asarray(arr)
                params[key] = src.astype(want or np_dtype)
                landed.add(key)
            elif name.startswith("visual.blocks."):
                rest = name[len("visual.blocks."):]
                layer_s, _, tail = rest.partition(".")
                if tail in layer_map:
                    key, transpose = layer_map[tail]
                    src = arr.T if transpose else arr
                    buf = params["layers"][key]
                    np.copyto(buf[int(layer_s)], src, casting="unsafe")
                    layer_seen[f"layers.{key}"][int(layer_s)] = True
    for k, seen in layer_seen.items():
        if seen.all():
            landed.add(k)
    missing = sorted(needed - landed)
    if missing:
        raise ValueError(
            f"qwen2vl visual checkpoint {path} missing tensors: {missing}"
        )
    return cfg, jax.tree.map(jnp.asarray, params)


# Per-layer tensor map for the Qwen2-Audio (Whisper-layout) tower:
# HF tail -> (stacked leaf, transpose). k_proj is bias-free (Whisper).
_AUDIO_LAYER = {
    "self_attn_layer_norm.weight": ("ln1_w", False),
    "self_attn_layer_norm.bias": ("ln1_b", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.out_proj.bias": ("bo", False),
    "final_layer_norm.weight": ("ln2_w", False),
    "final_layer_norm.bias": ("ln2_b", False),
    "fc1.weight": ("fc1", True),
    "fc1.bias": ("b1", False),
    "fc2.weight": ("fc2", True),
    "fc2.bias": ("b2", False),
}

_AUDIO_SIMPLE = {
    # HF name -> (leaf, transpose_spec). Conv kernels [D, C, 3] map to
    # the unfolded-einsum layout [3, C, D].
    "audio_tower.conv1.weight": ("conv1_w", (2, 1, 0)),
    "audio_tower.conv1.bias": ("conv1_b", None),
    "audio_tower.conv2.weight": ("conv2_w", (2, 1, 0)),
    "audio_tower.conv2.bias": ("conv2_b", None),
    "audio_tower.embed_positions.weight": ("pos_embed", None),
    "audio_tower.layer_norm.weight": ("ln_post_w", None),
    "audio_tower.layer_norm.bias": ("ln_post_b", None),
    "multi_modal_projector.linear.weight": ("proj", (1, 0)),
    "multi_modal_projector.linear.bias": ("proj_b", None),
}


def audio_config_from_hf(path: str, out_dim: int = 0):
    """AudioConfig from an HF Qwen2AudioForConditionalGeneration (or
    bare encoder) checkpoint dir: config.json `audio_config` carries the
    Whisper geometry; the projector target comes from text_config (or
    `out_dim`)."""
    from xllm_service_tpu.models.audio import AudioConfig

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    ac = hf.get("audio_config", hf)
    text = hf.get("text_config") or {}
    return AudioConfig(
        name=hf.get("model_type", "qwen2_audio") + "-audio",
        num_mel_bins=int(ac["num_mel_bins"]),
        mel_frames=2 * int(ac["max_source_positions"]),
        hidden_size=int(ac["d_model"]),
        intermediate_size=int(ac["encoder_ffn_dim"]),
        num_layers=int(ac["encoder_layers"]),
        num_heads=int(ac["encoder_attention_heads"]),
        out_dim=int(
            out_dim or text.get("hidden_size")
            or hf.get("hidden_size") or ac["d_model"]
        ),
    )


def load_audio_checkpoint(path: str, cfg=None, dtype=jnp.float32):
    """Load the Qwen2-Audio tower + projector (`audio_tower.*`,
    `multi_modal_projector.linear.*` — HF modeling_qwen2_audio layout)
    into the models/audio.py pytree. Returns (AudioConfig, params);
    missing tensors raise (no silent random-init serving)."""
    from xllm_service_tpu.models.audio import init_audio_params

    cfg = cfg or audio_config_from_hf(path)
    np_dtype = (
        ml_dtypes.bfloat16 if dtype == jnp.bfloat16 else np.dtype(dtype)
    )
    L = cfg.num_layers
    params = jax.tree.map(
        lambda x: np.zeros(x.shape, np_dtype),
        jax.eval_shape(
            lambda: init_audio_params(cfg, jax.random.key(0), dtype)
        ),
    )
    needed = {k for k, _ in _AUDIO_SIMPLE.values()}
    needed |= {f"layers.{k}" for k, _ in _AUDIO_LAYER.values()}
    landed = set()
    layer_seen = {
        f"layers.{k}": np.zeros(L, bool) for k, _ in _AUDIO_LAYER.values()
    }
    for file in _shard_files(path):
        for name, arr in read_safetensors(file):
            if name in _AUDIO_SIMPLE:
                key, perm = _AUDIO_SIMPLE[name]
                src = np.asarray(arr)
                if perm is not None:
                    src = src.transpose(perm)
                params[key] = np.ascontiguousarray(src).astype(np_dtype)
                landed.add(key)
            elif name.startswith("audio_tower.layers."):
                rest = name[len("audio_tower.layers."):]
                layer_s, _, tail = rest.partition(".")
                if tail in _AUDIO_LAYER:
                    key, transpose = _AUDIO_LAYER[tail]
                    src = arr.T if transpose else arr
                    np.copyto(
                        params["layers"][key][int(layer_s)], src,
                        casting="unsafe",
                    )
                    layer_seen[f"layers.{key}"][int(layer_s)] = True
    for k, seen in layer_seen.items():
        if seen.all():
            landed.add(k)
    missing = sorted(needed - landed)
    if missing:
        raise ValueError(
            f"qwen2-audio checkpoint {path} missing tensors: {missing}"
        )
    return cfg, jax.tree.map(jnp.asarray, params)


def save_qwen2audio_tower(params, cfg, path: str) -> None:
    """Inverse of load_audio_checkpoint (HF Qwen2-Audio layout) — CI
    round-trips and synthetic-tower export."""
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(
            {
                "model_type": "qwen2_audio",
                "audio_config": {
                    "model_type": "qwen2_audio_encoder",
                    "num_mel_bins": cfg.num_mel_bins,
                    "d_model": cfg.hidden_size,
                    "encoder_layers": cfg.num_layers,
                    "encoder_attention_heads": cfg.num_heads,
                    "encoder_ffn_dim": cfg.intermediate_size,
                    "max_source_positions": cfg.conv_frames,
                },
                "text_config": {"hidden_size": cfg.out_dim},
            },
            f,
        )

    def host(x) -> np.ndarray:
        a = np.asarray(x)
        return (
            a.astype(ml_dtypes.bfloat16)
            if a.dtype == ml_dtypes.bfloat16 else a
        )

    tensors: Dict[str, np.ndarray] = {}
    for name, (key, perm) in _AUDIO_SIMPLE.items():
        src = host(params[key])
        if perm is not None:
            inv = np.argsort(perm)
            src = np.ascontiguousarray(src.transpose(tuple(inv)))
        tensors[name] = src
    lp = params["layers"]
    for i in range(cfg.num_layers):
        for tail, (key, transpose) in _AUDIO_LAYER.items():
            t = host(lp[key])[i]
            tensors[f"audio_tower.layers.{i}.{tail}"] = (
                np.ascontiguousarray(t.T if transpose else t)
            )
    write_safetensors(os.path.join(path, "model.safetensors"), tensors)


def save_qwen2vl_visual(params, cfg, path: str) -> None:
    """Inverse of the qwen2vl branch of load_vision_checkpoint (HF
    Qwen2-VL `visual.*` layout) — round-trip tested; exports synthetic
    towers for CI."""
    if cfg.arch != "qwen2vl":
        # Fail BEFORE config.json is written: a qwen25vl tower uses
        # different layer maps and would KeyError mid-write, leaving a
        # half-written checkpoint dir (advisor finding, round 4).
        raise ValueError(
            f"save_qwen2vl_visual handles arch 'qwen2vl' only, got "
            f"{cfg.arch!r}"
        )
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(
            {
                "model_type": "qwen2_vl",
                "vision_config": {
                    "model_type": "qwen2_vl",
                    "embed_dim": cfg.hidden_size,
                    "hidden_size": cfg.out_dim,
                    "depth": cfg.num_layers,
                    "num_heads": cfg.num_heads,
                    "patch_size": cfg.patch_size,
                    "image_size": cfg.image_size,
                    "mlp_ratio": cfg.intermediate_size / cfg.hidden_size,
                    "spatial_merge_size": cfg.spatial_merge_size,
                    "temporal_patch_size": cfg.temporal_patch_size,
                },
            },
            f, indent=2,
        )

    E, P, T = cfg.hidden_size, cfg.patch_size, cfg.temporal_patch_size
    lp = params["layers"]
    arrays = {
        "visual.patch_embed.proj.weight": np.asarray(
            params["patch_embed"]
        ).T.reshape(E, 3, T, P, P),
    }
    for name, (key, transpose, _w) in _QWEN2VL_SIMPLE.items():
        a = np.asarray(params[key])
        arrays[name] = a.T if transpose else a
    for i in range(cfg.num_layers):
        for tail, (key, transpose) in _QWEN2VL_LAYER.items():
            a = np.asarray(lp[key][i])
            arrays[f"visual.blocks.{i}.{tail}"] = a.T if transpose else a
    write_safetensors(os.path.join(path, "model.safetensors"), arrays)


# HF SiglipVisionModel tensor name -> (leaf key, transpose). Layer leaves
# carry "layers." and a layer index parsed from the name.
_VISION_SIMPLE = {
    "vision_model.embeddings.position_embedding.weight": ("pos_embed", False),
    "vision_model.post_layernorm.weight": ("final_norm_w", False),
    "vision_model.post_layernorm.bias": ("final_norm_b", False),
}
_VISION_LAYER = {
    "layer_norm1.weight": ("ln1_w", False),
    "layer_norm1.bias": ("ln1_b", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.out_proj.bias": ("bo", False),
    "layer_norm2.weight": ("ln2_w", False),
    "layer_norm2.bias": ("ln2_b", False),
    "mlp.fc1.weight": ("w_up", True),
    "mlp.fc1.bias": ("b_up", False),
    "mlp.fc2.weight": ("w_down", True),
    "mlp.fc2.bias": ("b_down", False),
}


def load_vision_checkpoint(
    path: str, cfg=None, dtype=jnp.bfloat16, out_dim: int = 0
):
    """Load an HF SiglipVisionModel-layout checkpoint dir into the
    models/vision.py `siglip` param pytree. Returns (VisionConfig, params).

    The conv patch embedding [E, 3, P, P] flattens to the patchify
    matmul's [P*P*3, E] layout ((py, px, c) lane order — models/vision.py
    _patchify). A `multi_modal_projector.linear.weight` (or `proj.weight`)
    maps to the LM-dim projector when present; otherwise the projector
    initializes to identity-like random and `out_dim` falls back to the
    tower width (caller projects downstream)."""
    from xllm_service_tpu.models.vision import init_vision_params

    cfg = cfg or vision_config_from_hf(path, out_dim=out_dim)
    np_dtype = ml_dtypes.bfloat16 if dtype == jnp.bfloat16 else np.dtype(dtype)
    if cfg.arch in ("qwen2vl", "qwen25vl"):
        return _load_qwen2vl_visual(path, cfg, dtype, np_dtype)
    E, L, P = cfg.hidden_size, cfg.num_layers, cfg.patch_size

    # Stage over random init so an absent projector keeps a usable leaf;
    # every TOWER leaf must land (tracked below). np.array: a WRITABLE
    # host copy (np.asarray of a jax array is read-only).
    params = jax.tree.map(
        lambda x: np.array(x), init_vision_params(cfg, jax.random.key(0), dtype)
    )
    needed = {"patch_embed", "patch_bias", "pos_embed",
              "final_norm_w", "final_norm_b"}
    needed |= {f"layers.{k}" for k, _ in _VISION_LAYER.values()}
    landed = set()
    layer_seen: Dict[str, np.ndarray] = {
        f"layers.{k}": np.zeros(L, bool) for k, _ in _VISION_LAYER.values()
    }

    for file in _shard_files(path):
        for name, arr in read_safetensors(file):
            # VLM checkpoints prefix the tower (e.g. "vision_tower.");
            # strip anything before "vision_model.".
            if "vision_model." in name:
                name = name[name.index("vision_model."):]
            if name == "vision_model.embeddings.patch_embedding.weight":
                # conv [E, 3, P, P] -> [(py, px, c), E]
                w = np.transpose(arr, (2, 3, 1, 0)).reshape(P * P * 3, E)
                params["patch_embed"] = w.astype(np_dtype)
                landed.add("patch_embed")
            elif name == "vision_model.embeddings.patch_embedding.bias":
                params["patch_bias"] = np.asarray(arr, np_dtype)
                landed.add("patch_bias")
            elif name in _VISION_SIMPLE:
                key, _t = _VISION_SIMPLE[name]
                if key == "pos_embed" and arr.shape[0] != cfg.num_patches:
                    # CLIP-style towers carry a class token (num_patches+1
                    # rows) and a different computation (pre_layrnorm,
                    # quick_gelu) — reject loudly instead of broadcasting
                    # garbage inside the jitted encode.
                    raise ValueError(
                        f"position embedding has {arr.shape[0]} rows, "
                        f"expected {cfg.num_patches}: class-token (CLIP) "
                        f"towers are not supported; use a SigLIP-layout "
                        f"tower"
                    )
                want = (
                    np.float32 if key.startswith(("final_norm",)) else np_dtype
                )
                params[key] = np.asarray(arr, want)
                landed.add(key)
            elif name.startswith("vision_model.encoder.layers."):
                rest = name[len("vision_model.encoder.layers."):]
                layer_s, _, tail = rest.partition(".")
                if tail in _VISION_LAYER:
                    key, transpose = _VISION_LAYER[tail]
                    src = arr.T if transpose else arr
                    buf = params["layers"][key]
                    np.copyto(buf[int(layer_s)], src, casting="unsafe")
                    layer_seen[f"layers.{key}"][int(layer_s)] = True
            elif name in (
                "multi_modal_projector.linear.weight", "proj.weight"
            ):
                params["proj"] = np.asarray(arr.T, np_dtype)
                landed.add("proj")
            elif name in (
                "multi_modal_projector.linear.bias", "proj.bias"
            ):
                params["proj_bias"] = np.asarray(arr, np_dtype)
                landed.add("proj_bias")

    for k, seen in layer_seen.items():
        if seen.all():
            landed.add(k)
    missing = sorted(needed - landed)
    if missing:
        raise ValueError(f"vision checkpoint {path} missing tensors: {missing}")
    if "proj" in landed:
        # The checkpoint's own projector decides the output dim (without
        # one, the random-init projector already staged at cfg.out_dim
        # stands). A weight without a bias keeps bias = 0 at the RIGHT
        # width.
        proj_dim = int(params["proj"].shape[1])
        if proj_dim != cfg.out_dim:
            import dataclasses

            cfg = dataclasses.replace(cfg, out_dim=proj_dim)
        if "proj_bias" not in landed:
            params["proj_bias"] = np.zeros((proj_dim,), np_dtype)
    return cfg, jax.tree.map(jnp.asarray, params)


def save_vision_checkpoint(params, cfg, path: str) -> None:
    """Inverse of load_vision_checkpoint (HF SiglipVisionModel layout) —
    round-trip tested; usable for exporting synthetic towers."""
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(
            {
                "model_type": "siglip_vision_model",
                "vision_config": {
                    "image_size": cfg.image_size,
                    "patch_size": cfg.patch_size,
                    "hidden_size": cfg.hidden_size,
                    "intermediate_size": cfg.intermediate_size,
                    "num_hidden_layers": cfg.num_layers,
                    "num_attention_heads": cfg.num_heads,
                    "layer_norm_eps": cfg.rms_norm_eps,
                },
            },
            f, indent=2,
        )

    def host(x) -> np.ndarray:
        a = np.asarray(x)
        return a.astype(ml_dtypes.bfloat16) if a.dtype == ml_dtypes.bfloat16 else a

    E, P = cfg.hidden_size, cfg.patch_size
    lp = params["layers"]
    tensors: Dict[str, np.ndarray] = {
        "vision_model.embeddings.patch_embedding.weight": np.ascontiguousarray(
            np.transpose(
                host(params["patch_embed"]).reshape(P, P, 3, E), (3, 2, 0, 1)
            )
        ),
        "vision_model.embeddings.patch_embedding.bias": host(params["patch_bias"]),
        "vision_model.embeddings.position_embedding.weight": host(params["pos_embed"]),
        "vision_model.post_layernorm.weight": host(params["final_norm_w"]),
        "vision_model.post_layernorm.bias": host(params["final_norm_b"]),
        "proj.weight": np.ascontiguousarray(host(params["proj"]).T),
        "proj.bias": host(params["proj_bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"vision_model.encoder.layers.{i}."
        for tail, (key, transpose) in _VISION_LAYER.items():
            t = host(lp[key])[i]
            tensors[pre + tail] = np.ascontiguousarray(t.T if transpose else t)
    write_safetensors(os.path.join(path, "model.safetensors"), tensors)


# ---------------------------------------------------------------- HF export


def save_hf_checkpoint(params: Params, cfg: ModelConfig, path: str) -> None:
    """Write params back out as an HF-layout checkpoint dir (config.json +
    model.safetensors) — the inverse of load_checkpoint. Used by the
    round-trip test and for exporting synthetic checkpoints."""
    os.makedirs(path, exist_ok=True)
    if cfg.norm_zero_centered:
        arch = "GemmaForCausalLM"
    elif cfg.is_mla and (
        cfg.topk_method == "noaux_tc" or cfg.scoring_func == "sigmoid"
    ):
        # V3 routing can't run under the V2 gate (transformers'
        # DeepseekV2MoEGate has no noaux_tc/sigmoid branch).
        arch = "DeepseekV3ForCausalLM"
    elif cfg.is_mla:
        arch = "DeepseekV2ForCausalLM"
    elif cfg.is_moe and cfg.qk_norm:
        arch = "Qwen3MoeForCausalLM"
    elif cfg.is_moe:
        arch = "MixtralForCausalLM"
    elif cfg.qk_norm:
        arch = "Qwen3ForCausalLM"
    elif cfg.attn_bias:
        arch = "Qwen2ForCausalLM"
    else:
        arch = "LlamaForCausalLM"
    hf_cfg = {
        "architectures": [arch],
        "model_type": arch[: -len("ForCausalLM")].lower(),
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": (
            cfg.moe_intermediate_size
            if (cfg.is_moe and not cfg.is_mla and not cfg.qk_norm)
            else cfg.intermediate_size
        ),
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "max_position_embeddings": cfg.max_position_embeddings,
        "tie_word_embeddings": cfg.tie_word_embeddings,
    }
    if cfg.is_moe and cfg.qk_norm:
        hf_cfg.update(
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
        )
    if cfg.is_mla:
        hf_cfg.update(
            kv_lora_rank=cfg.kv_lora_rank,
            q_lora_rank=cfg.q_lora_rank or None,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
            first_k_dense_replace=cfg.first_k_dense_replace,
        )
        if cfg.is_moe:
            hf_cfg.update(
                n_routed_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                moe_intermediate_size=cfg.moe_intermediate_size,
                n_shared_experts=cfg.n_shared_experts,
                scoring_func=cfg.scoring_func,
                # transformers' V2 gate knows only greedy /
                # group_limited_greedy; our internal "plain" maps back
                topk_method=(
                    "greedy" if cfg.topk_method == "plain"
                    else cfg.topk_method
                ),
                n_group=cfg.n_group or None,
                topk_group=cfg.topk_group or None,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
            )
    elif cfg.is_moe:
        hf_cfg["num_local_experts"] = cfg.num_experts
        hf_cfg["num_experts_per_tok"] = cfg.num_experts_per_tok
    if cfg.sliding_window:
        hf_cfg["sliding_window"] = cfg.sliding_window
    if cfg.rope_scaling_type:
        # Inverse of _hf_rope_scaling — lets the HF-parity tests load the
        # same rope-scaled geometry through transformers.
        rs: Dict[str, Any] = {"rope_type": cfg.rope_scaling_type}
        if cfg.rope_scaling_type in ("linear", "dynamic", "llama3", "yarn"):
            rs["factor"] = cfg.rope_scaling_factor
        if cfg.rope_scaling_type == "yarn":
            rs["beta_fast"] = cfg.rope_beta_fast
            rs["beta_slow"] = cfg.rope_beta_slow
            rs["truncate"] = cfg.rope_scaling_truncate
            if cfg.rope_mscale:
                rs["mscale"] = cfg.rope_mscale
            if cfg.rope_mscale_all_dim:
                rs["mscale_all_dim"] = cfg.rope_mscale_all_dim
            if cfg.rope_attention_factor:
                rs["attention_factor"] = cfg.rope_attention_factor
            if cfg.rope_original_max_position:
                rs["original_max_position_embeddings"] = (
                    cfg.rope_original_max_position
                )
        if cfg.rope_scaling_type == "llama3":
            rs["low_freq_factor"] = cfg.rope_low_freq_factor
            rs["high_freq_factor"] = cfg.rope_high_freq_factor
            rs["original_max_position_embeddings"] = (
                cfg.rope_original_max_position
            )
        if cfg.rope_scaling_type == "longrope":
            rs["short_factor"] = list(cfg.rope_short_factor)
            rs["long_factor"] = list(cfg.rope_long_factor)
            if cfg.rope_attention_factor:
                rs["attention_factor"] = cfg.rope_attention_factor
            # Phi-3 keeps the original context at the config top level.
            hf_cfg["original_max_position_embeddings"] = (
                cfg.rope_original_max_position
            )
        hf_cfg["rope_scaling"] = rs
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2)

    def host(x) -> np.ndarray:
        a = np.asarray(x)
        return a.astype(ml_dtypes.bfloat16) if a.dtype == ml_dtypes.bfloat16 else a

    def norm_out(x) -> np.ndarray:
        # Gemma checkpoints store zero-centered norm weights (load adds 1)
        return host(x) - 1.0 if cfg.norm_zero_centered else host(x)

    tensors: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": norm_out(params["final_norm"]),
    }
    if not cfg.tie_word_embeddings:
        tensors["lm_head.weight"] = host(params["lm_head"]).T
    kd = cfg.first_k_dense_replace
    for hf_i in range(cfg.num_layers):
        # Heterogeneous stacks: HF layer hf_i < kd reads the dense-prefix
        # stack (dense MLP names); later layers read the main stack.
        if kd and hf_i < kd:
            lp, i, layer_moe = params["dense_layers"], hf_i, False
        else:
            lp, i, layer_moe = params["layers"], hf_i - kd, cfg.is_moe
        pre = f"model.layers.{hf_i}."
        tensors[pre + "input_layernorm.weight"] = norm_out(lp["attn_norm"])[i]
        tensors[pre + "post_attention_layernorm.weight"] = norm_out(lp["mlp_norm"])[i]
        if cfg.is_mla:
            dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
            kvr, Hq = cfg.kv_lora_rank, cfg.num_heads
            tensors[pre + "self_attn.kv_a_proj_with_mqa.weight"] = host(
                lp["w_dkv"]
            )[i].T
            tensors[pre + "self_attn.kv_a_layernorm.weight"] = host(
                lp["kv_norm"]
            )[i]
            # Re-interleave w_uk/w_uv per head into HF's kv_b_proj layout
            # [Hq*(dn+dv), kvr] (the inverse of load_checkpoint's split).
            uk = np.transpose(host(lp["w_uk"])[i], (1, 0, 2))  # [kvr,Hq,dn]
            uv = np.transpose(host(lp["w_uv"])[i], (1, 0, 2))  # [kvr,Hq,dv]
            kv_b = np.concatenate([uk, uv], axis=-1).reshape(
                kvr, Hq * (dn + dv)
            )
            tensors[pre + "self_attn.kv_b_proj.weight"] = kv_b.T
            if cfg.q_lora_rank > 0:
                tensors[pre + "self_attn.q_a_proj.weight"] = host(lp["w_dq"])[i].T
                tensors[pre + "self_attn.q_a_layernorm.weight"] = host(
                    lp["q_norm"]
                )[i]
                tensors[pre + "self_attn.q_b_proj.weight"] = host(lp["w_uq"])[i].T
            else:
                tensors[pre + "self_attn.q_proj.weight"] = host(lp["w_q"])[i].T
            tensors[pre + "self_attn.o_proj.weight"] = host(lp["wo"])[i].T
        else:
            tensors[pre + "self_attn.q_proj.weight"] = host(lp["wq"])[i].T
            tensors[pre + "self_attn.k_proj.weight"] = host(lp["wk"])[i].T
            tensors[pre + "self_attn.v_proj.weight"] = host(lp["wv"])[i].T
            tensors[pre + "self_attn.o_proj.weight"] = host(lp["wo"])[i].T
            if cfg.attn_bias:
                tensors[pre + "self_attn.q_proj.bias"] = host(lp["bq"])[i]
                tensors[pre + "self_attn.k_proj.bias"] = host(lp["bk"])[i]
                tensors[pre + "self_attn.v_proj.bias"] = host(lp["bv"])[i]
            if cfg.qk_norm:
                tensors[pre + "self_attn.q_norm.weight"] = host(
                    lp["q_head_norm"]
                )[i]
                tensors[pre + "self_attn.k_norm.weight"] = host(
                    lp["k_head_norm"]
                )[i]
        if layer_moe:
            gate_name, exp_pre, w_names = (
                ("mlp.gate.weight", "mlp.experts.",
                 ("gate_proj.weight", "up_proj.weight", "down_proj.weight"))
                if cfg.is_mla or cfg.qk_norm  # deepseek + qwen3-moe naming
                else ("block_sparse_moe.gate.weight", "block_sparse_moe.experts.",
                      ("w1.weight", "w3.weight", "w2.weight"))
            )
            tensors[pre + gate_name] = host(lp["router"])[i].T
            if lp.get("router_bias") is not None:
                tensors[pre + "mlp.gate.e_score_correction_bias"] = host(
                    lp["router_bias"]
                )[i]
            for j in range(cfg.num_experts):
                ep = pre + exp_pre + f"{j}."
                tensors[ep + w_names[0]] = host(lp["w_gate"])[i, j].T
                tensors[ep + w_names[1]] = host(lp["w_up"])[i, j].T
                tensors[ep + w_names[2]] = host(lp["w_down"])[i, j].T
            if cfg.n_shared_experts > 0:
                tensors[pre + "mlp.shared_experts.gate_proj.weight"] = host(
                    lp["w_sh_gate"]
                )[i].T
                tensors[pre + "mlp.shared_experts.up_proj.weight"] = host(
                    lp["w_sh_up"]
                )[i].T
                tensors[pre + "mlp.shared_experts.down_proj.weight"] = host(
                    lp["w_sh_down"]
                )[i].T
        else:
            tensors[pre + "mlp.gate_proj.weight"] = host(lp["w_gate"])[i].T
            tensors[pre + "mlp.up_proj.weight"] = host(lp["w_up"])[i].T
            tensors[pre + "mlp.down_proj.weight"] = host(lp["w_down"])[i].T
    write_safetensors(os.path.join(path, "model.safetensors"), tensors)


# ------------------------------------------------------------- LoRA (peft)

# peft target-module names -> stacked-leaf projection names (llama family)
_LORA_PROJ_MAP = {
    "q_proj": "wq",
    "k_proj": "wk",
    "v_proj": "wv",
    "o_proj": "wo",
    "gate_proj": "w_gate",
    "up_proj": "w_up",
    "down_proj": "w_down",
}


def load_lora_checkpoint(path: str, cfg: ModelConfig):
    """Load one peft-layout LoRA adapter dir into the executor's format:
    {proj: (A [L, in, r], B [L, r, out])} with the peft scaling
    (lora_alpha / r) folded into B. Layers the adapter does not cover
    stay zero. Expects `adapter_model.safetensors` with
    `...layers.{i}.(self_attn|mlp).<target>.lora_{A,B}.weight` keys
    (peft stores A as [r, in] and B as [out, r]) and an optional
    `adapter_config.json` carrying r / lora_alpha."""
    import json as _json

    cfg_path = os.path.join(path, "adapter_config.json")
    alpha = r_cfg = None
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            acfg = _json.load(f)
        alpha = acfg.get("lora_alpha")
        r_cfg = acfg.get("r")
    st_path = os.path.join(path, "adapter_model.safetensors")
    if not os.path.exists(st_path):
        raise FileNotFoundError(f"no adapter_model.safetensors in {path}")

    per_proj: Dict[str, Dict[int, Dict[str, np.ndarray]]] = {}
    layer_re = re.compile(r"\.layers\.(\d+)\.")
    for key, arr in read_safetensors(st_path):
        m = layer_re.search(key)
        if m is None:
            continue
        layer = int(m.group(1))
        tail = key[m.end():]  # e.g. self_attn.q_proj.lora_A.weight
        parts = tail.split(".")
        if len(parts) < 3 or parts[-1] != "weight":
            continue
        which = parts[-2]  # lora_A | lora_B
        target = parts[-3]
        proj = _LORA_PROJ_MAP.get(target)
        if proj is None or which not in ("lora_A", "lora_B"):
            continue
        per_proj.setdefault(proj, {}).setdefault(layer, {})[which] = arr
    if not per_proj:
        raise ValueError(f"{st_path}: no recognizable lora_A/lora_B keys")

    out = {}
    for proj, layers in per_proj.items():
        any_layer = next(iter(layers.values()))
        if "lora_A" not in any_layer or "lora_B" not in any_layer:
            raise ValueError(f"{proj}: incomplete lora_A/lora_B pair")
        r = any_layer["lora_A"].shape[0]
        e_in = any_layer["lora_A"].shape[1]
        e_out = any_layer["lora_B"].shape[0]
        scaling = (alpha / (r_cfg or r)) if alpha else 1.0
        A = np.zeros((cfg.num_layers, e_in, r), np.float32)
        B = np.zeros((cfg.num_layers, r, e_out), np.float32)
        for layer, pair in layers.items():
            if layer >= cfg.num_layers:
                raise ValueError(
                    f"{proj}: adapter layer {layer} out of range"
                )
            A[layer] = pair["lora_A"].astype(np.float32).T  # [in, r]
            B[layer] = pair["lora_B"].astype(np.float32).T * scaling
        out[proj] = (A, B)
    return out


def save_lora_checkpoint(adapter, path: str, alpha=None, r=None) -> None:
    """Write {proj: (A [L, in, r], B [L, r, out])} as a peft-layout dir
    (testing/roundtrip; B is UNSCALED here — pass alpha/r to record the
    scaling load_lora_checkpoint will fold in)."""
    import json as _json

    os.makedirs(path, exist_ok=True)
    inv = {v: k for k, v in _LORA_PROJ_MAP.items()}
    tensors: Dict[str, np.ndarray] = {}
    for proj, (A, B) in adapter.items():
        target = inv[proj]
        grp = "self_attn" if proj.startswith("w") and proj[1] in "qkvo" \
            else "mlp"
        for layer in range(A.shape[0]):
            base = (
                f"base_model.model.model.layers.{layer}.{grp}.{target}"
            )
            tensors[f"{base}.lora_A.weight"] = np.ascontiguousarray(
                A[layer].T.astype(np.float32)
            )
            tensors[f"{base}.lora_B.weight"] = np.ascontiguousarray(
                B[layer].T.astype(np.float32)
            )
    write_safetensors(
        os.path.join(path, "adapter_model.safetensors"), tensors
    )
    if alpha is not None:
        with open(os.path.join(path, "adapter_config.json"), "w") as f:
            _json.dump({"lora_alpha": alpha, "r": r}, f)
