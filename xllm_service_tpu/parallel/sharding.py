"""Megatron-style tensor-parallel sharding specs for the Llama param pytree.

The per-layer tensors carry a leading stacked-layer axis (models/llama.py),
so specs shift right by one. Contract:

  wq/wk/wv  [L, E, H*D]   → shard output heads over tp
  wo        [L, H*D, E]   → shard contracting dim over tp (psum after)
  dense MLP w_gate/up [L, E, F] → shard F; w_down [L, F, E] → shard F
  MoE       w_gate/up [L, X, E, Fm], w_down [L, X, Fm, E] → experts X over
            ep (when an ep mesh axis is given) and Fm over tp; without an
            ep axis, X rides tp (pure-TP MoE for small expert counts)
  embed     [V, E]        → shard V (all-gather on embed lookup is tiny)
  lm_head   [E, V]        → shard V
  KV caches [L, B, bs, Hkv, D] → shard Hkv over tp

XLA derives the matching collectives (psum for row-parallel contractions)
from these annotations under jit — no hand-written comms.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xllm_service_tpu.models.configs import ModelConfig


def param_shardings(
    cfg: ModelConfig,
    mesh: Mesh,
    tp_axis: str = "tp",
    ep_axis: str | None = None,
) -> Dict[str, Any]:
    """Sharding pytree matching the Llama param pytree.

    `ep_axis` (when set and present in the mesh) shards the MoE expert axis
    over its own mesh axis while `tp_axis` shards each expert's hidden dim —
    true EP×TP. With ep_axis=None, experts ride the tp axis (pure-TP MoE,
    right for small expert counts on one slice)."""

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    tp = tp_axis if tp_axis in mesh.shape else None
    if cfg.is_hybrid:
        return _hybrid_param_shardings(cfg, ns)
    layers: Dict[str, Any] = {
        "attn_norm": ns(None, None),
        "mlp_norm": ns(None, None),
    }
    if cfg.is_mla:
        # MLA (deepseek.py): the shared latent path (w_dq/w_dkv and norms)
        # replicates — it is tiny and feeds every head; the per-head
        # up-projections and wo shard over heads (Megatron column/row).
        layers.update(
            {
                "w_dkv": ns(None, None, None),
                "kv_norm": ns(None, None),
                "w_uk": ns(None, tp, None, None),
                "w_uv": ns(None, tp, None, None),
                "wo": ns(None, tp, None),
            }
        )
        if cfg.q_lora_rank > 0:
            layers.update(
                {
                    "w_dq": ns(None, None, None),
                    "q_norm": ns(None, None),
                    "w_uq": ns(None, None, tp),
                }
            )
        else:
            layers["w_q"] = ns(None, None, tp)
    else:
        layers.update(
            {
                "wq": ns(None, None, tp),
                "wk": ns(None, None, tp),
                "wv": ns(None, None, tp),
                "wo": ns(None, tp, None),
            }
        )
        if cfg.attn_bias:
            layers.update(
                {"bq": ns(None, tp), "bk": ns(None, tp), "bv": ns(None, tp)}
            )
        if cfg.qk_norm:
            # Head-dim norms are tiny and head-agnostic: replicate.
            layers.update(
                {
                    "q_head_norm": ns(None, None),
                    "k_head_norm": ns(None, None),
                }
            )
    if cfg.is_retention:
        # The retention gate is one column a KV head: replicated (the
        # family is served at tp_size 1 only, runtime/executor.py).
        layers.update(
            {"w_ret_gate": ns(None, None, None), "b_ret_gate": ns(None, None)}
        )
    if cfg.is_moe:
        ep = ep_axis if ep_axis is not None and ep_axis in mesh.shape else None
        e, t = (ep, tp) if ep is not None else (tp, None)
        layers.update(
            {
                "router": ns(None, None, None),
                "w_gate": ns(None, e, None, t),
                "w_up": ns(None, e, None, t),
                "w_down": ns(None, e, t, None),
            }
        )
        if cfg.topk_method == "noaux_tc":
            layers["router_bias"] = ns(None, None)  # replicated like router
        if cfg.n_shared_experts > 0:
            # DeepSeek shared experts: dense SwiGLU, ordinary column/row TP.
            layers.update(
                {
                    "w_sh_gate": ns(None, None, tp),
                    "w_sh_up": ns(None, None, tp),
                    "w_sh_down": ns(None, tp, None),
                }
            )
    else:
        layers.update(
            {
                "w_gate": ns(None, None, tp),
                "w_up": ns(None, None, tp),
                "w_down": ns(None, tp, None),
            }
        )
    out: Dict[str, Any] = {
        "embed": ns(tp, None),
        "layers": layers,
        "final_norm": ns(None),
    }
    if cfg.first_k_dense_replace > 0:
        # Heterogeneous DeepSeek stack: the dense prefix carries the same
        # MLA attention specs plus dense-SwiGLU MLP specs (models/deepseek
        # _layer_stack(moe=False)).
        dense = {
            k: v
            for k, v in layers.items()
            if k
            not in (
                "router", "router_bias", "w_gate", "w_up", "w_down",
                "w_sh_gate", "w_sh_up", "w_sh_down",
            )
        }
        dense.update(
            {
                "w_gate": ns(None, None, tp),
                "w_up": ns(None, None, tp),
                "w_down": ns(None, tp, None),
            }
        )
        out["dense_layers"] = dense
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ns(None, tp)
    return out


def _hybrid_param_shardings(cfg: ModelConfig, ns) -> Dict[str, Any]:
    """models/granite.py's tree, every leaf replicated: the hybrid stack
    is served at tp_size 1 only (its state pool is not sharded;
    runtime/executor.py refuses the rest by name)."""

    def rep(names, ndim):
        return {k: ns(*(None,) * ndim) for k in names}

    state_stacks = {
        "mamba": {
            **rep(("conv_b", "dt_bias", "A_log", "D", "gate_norm"), 2),
            **rep(("w_in", "conv_w", "w_out"), 3),
        },
        "kda": {
            **rep(("conv_b", "dt_bias", "A_log", "o_norm"), 2),
            **rep(("wq", "wk", "wv", "wo", "conv_w", "w_f1", "w_f2", "w_beta",
                   "w_g1", "w_g2"), 3),
        },
        "lightning": {
            **rep(("q_norm", "k_norm", "o_norm"), 2),
            **rep(("wq", "wk", "wv", "w_ogate", "wo"), 3),
        },
    }
    tree = {
        "embed": ns(None, None),
        "final_norm": ns(None),
        "layers": {
            **rep(("attn_norm", "mlp_norm"), 2),
            **rep(("router",) if cfg.is_moe else (), 3),
            **rep(("w_sh_gate", "w_sh_up", "w_sh_down") if cfg.n_shared_experts else (), 3),
            **rep(("router_bias",) if cfg.topk_method == "noaux_tc" else (), 2),
            # the held experts' matrices, or a dense MLP in every layer
            **rep(("w_gate", "w_up", "w_down"), 4 if cfg.is_moe else 3),
        },
        "attn": {
            **rep(("wq", "wk", "wv", "wo") + (("w_ogate",) if cfg.attn_gate else ()), 3),
            **rep(("q_norm", "k_norm") if cfg.num_sparse_layers and cfg.qk_norm else (), 2),
        },
    }
    if cfg.state_layer_kind:
        tree[cfg.state_layer_kind] = state_stacks[cfg.state_layer_kind]
    if cfg.num_window_layers:
        tree["attn_w"] = {
            **rep(("wq", "wk", "wv", "wo") + (("w_ogate",) if cfg.attn_gate else ()), 3),
            **rep(("sink",) if cfg.window_sink else (), 2),
        }
    if cfg.first_k_dense_replace:
        tree["dense_layers"] = rep(("w_gate", "w_up", "w_down"), 3)
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = ns(None, None)
    return tree


def kv_cache_sharding(mesh: Mesh) -> NamedSharding:
    # [L, num_blocks, Hkv, bs, D]: KV heads over tp.
    return NamedSharding(mesh, P(None, None, "tp", None, None))


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    # int8 cache scales [L, num_blocks, Hkv, G, bs]: KV heads over tp,
    # same placement as the data rows they scale (G = sub-channel groups,
    # a multiple of 8 so the per-block [G, bs] DMA tile is Mosaic-legal
    # on every tp shard — see ops/kv_cache.py).
    return NamedSharding(mesh, P(None, None, "tp", None, None))


def check_tp_divisibility(cfg: ModelConfig, tp: int, ep: int = 1) -> None:
    if cfg.is_mla:
        # MLA: only query heads shard (the latent cache is shared/replicated).
        if cfg.num_heads % tp:
            raise ValueError(
                f"tp={tp} must divide num_heads={cfg.num_heads}"
            )
    elif cfg.num_kv_heads % tp or cfg.num_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={cfg.num_heads} and "
            f"num_kv_heads={cfg.num_kv_heads}"
        )
    # head_dim<128 packed rows cap the shardable cache-head axis at the
    # packed count; when tp doesn't divide it the executor falls back to
    # the unpacked layout via resolve_kv_packing (ADVICE r3) instead of
    # rejecting the config here.
    if cfg.is_moe:
        _check_moe_divisibility(cfg, tp, ep)
    elif cfg.intermediate_size % tp:
        raise ValueError(
            f"tp={tp} must divide intermediate={cfg.intermediate_size}"
        )


def resolve_kv_packing(cfg: ModelConfig, tp: int) -> ModelConfig:
    """Disable head_dim<128 row packing when tp doesn't divide the packed
    head count (e.g. llama-1B-class: Hkv=8, D=64 packs to 4 rows, so tp=8
    only works unpacked). The unpacked cache keeps the gather attention
    path functional; packing (and kernel eligibility) is purely a layout
    optimization, never a correctness requirement."""
    from xllm_service_tpu.ops.kv_cache import kv_pack_factor

    if cfg.is_mla or cfg.kv_pack_disable:
        return cfg
    pf = kv_pack_factor(cfg.num_kv_heads, cfg.head_dim)
    if pf > 1 and (cfg.num_kv_heads // pf) % tp:
        return dataclasses.replace(cfg, kv_pack_disable=True)
    return cfg


def _check_moe_divisibility(cfg: ModelConfig, tp: int, ep: int) -> None:
    # EP×TP: experts over ep, per-expert hidden over tp; pure-TP MoE
    # (ep=1) shards the expert axis over tp instead.
    if ep > 1:
        if cfg.num_experts % ep:
            raise ValueError(
                f"ep={ep} must divide num_experts={cfg.num_experts}"
            )
        if cfg.moe_intermediate_size % tp:
            raise ValueError(
                f"tp={tp} must divide "
                f"moe_intermediate={cfg.moe_intermediate_size}"
            )
    elif cfg.num_experts % tp:
        raise ValueError(
            f"tp={tp} must divide num_experts={cfg.num_experts}"
        )
    # Heterogeneous stack: the dense prefix shards intermediate_size.
    if cfg.first_k_dense_replace > 0 and cfg.intermediate_size % tp:
        raise ValueError(
            f"tp={tp} must divide dense-prefix intermediate="
            f"{cfg.intermediate_size}"
        )
