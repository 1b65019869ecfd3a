"""Model families (engine tier, SURVEY.md §2.3).

Every module exports the same function surface — init_params / decode_step /
prefill_batch_step / forward_dense — over the shared paged-cache runtime;
`get_module(cfg)` dispatches on the architecture the config describes so the
executor never branches on family internals.
"""

from __future__ import annotations

from xllm_service_tpu.models.configs import ModelConfig


def get_module(cfg: ModelConfig):
    """The model-family module for a config: MLA configs (kv_lora_rank > 0)
    run models/deepseek.py; power-retention configs (retention_degree > 0)
    models/brumby.py; a stack whose layer pattern is data (layer_types:
    Mamba-2 beside GQA layers) models/granite.py; everything else (Llama/Qwen2/Mixtral-style GQA +
    optional MoE) runs models/llama.py."""
    if cfg.is_retention:
        from xllm_service_tpu.models import brumby

        return brumby
    if cfg.is_hybrid:
        from xllm_service_tpu.models import granite

        return granite
    if cfg.is_mla:
        from xllm_service_tpu.models import deepseek

        return deepseek
    from xllm_service_tpu.models import llama

    return llama


def cache_row_dims(cfg: ModelConfig):
    """(head_axis, row_dim) of one paged-cache row (of one state row for
    a retention family) — delegated to the family module, the single
    source of truth for its cache layout."""
    return get_module(cfg).cache_row_dims(cfg)


def num_caches(cfg: ModelConfig) -> int:
    """Cache array count: 2 (K + V) for GQA and for a hybrid stack's
    attention layers; 1 (latent) for MLA; 2 (the state and its
    normaliser) for retention — delegated to the family module."""
    return get_module(cfg).NUM_CACHES
