"""Bytes and FLOPs a call NEEDS, computed from shapes, and the table of
device peaks. The counts are the Llama family's (benchmarks/families/
llama.py: GQA, SwiGLU, a K and a V cache) and read its configuration
keys; a later family brings its operations and bytes in a file of its own
beside the per-layer metrics that read them, and takes only PEAKS, peaks
and hbm_time_s from here. Nothing here is read from the compiler (XLA's
"bytes accessed" counts what its schedule touches, not what the algorithm
needs) or from the program.

All counts are lower bounds on what the chip reads: KV blocks are padded
to the block size and activations are left out, so a share computed from
them cannot pass 100 % by the count's fault."""

from __future__ import annotations

from typing import Dict, Mapping

# Google Cloud documentation, "TPU v5e" system architecture page: per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. Keyed by
# jax.devices()[0].device_kind. A device that is not here is an error.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; add a row "
            f"to benchmarks/harness/counts.py PEAKS with its source"
        ) from None


def head_dim(m: Mapping) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameter counts of a llama-style decoder from its HF config keys."""
    E, F, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    Hq, Hkv, D, V = (
        m["num_attention_heads"], m["num_key_value_heads"], head_dim(m),
        m["vocab_size"],
    )
    attn = E * Hq * D + 2 * E * Hkv * D + Hq * D * E
    if m.get("attention_bias"):
        attn += Hq * D + 2 * Hkv * D
    mlp = 3 * E * F
    norms = 2 * E
    embed = V * E
    head = 0 if m.get("tie_word_embeddings") else V * E
    return {
        "per_layer": attn + mlp + norms,
        "layers": L * (attn + mlp + norms),
        "embed": embed,
        "lm_head": head,
        "final_norm": E,
        "total": L * (attn + mlp + norms) + embed + head + E,
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes ONE decode step must read on one chip: every layer's
    matrices and the output head once (a tied head is the embedding
    table, read whole for the logits); the embedding LOOKUP reads only
    rows and is left out. Norm weights are float32. With tensor
    parallelism each chip reads its 1/tp of the matrices."""
    b = DTYPE_BYTES[dtype]
    c = param_counts(m)
    E, L = m["hidden_size"], m["num_hidden_layers"]
    norm_params = L * 2 * E + E
    matrices = c["layers"] - L * 2 * E + m["vocab_size"] * E
    return matrices * b // tp + norm_params * 4


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """K and V bytes one cached token occupies on one chip, all layers."""
    return (
        2 * m["num_hidden_layers"] * m["num_key_value_heads"] * head_dim(m)
        * DTYPE_BYTES[dtype] // tp
    )


def decode_step_flops(m: Mapping, rows: int, context_tokens: int) -> int:
    """FLOPs of one decode step over `rows` sequences whose contexts sum
    to `context_tokens`: 2 per weight per row for the matrices and head,
    4 * Hq * D per cached token per layer for QK^T and PV."""
    c = param_counts(m)
    E, L = m["hidden_size"], m["num_hidden_layers"]
    matrices = c["layers"] - L * 2 * E + m["vocab_size"] * E
    attn = 4 * m["num_attention_heads"] * head_dim(m) * L * context_tokens
    return 2 * matrices * rows + attn


def prefill_flops(m: Mapping, tokens: int, context_pairs: int) -> int:
    """FLOPs of prefilling `tokens` prompt tokens (matrices; the head only
    for the last token is left out) plus causal attention over
    `context_pairs` (query, key) pairs."""
    c = param_counts(m)
    E, L = m["hidden_size"], m["num_hidden_layers"]
    matrices = c["layers"] - L * 2 * E
    attn = 4 * m["num_attention_heads"] * head_dim(m) * L * context_pairs
    return 2 * matrices * tokens + attn


def hbm_time_s(nbytes: float, device_kind: str) -> float:
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
