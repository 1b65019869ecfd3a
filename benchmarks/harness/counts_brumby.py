"""Bytes and FLOPs a call of the Brumby family NEEDS, from shapes (the
counterpart of benchmarks/harness/counts.py, which is the Llama family's;
PEAKS and hbm_time_s are taken from there). Every count is a lower bound:
the state has D = d(d+1)/2 true features a KV head whatever the layout
pads to (the program stores (d/2 + 1) d = 8320 for 8256 at d = 128), the
normaliser column is left out of the update kernel's bytes, and a FLOP is
counted once although the kernels split float32 operands into bf16 parts
and run up to three MXU passes."""

from __future__ import annotations

from typing import Mapping

from benchmarks.harness.counts import DTYPE_BYTES


def true_features(m: Mapping) -> int:
    d = m["head_dim"]
    return d * (d + 1) // 2


def state_bytes_per_row(m: Mapping, state_dtype: str = "float32") -> int:
    """Bytes of ONE sequence's state S over all layers (one direction:
    a decode step reads them and writes them)."""
    return (
        m["num_hidden_layers"] * m["num_key_value_heads"] * true_features(m)
        * m["head_dim"] * DTYPE_BYTES[state_dtype]
    )


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16") -> int:
    """Weight bytes one step must read: every layer's matrices (q, k, v,
    o, the retention gate, SwiGLU) and the output head once; the
    embedding lookup reads rows and is left out; norms are float32."""
    E, F, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    matrices = L * (2 * E * Hq * d + 2 * E * Hkv * d + E * Hkv + 3 * E * F)
    matrices += m["vocab_size"] * E  # the head (tied or not, read once)
    norms = L * (2 * E + 2 * d + Hkv) + E
    return matrices * DTYPE_BYTES[dtype] + norms * 4


def chunk_kernel_flops(m: Mapping, tokens: int) -> int:
    """FLOPs of the inter-chunk part of `tokens` prefill tokens, what
    retention_chunk_kernel computes: per token and layer the read
    phi(q)^T [S, z] for every query head and the update of [S, z] for
    every KV head, 2 D (d + 1) each. The intra-chunk part (attention
    form, in XLA) is not the kernel's and is left out. No manifest metric
    reads this: a 3 s trace of the cell does not always hold a chunk, and
    a traced run that lacks a listed metric is refused (PERF.md section 7
    has the share read by hand from the traces that did)."""
    Hq, Hkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    per_token = 2 * true_features(m) * (d + 1) * (Hq + Hkv)
    return m["num_hidden_layers"] * per_token * tokens
