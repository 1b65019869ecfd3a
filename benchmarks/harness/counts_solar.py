"""Parameters, bytes and FLOPs a call of the Solar-Open2 family NEEDS,
from shapes (the counterpart of benchmarks/harness/counts_granite.py for
the other hybrid; PEAKS and hbm_time_s are counts.py's), and what the
traced steps of a cell of it held (the tap's decode rows and prefill
chunks, each checked against the trace's own step programs: the functions
of harness/counts_deepseek.py, whose clock-joining this family shares).
Every count is a lower bound: the state is its H x d x d true numbers a
layer (the pool stores exactly those), the convolution's rows, the K/V
rows, the kernel's columns and every activation are left out of the update
kernel's bytes, attention FLOPs are counted over the causal pairs alone,
and a FLOP is counted once although float32 operands run up to six bf16
passes. What the ROUTER did in the traced steps reaches no reader (PERF.md
section 7): model FLOPs take the model's own number of held pairs a token."""

from __future__ import annotations

from typing import Dict, Mapping

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    chunk_pairs, kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)


def kinds(m: Mapping) -> tuple:
    gqa = set(m["gqa_layers"])
    return tuple("attention" if l in gqa else "kda" for l in range(m["num_hidden_layers"]))


def kda_layers(m: Mapping) -> int:
    return kinds(m).count("kda")


def attention_layers(m: Mapping) -> int:
    return kinds(m).count("attention")


def held_experts(m: Mapping) -> int:
    return int(m["n_routed_experts"])


def router_width(m: Mapping) -> int:
    return int(m.get("n_routed_experts_published", m["n_routed_experts"]))


def _kda_dims(m: Mapping):
    la = m["linear_attn_config"]
    H, d = la["num_heads"], la["head_dim"]
    return H, d, la["short_conv_kernel_size"], int(m.get("kda_gate_rank", d)), int(m.get("kda_chunk_size", 64))


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters of the configuration AS HELD (norm gains apart, the
    convolution and the per-channel vectors in): `n_routed_experts`
    experts a layer, `vocab_size` rows of the embedding and of the head."""
    E = m["hidden_size"]
    H, d, K, r, _ = _kda_dims(m)
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    low_rank = E * r + r * H * d
    kda = (4 * E * H * d + 2 * low_rank + E * H  # q, k, v, o; the decay's and the gate's pairs; beta
           + 3 * H * d * (K + 1) + H * d + H)  # the convolution and its bias, dt_bias, A_log
    attention = 2 * E * Hq * D + 2 * E * Hkv * D + (E * Hq * D if m["use_gqa_gate"] else 0)
    expert = 3 * E * m["moe_intermediate_size"]
    shared = m["n_shared_experts"] * expert
    router = E * router_width(m)
    mlp = held_experts(m) * expert + shared + router
    Lk, La = kda_layers(m), attention_layers(m)
    embed = m["vocab_size"] * E
    return {
        "kda": kda, "attention": attention, "expert": expert, "shared": shared,
        "router": router, "mlp": mlp, "kda_layer": kda + mlp, "attention_layer": attention + mlp,
        "embed": embed, "head": embed,
        "total": Lk * (kda + mlp) + La * (attention + mlp) + 2 * embed,
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a step reads when it touches EVERY held expert: all
    matrices once, the head whole (the embedding's lookup reads rows)."""
    c = param_counts(m)
    return (c["total"] - c["embed"]) * DTYPE_BYTES[dtype] // tp


def state_bytes_per_row(m: Mapping, state_dtype: str = "float32") -> int:
    """Bytes of ONE sequence's delta-rule state over the KDA layers (one
    direction: a decode step reads them and writes them)."""
    H, d, _, _, _ = _kda_dims(m)
    return kda_layers(m) * H * d * d * DTYPE_BYTES[state_dtype]


def slot_bytes(m: Mapping, state_dtype: str = "float32") -> int:
    """... and with the convolution's K-1 rows of q, k and v: a state slot."""
    H, d, K, _, _ = _kda_dims(m)
    return state_bytes_per_row(m, state_dtype) + kda_layers(m) * (K - 1) * 3 * H * d \
        * DTYPE_BYTES[state_dtype]


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds: the attention layers alone."""
    return 2 * attention_layers(m) * m["num_key_value_heads"] * m["head_dim"] * DTYPE_BYTES[dtype]


def update_kernel_bytes(m: Mapping, rows: int, state_dtype: str = "float32") -> int:
    """What kda_update_kernel must move for `rows` live decode rows over
    the KDA layers: every row's state read and written once."""
    return 2 * rows * state_bytes_per_row(m, state_dtype)


def recurrence_flops_per_token(m: Mapping) -> int:
    """The recurrence of ONE token in one KDA layer: the state's decay
    (1 FLOP an entry), k^T S, the rank-one update and the read-out
    (2 each)."""
    H, d, _, _, _ = _kda_dims(m)
    return 7 * H * d * d


def chunk_flops(m: Mapping, tokens: int) -> int:
    """The chunk form of `tokens` tokens from a carried state in one KDA
    layer, in chunks of C: per chunk and head the two decayed grams over
    the causal pairs (2 d each), U = T V and W = T K+ over the causal
    pairs (2 d each), the carried state's three products W S, Q+ S and
    K^T U~ (2 C d d each) and the intra-chunk output over the causal
    pairs (2 d). The triangular solve is left out."""
    H, d, _, _, C = _kda_dims(m)
    C = min(C, tokens)
    chunks, pairs = tokens // C, C * (C + 1) // 2
    return chunks * H * (5 * 2 * d * pairs + 3 * 2 * C * d * d)


def routed_pairs_per_token(m: Mapping) -> float:
    """Pairs a token brings to THIS holder's experts over the layers, by
    the model's definition: top-k of the published router, the held share."""
    return m["num_hidden_layers"] * m["num_experts_per_tok"] * held_experts(m) / router_width(m)


def expert_pair_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["expert"]


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers, routed experts, the
    delta rule and the head apart: the mixers' projections (and the
    depthwise convolution), the shared expert and the router."""
    c = param_counts(m)
    Lk, La = kda_layers(m), attention_layers(m)
    return 2 * (Lk * c["kda"] + La * c["attention"] + (Lk + La) * (c["shared"] + c["router"]))


def attention_pair_flops(m: Mapping) -> int:
    """One (query token, cached position) pair over the attention layers:
    scores and context, 2 x D each a query head."""
    return attention_layers(m) * 4 * m["head_dim"] * m["num_attention_heads"]


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["head"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.think's numerator."""
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    per_token = (token_matrix_flops(m) + routed_pairs_per_token(m) * expert_pair_flops(m))
    delta = kda_layers(m) * (len(chunk_starts) * chunk_flops(m, chunk)
                             + len(decode_contexts) * recurrence_flops_per_token(m))
    pairs = sum(chunk_pairs(s, chunk) for s in chunk_starts) + sum(decode_contexts)
    return (tokens * per_token + delta + pairs * attention_pair_flops(m)
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))
