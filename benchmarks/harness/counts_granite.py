"""Parameters, bytes and FLOPs a call of the Granite 4.0-H family NEEDS,
from shapes (the counterpart of benchmarks/harness/counts.py, which is the
Llama family's; PEAKS and hbm_time_s are taken from there), and what the
traced steps of a cell of it held (the tap's decode rows and prefill
chunks, each checked against the trace's own step programs: the two
functions of harness/counts_deepseek.py, whose clock-joining this family
shares). Every count is a lower bound: the state is its H x P x N true
numbers a layer (the pool stores exactly those), the convolution's rows,
the K/V rows and every activation are left out of the update kernel's
bytes, attention FLOPs are counted over the causal pairs alone, and a FLOP
is counted once although float32 operands run up to six bf16 passes. What
the ROUTER did in the traced steps reaches no reader (PERF.md section 7):
model FLOPs take the model's own number of held pairs a token."""

from __future__ import annotations

from typing import Dict, Mapping

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    chunk_pairs, kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)


def kinds(m: Mapping) -> tuple:
    return tuple(m["layer_types"][: m["num_hidden_layers"]])


def mamba_layers(m: Mapping) -> int:
    return kinds(m).count("mamba")


def attention_layers(m: Mapping) -> int:
    return kinds(m).count("attention")


def held_experts(m: Mapping) -> int:
    return int(m["num_local_experts"])


def router_width(m: Mapping) -> int:
    return int(m.get("num_local_experts_published", m["num_local_experts"]))


def _mamba_dims(m: Mapping):
    H, P, G, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"], m["mamba_d_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters of the configuration AS HELD (norm gains apart, the
    convolution and the per-head vectors in): `num_local_experts` experts
    a layer, `vocab_size` rows of the tied embedding."""
    E = m["hidden_size"]
    H, _, _, _, d_in, conv = _mamba_dims(m)
    Hq, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = E // Hq
    mamba = (E * (d_in + conv + H) + conv * m["mamba_d_conv"] + conv + 3 * H + d_in * E)
    attention = 2 * E * Hq * D + 2 * E * Hkv * D
    expert = 3 * E * m["intermediate_size"]
    shared = 3 * E * m["shared_intermediate_size"]
    router = E * router_width(m)
    mlp = held_experts(m) * expert + shared + router
    Lm, La = mamba_layers(m), attention_layers(m)
    embed = m["vocab_size"] * E
    return {
        "mamba": mamba, "attention": attention, "expert": expert, "shared": shared,
        "router": router, "mlp": mlp, "mamba_layer": mamba + mlp, "attention_layer": attention + mlp,
        "embed": embed, "total": Lm * (mamba + mlp) + La * (attention + mlp) + embed,
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a step reads when it touches EVERY held expert: all
    matrices once, the tied table as the head (the lookup reads rows)."""
    return param_counts(m)["total"] * DTYPE_BYTES[dtype] // tp


def state_bytes_per_row(m: Mapping, state_dtype: str = "float32") -> int:
    """Bytes of ONE sequence's SSM state over the Mamba layers (one
    direction: a decode step reads them and writes them)."""
    H, P, _, N, _, _ = _mamba_dims(m)
    return mamba_layers(m) * H * P * N * DTYPE_BYTES[state_dtype]


def slot_bytes(m: Mapping, state_dtype: str = "float32") -> int:
    """... and with the convolution's K-1 rows: a state slot."""
    conv = _mamba_dims(m)[5]
    return state_bytes_per_row(m, state_dtype) + mamba_layers(m) * (m["mamba_d_conv"] - 1) * conv \
        * DTYPE_BYTES[state_dtype]


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds: the attention layers alone."""
    D = m["hidden_size"] // m["num_attention_heads"]
    return 2 * attention_layers(m) * m["num_key_value_heads"] * D * DTYPE_BYTES[dtype]


def update_kernel_bytes(m: Mapping, rows: int, state_dtype: str = "float32") -> int:
    """What mamba_update_kernel must move for `rows` live decode rows over
    the Mamba layers: every row's state read and written once."""
    return 2 * rows * state_bytes_per_row(m, state_dtype)


def scan_flops_per_token(m: Mapping) -> int:
    """The recurrence of ONE token in one Mamba layer: the state's decay,
    its rank-one update and the read-out, 2 FLOPs each an entry."""
    H, P, _, N, _, _ = _mamba_dims(m)
    return 6 * H * P * N


def chunk_flops(m: Mapping, tokens: int) -> int:
    """The chunk form of `tokens` tokens from a carried state in one Mamba
    layer: C B^T, the masked product with dt X over the causal pairs, the
    carried state's read-out and the state's update."""
    H, P, G, N, _, _ = _mamba_dims(m)
    pairs = tokens * (tokens + 1) // 2
    return 2 * G * N * pairs + 2 * H * P * pairs + 4 * tokens * H * P * N


def routed_pairs_per_token(m: Mapping) -> float:
    """Pairs a token brings to THIS holder's experts over the layers, by
    the model's definition: top-k of the published router, the held share."""
    return m["num_hidden_layers"] * m["num_experts_per_tok"] * held_experts(m) / router_width(m)


def expert_pair_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["expert"]


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers, routed experts, the
    scan and the head apart: the mixers' projections (and the depthwise
    convolution), the shared MLP and the router."""
    c = param_counts(m)
    Lm, La = mamba_layers(m), attention_layers(m)
    return 2 * (Lm * c["mamba"] + La * c["attention"] + (Lm + La) * (c["shared"] + c["router"]))


def attention_pair_flops(m: Mapping) -> int:
    """One (query token, cached position) pair over the attention layers:
    scores and context, 2 x D each a query head."""
    D = m["hidden_size"] // m["num_attention_heads"]
    return attention_layers(m) * 4 * D * m["num_attention_heads"]


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["embed"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.assist's numerator."""
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    per_token = (token_matrix_flops(m) + routed_pairs_per_token(m) * expert_pair_flops(m))
    scan = mamba_layers(m) * (len(chunk_starts) * chunk_flops(m, chunk)
                              + len(decode_contexts) * scan_flops_per_token(m))
    pairs = sum(chunk_pairs(s, chunk) for s in chunk_starts) + sum(decode_contexts)
    return (tokens * per_token + scan + pairs * attention_pair_flops(m)
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))
