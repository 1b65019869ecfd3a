"""Parameters, bytes and FLOPs a call of the MiMo-V2-Flash family NEEDS,
from shapes (the counterpart of benchmarks/harness/counts_solar.py for the
window family; PEAKS and hbm_time_s are counts.py's), and what the traced
steps of a cell of it held (the tap's decode rows and prefill chunks, each
checked against the trace's own step programs: the functions of
harness/counts_deepseek.py, whose clock-joining this family shares).

Every count is TRUE bytes and operations, whatever the pool's layout: a
key row is its 192 lanes (the pool pads it to 256), a window layer's decode
row reads the min(context, window) positions it can see (the kernel
fetches whole blocks: two for 128 positions that straddle one), attention
FLOPs are counted over the visible (query, position) pairs alone, and a
FLOP is counted once. What the ROUTER did in the traced steps reaches no
reader (PERF.md section 7): model FLOPs take the model's own number of
held pairs a token."""

from __future__ import annotations

from typing import Dict, Mapping

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    chunk_pairs, kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)

WINDOW_DECODE_KERNEL = "%window_paged_attention_kernel"
FULL_DECODE_KERNEL = "%paged_attention_kernel"


def kinds(m: Mapping) -> tuple:
    held = m.get("layers_held", range(m["num_hidden_layers"]))
    return tuple("window" if m["hybrid_layer_pattern"][l] else "attention" for l in held)


def window_layers(m: Mapping) -> int:
    return kinds(m).count("window")


def full_layers(m: Mapping) -> int:
    return kinds(m).count("attention")


def dense_layers(m: Mapping) -> int:
    held = m.get("layers_held", range(m["num_hidden_layers"]))
    return sum(1 for l in held if not m["moe_layer_freq"][l])


def routed_layers(m: Mapping) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def held_experts(m: Mapping) -> int:
    return int(m["n_routed_experts"])


def router_width(m: Mapping) -> int:
    return int(m.get("n_routed_experts_published", m["n_routed_experts"]))


def param_counts(m: Mapping) -> Dict[str, int]:
    """Matrix parameters of the configuration AS HELD (norm gains, sinks
    and the selection bias apart): `n_routed_experts` experts a routed
    layer, `vocab_size` rows of the embedding and of the head."""
    E, Hq = m["hidden_size"], m["num_attention_heads"]
    D, Dv = m["head_dim"], m["v_head_dim"]

    def gqa(kv):  # q and o over the query heads, k and v over the KV heads
        return E * Hq * (D + Dv) + E * kv * (D + Dv)

    full, window = gqa(m["num_key_value_heads"]), gqa(m["swa_num_key_value_heads"])
    expert = 3 * E * m["moe_intermediate_size"]
    dense = 3 * E * m["intermediate_size"]
    router = E * router_width(m)
    routed = held_experts(m) * expert + router
    embed = m["vocab_size"] * E
    return {
        "full": full, "window": window, "expert": expert, "dense": dense, "router": router,
        "routed": routed, "embed": embed, "head": embed,
        "total": (full_layers(m) * full + window_layers(m) * window + dense_layers(m) * dense
                  + routed_layers(m) * routed + 2 * embed),
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a step reads when it touches EVERY held expert: all
    matrices once, the head whole (the embedding's lookup reads rows)."""
    c = param_counts(m)
    return (c["total"] - c["embed"]) * DTYPE_BYTES[dtype] // tp


def full_kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds over the FULL layers: the
    pool that grows with the context."""
    return (full_layers(m) * m["num_key_value_heads"] * (m["head_dim"] + m["v_head_dim"])
            * DTYPE_BYTES[dtype])


def window_kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """... and over the WINDOW layers, for the positions inside the window."""
    return (window_layers(m) * m["swa_num_key_value_heads"] * (m["head_dim"] + m["v_head_dim"])
            * DTYPE_BYTES[dtype])


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    return full_kv_bytes_per_token(m, dtype) // tp


def window_decode_bytes(m: Mapping, contexts, dtype: str = "bfloat16") -> int:
    """What the window layers' decode launches must read for decode rows
    of the given contexts: each row its last min(context, window) tokens."""
    W = int(m["sliding_window"])
    return sum(min(int(c), W) for c in contexts) * window_kv_bytes_per_token(m, dtype)


def full_decode_bytes(m: Mapping, contexts, dtype: str = "bfloat16") -> int:
    """... and the full layers': each row its whole context."""
    return sum(int(c) for c in contexts) * full_kv_bytes_per_token(m, dtype)


def window_chunk_pairs(start: int, tokens: int, window: int) -> int:
    """(query, position) pairs a window layer sees for a chunk of `tokens`
    after `start` cached ones: position p sees min(p + 1, window)."""
    return sum(min(p + 1, window) for p in range(start, start + tokens))


def routed_pairs_per_token(m: Mapping) -> float:
    """Pairs a token brings to THIS holder's experts over the routed
    layers, by the model's definition: top-k of the published router, the
    held share."""
    return routed_layers(m) * m["num_experts_per_tok"] * held_experts(m) / router_width(m)


def expert_pair_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["expert"]


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers, routed experts and
    the head apart: the mixers' projections, the dense MLP, the routers."""
    c = param_counts(m)
    return 2 * (full_layers(m) * c["full"] + window_layers(m) * c["window"]
                + dense_layers(m) * c["dense"] + routed_layers(m) * c["router"])


def attention_pair_flops(m: Mapping) -> int:
    """One (query token, cached position) pair in ONE layer: scores over
    the key's lanes and context over the value's, 2 each a query head."""
    return 2 * (m["head_dim"] + m["v_head_dim"]) * m["num_attention_heads"]


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["head"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.longmix's numerator."""
    W = int(m["sliding_window"])
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    per_token = token_matrix_flops(m) + routed_pairs_per_token(m) * expert_pair_flops(m)
    full = sum(chunk_pairs(s, chunk) for s in chunk_starts) + sum(decode_contexts)
    window = (sum(window_chunk_pairs(s, chunk, W) for s in chunk_starts)
              + sum(min(int(c), W) for c in decode_contexts))
    pairs = full_layers(m) * full + window_layers(m) * window
    return (tokens * per_token + pairs * attention_pair_flops(m)
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))
