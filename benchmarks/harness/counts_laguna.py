"""Parameters, bytes and FLOPs a call of the Laguna family NEEDS, from
shapes (the counterpart of benchmarks/harness/counts_mimo.py for a window
family whose kinds differ in QUERY heads; PEAKS and hbm_time_s are
counts.py's), and what the traced steps of a cell of it held (the tap's
decode rows and prefill chunks, each checked against the trace's own step
programs: the functions of harness/counts_deepseek.py, whose clock-joining
this family shares).

Every count is TRUE bytes and operations: a window layer's decode row reads
the min(context, window) positions it can see (the kernel fetches whole
blocks: five where 512 positions straddle one), attention FLOPs are counted
over the visible (query, position) pairs and the kind's TRUE query heads (48
on a full layer: the decode kernel pads a group of 6 to 8 sublanes and the
pad is not work), and a FLOP is counted once. What the expert product
streams is the program's own count over the window (`touched_share`: the
router decides what a step touches, and under this family's first draw a
512-row chunk touched 40 to 90 of 256 experts where rows choosing uniformly
would touch all: PERF.md section 6); no reader here gives the grouped
launches a roofline share, because nothing a reader is handed says what the
TRACED steps touched (PERF.md section 7)."""

from __future__ import annotations

from typing import Dict, Mapping, Optional

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    chunk_pairs, kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)
from benchmarks.harness.counts_mimo import window_chunk_pairs  # noqa: F401

WINDOW_DECODE_KERNEL = "%window_paged_attention_kernel"
FULL_DECODE_KERNEL = "%paged_attention_kernel"
KINDS = {"full_attention": "attention", "sliding_attention": "window"}


def _held(m: Mapping):
    return m.get("layers_held", range(m["num_hidden_layers"]))


def kinds(m: Mapping) -> tuple:
    return tuple(KINDS[m["layer_types"][l]] for l in _held(m))


def layers_of(m: Mapping, kind: str) -> int:
    return kinds(m).count(kind)


def dense_layers(m: Mapping) -> int:
    return sum(1 for l in _held(m) if m["mlp_layer_types"][l] == "dense")


def routed_layers(m: Mapping) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def query_heads(m: Mapping, kind: str) -> int:
    return next(m["num_attention_heads_per_layer"][l]
                for l, k in zip(_held(m), kinds(m)) if k == kind)


def param_counts(m: Mapping) -> Dict[str, int]:
    """Matrix parameters of the configuration as held (norm gains apart)."""
    E, D, Hkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]

    def gqa(kind):  # q and o over the kind's query heads, k and v, the gate a head
        Hq = query_heads(m, kind)
        return E * (2 * Hq * D + 2 * Hkv * D + Hq)

    expert = 3 * E * m["moe_intermediate_size"]
    shared = 3 * E * m["shared_expert_intermediate_size"]
    dense = 3 * E * m["intermediate_size"]
    router = E * m["num_experts"]
    routed = m["num_experts"] * expert + shared + router
    embed = m["vocab_size"] * E
    full, window = gqa("attention"), gqa("window")
    return {
        "full": full, "window": window, "expert": expert, "shared": shared, "dense": dense,
        "router": router, "routed": routed, "embed": embed, "head": embed,
        "total": (layers_of(m, "attention") * full + layers_of(m, "window") * window
                  + dense_layers(m) * dense + routed_layers(m) * routed + 2 * embed),
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a step reads when it touches EVERY expert: all
    matrices once, the head whole (the embedding's lookup reads rows)."""
    c = param_counts(m)
    return (c["total"] - c["embed"]) * DTYPE_BYTES[dtype] // tp


def _kv_bytes_per_token(m: Mapping, kind: str, dtype: str) -> int:
    return (layers_of(m, kind) * m["num_key_value_heads"] * 2 * m["head_dim"]
            * DTYPE_BYTES[dtype])


def full_kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds over the FULL layers: the
    pool that grows with the context."""
    return _kv_bytes_per_token(m, "attention", dtype)


def window_kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """... and over the WINDOW layers, for the positions inside the window."""
    return _kv_bytes_per_token(m, "window", dtype)


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


def routed_pairs_per_token(m: Mapping) -> int:
    """Pairs a token makes over the routed layers: every one is computed
    here (all experts are held)."""
    return routed_layers(m) * m["num_experts_per_tok"]


def expert_pair_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["expert"]


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers, routed experts and
    the head apart: both kinds' projections and gates, the dense MLP, the
    routers and the shared experts."""
    c = param_counts(m)
    return 2 * (layers_of(m, "attention") * c["full"] + layers_of(m, "window") * c["window"]
                + dense_layers(m) * c["dense"]
                + routed_layers(m) * (c["router"] + c["shared"]))


def attention_pair_flops(m: Mapping, kind: str) -> int:
    """One (query token, cached position) pair in ONE layer of `kind`:
    scores and context, 2 FLOPs a lane each, over the kind's query heads."""
    return 4 * m["head_dim"] * query_heads(m, kind)


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["head"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.agent's numerator."""
    W = int(m["sliding_window"])
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    per_token = token_matrix_flops(m) + routed_pairs_per_token(m) * expert_pair_flops(m)
    full = sum(chunk_pairs(s, chunk) for s in chunk_starts) + sum(decode_contexts)
    window = (sum(window_chunk_pairs(s, chunk, W) for s in chunk_starts)
              + sum(min(int(c), W) for c in decode_contexts))
    attention = (layers_of(m, "attention") * full * attention_pair_flops(m, "attention")
                 + layers_of(m, "window") * window * attention_pair_flops(m, "window"))
    return (tokens * per_token + attention
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))


def touched_share(w) -> Optional[float]:
    """Held experts a layer and step touched / held experts, over the
    window, from the program's counters; None where it has not both."""
    touched = w.counter_delta("xllm_engine_moe_experts_touched_total")
    held = w.counter_delta("xllm_engine_moe_experts_held_total")
    if touched is None or not held:
        return None
    return touched / held
