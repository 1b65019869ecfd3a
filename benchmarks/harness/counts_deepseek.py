"""Parameters, bytes and FLOPs a call of the DeepSeek-V2 family NEEDS, from
shapes (the counterpart of benchmarks/harness/counts.py, which is the Llama
family's; PEAKS and hbm_time_s are taken from there), and what the traced
steps of a cell of it held: the decode rows and prefill chunks the TAP saw
while the profiler was recording, each count checked against the trace's
own number of step programs. Every count is a lower bound: a latent row is
its 576 true numbers (stored in 640 lanes), attention FLOPs are counted
over the causal pairs alone (the kernels compute whole tiles), activations
are left out, and a FLOP is counted once. What the ROUTER did in the traced
steps (experts touched, pairs held) reaches no reader: the program's
counters are read at the window's ends alone (PERF.md section 7)."""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

from benchmarks.harness.counts import DTYPE_BYTES


def _dims(m: Mapping):
    return (m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def held_experts(m: Mapping) -> int:
    return int(m["n_routed_experts"])


def router_width(m: Mapping) -> int:
    return int(m.get("n_routed_experts_published", m["n_routed_experts"]))


def param_counts(m: Mapping) -> Dict[str, int]:
    """Matrix parameters (norm gains apart) of the configuration AS HELD:
    `n_routed_experts` experts a layer, `vocab_size` rows of both
    vocabulary matrices."""
    E, H, kvr, qr, dn, dr, dv = _dims(m)
    attn = E * qr + qr * H * (dn + dr) + E * (kvr + dr) + H * kvr * (dn + dv) + H * dv * E
    expert = 3 * E * m["moe_intermediate_size"]
    shared = m["n_shared_experts"] * expert
    router = E * router_width(m)
    dense = 3 * E * m["intermediate_size"]
    kd = m["first_k_dense_replace"]
    n = m["num_hidden_layers"] - kd
    moe_layer = attn + shared + router + held_experts(m) * expert
    vocab = m["vocab_size"] * E
    return {
        "attention": attn, "expert": expert, "shared": shared, "router": router, "dense_mlp": dense,
        "dense_layer": attn + dense, "expert_layer": moe_layer, "embed": vocab, "lm_head": vocab,
        "total": kd * (attn + dense) + n * moe_layer + 2 * vocab,
    }


def expert_bytes(m: Mapping, dtype: str = "bfloat16") -> int:
    """Bytes of ONE routed expert's three matrices."""
    return param_counts(m)["expert"] * DTYPE_BYTES[dtype]


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a step reads when it touches EVERY held expert: all
    matrices but the embedding table (a lookup reads rows), once."""
    c = param_counts(m)
    return (c["total"] - c["embed"]) * DTYPE_BYTES[dtype] // tp


def fixed_weight_bytes(m: Mapping, dtype: str = "bfloat16") -> int:
    """decode_weight_bytes less the routed experts: what every step reads
    whichever experts its tokens chose."""
    n = m["num_hidden_layers"] - m["first_k_dense_replace"]
    return decode_weight_bytes(m, dtype) - n * held_experts(m) * expert_bytes(m, dtype)


def latent_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """Bytes one cached token holds over all layers: c_kv and the one
    k_pe, their true widths (the pool stores 640 lanes for 576)."""
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * DTYPE_BYTES[dtype]


def attention_pair_flops(m: Mapping, absorbed: bool) -> int:
    """FLOPs of one (query token, cached position) pair in one layer, all
    heads: absorbed form 2 x (576 + 512) a head (scores against the latent
    row, context in latent space), materialised 2 x (192 + 128)."""
    _, H, kvr, _, dn, dr, dv = _dims(m)
    per_head = (kvr + dr) + kvr if absorbed else (dn + dr) + dv
    return 2 * per_head * H


def chunk_pairs(start: int, tokens: int) -> int:
    """Causal (query, position) pairs of a chunk of `tokens` after `start`
    cached tokens."""
    return tokens * start + tokens * (tokens + 1) // 2


def chunk_attention_flops(m: Mapping, start: int, tokens: int, absorbed: bool = True) -> int:
    return m["num_hidden_layers"] * attention_pair_flops(m, absorbed) * chunk_pairs(start, tokens)


def decode_attention_flops(m: Mapping, context: int, absorbed: bool = True) -> int:
    """One decode row over `context` cached positions (its own included)."""
    return m["num_hidden_layers"] * attention_pair_flops(m, absorbed) * context


def expert_pair_flops(m: Mapping) -> int:
    """One pair through one expert: 6 x E x F."""
    return 2 * param_counts(m)["expert"]


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers, routed experts and
    the head apart: attention projections, the dense MLP, the shared
    expert and the router."""
    c = param_counts(m)
    kd = m["first_k_dense_replace"]
    n = m["num_hidden_layers"] - kd
    return 2 * (m["num_hidden_layers"] * c["attention"] + kd * c["dense_mlp"]
                + n * (c["shared"] + c["router"]))


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["lm_head"]


# ---------------------------------------------------- the traced steps
#
# The tap (harness/stack.py) stamps every emission of every request with the
# process's monotonic clock; run.py's `trace_span` says on the same clock
# when the profiler was recording. An engine step books all its rows in one
# pass, so the tap's stamps fall into one cluster a step; a prompt's chunks
# run one a step (the budget is one chunk, every prompt whole chunks), in
# consecutive steps, one prompt after another in the order of their first
# tokens. Both counts are held against the trace's own number of step
# programs (`check`): readers.traced_emissions' check holds the rows against
# the WINDOW's mean rows a step, which an open loop leaves by a factor of two
# in 3 s (16, 9.5, 7.9 in flight over the thirds of one window).

STEP_PROGRAMS = ("_decode_impl", "_mixed_impl")
MIXED_PROGRAM = "_mixed_impl"
CHECK_RANGE = (0.8, 1.25)  # as readers.TRACED_TOKENS_RANGE
CHECK_FROM = 10  # a ratio of fewer counts than this says nothing


def span_of(w) -> Optional[Tuple[float, float]]:
    if w.trace is None or w.trace_span is None:
        return None
    return w.t_zero + w.trace_span[0], w.t_zero + w.trace_span[1]


def program_durations_ns(w, *programs: str) -> List[float]:
    return [d for p in programs for d in w.trace["program_durations_ns"].get(p, ())]


def traced_steps(w) -> Tuple[int, float]:
    """(step programs in the trace, their summed device seconds)."""
    durs = program_durations_ns(w, *STEP_PROGRAMS)
    return len(durs), sum(durs) / 1e9


def check(w, name: str, counted: int, traced: int, **more) -> None:
    """counted from the tap / counted in the trace, written to `w.checks`
    (run.py logs it and holds it to the range, as PR 33 made the rule)."""
    if traced >= CHECK_FROM:
        w.checks[name] = {"value": counted / traced, "low": CHECK_RANGE[0], "high": CHECK_RANGE[1],
                          "from_tap": counted, "in_trace": traced, **more}


def traced_decode_contexts(w) -> List[int]:
    """Context of every decode row of the traced steps: a token the tap
    saw emitted while the profiler was recording that was not its
    request's first (that one comes out of a prefill chunk). Checks
    itself: the steps the tap saw in the span (clusters of stamps closer
    than half the shortest traced step program) against the step
    programs the trace holds; a step with no decode row and no prompt's
    last chunk emits nothing and is the tap's to miss."""
    span = span_of(w)
    if span is None:
        return []
    out, stamps = [], []
    for tap in w.taps.values():
        seen = 0
        for t, n in zip(tap["times"], tap["counts"]):
            if span[0] <= t < span[1]:
                stamps.append(t)
                out += [tap["prompt_len"] + seen + k for k in range(n) if seen + k > 0]
            seen += n
    durs = program_durations_ns(w, *STEP_PROGRAMS)
    if durs and stamps:
        stamps.sort()
        apart = 0.5 * min(durs) / 1e9
        steps = 1 + sum(1 for a, b in zip(stamps, stamps[1:]) if b - a > apart)
        check(w, "traced_steps_ratio.doc", steps, len(durs), decode_rows=len(out))
    return out


def traced_chunk_starts(w, chunk: int) -> List[int]:
    """Cached tokens before each prefill chunk of the traced steps, from
    the tap: a prompt's n chunks run in n consecutive steps that end at
    its first token, and start where the prompt before it (by first
    token) ended or, if later, where it was added; a chunk is counted
    where its step's end falls in the span. Checks itself against the
    trace's `_mixed_impl` executions (one a chunk)."""
    span = span_of(w)
    if span is None:
        return []
    starts, free = [], float("-inf")
    for tap in sorted((t for t in w.taps.values() if t["times"]), key=lambda t: t["times"][0]):
        n = max(1, tap["prompt_len"] // chunk)
        t0, t1 = max(tap["t_add"], free), tap["times"][0]
        starts += [j * chunk for j in range(n) if span[0] <= t0 + (j + 1) / n * (t1 - t0) < span[1]]
        free = t1
    check(w, "traced_chunks_ratio.doc", len(starts), len(program_durations_ns(w, MIXED_PROGRAM)))
    return starts


def routed_pairs_per_token(m: Mapping) -> float:
    """Pairs a token brings to THIS holder's experts over the expert
    layers, by the model's definition: top-k of the published router, the
    held share of it. Model FLOPs (step_mfu.doc) count these, whatever the
    router did in the traced steps."""
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    return layers * m["num_experts_per_tok"] * held_experts(m) / router_width(m)


def kernel_seconds(w, *prefixes: str) -> float:
    return sum(v for k, v in w.trace["ops"].items() if k.startswith(prefixes)) / 1e9
