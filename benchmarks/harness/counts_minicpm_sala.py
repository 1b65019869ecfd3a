"""Parameters, bytes and FLOPs a call of the MiniCPM-SALA family NEEDS, from
shapes (the counterpart of benchmarks/harness/counts_falcon_h1.py: PEAKS and
hbm_time_s are counts.py's, the traced steps' readers counts_deepseek.py's,
whose clock-joining this family shares). Two kinds of layer: `minicpm4`
(block-sparse attention: K/V rows, compressed-key rows, a selection a query
group) and `lightning-attn` (a state slot). Every count is a lower bound and
counts the WORK, not what a kernel happens to move: a row past dense_len
needs the K and V rows of the tokens of its `topk` selected blocks (the own
block's only up to the row) and the compressed keys it can see; a row at or
under it its whole context; the state is its H x d x d true numbers a layer;
attention FLOPs are counted over the pairs attended alone, and a FLOP is
counted once although float32 operands run up to six bf16 passes. So a
roofline share read from these stays the same whatever kernel does the work,
and cannot pass 100 %."""

from __future__ import annotations

from typing import Dict, Mapping, Sequence

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)

# the launches whose device time is a roofline's denominator, by the names
# they carry in the trace: a later kernel under another name is added HERE
# (a name the trace lacks adds nothing)
UPDATE_KERNELS = ("%lightning_update_kernel",)
STAGE2_KERNELS = ("%paged_attention_kernel",)
SELECT_REGION = "attn_select"


def kinds(m: Mapping) -> Dict[str, int]:
    """Layers held, by mixer type."""
    ids = m.get("layer_ids") or range(m["num_hidden_layers"])
    held = [m["mixer_types"][int(i)] for i in ids]
    return {"sparse": held.count("minicpm4"), "lightning": held.count("lightning-attn")}


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters of the configuration AS HELD (the layer norms and the
    final norm apart; the QK and output norm gains in)."""
    E, F = m["hidden_size"], m["intermediate_size"]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    H, d = m["lightning_nh"], m["lightning_head_dim"]
    n = kinds(m)
    sparse = 3 * E * Hq * D + 2 * E * Hkv * D + 2 * D
    lightning = 5 * E * H * d + 2 * d + H * d
    mlp = 3 * E * F
    embed = m["vocab_size"] * E
    layers = n["sparse"] * (sparse + mlp) + n["lightning"] * (lightning + mlp)
    return {"sparse": sparse, "lightning": lightning, "mlp": mlp, "layers": layers,
            "embed": embed, "head": embed, "total": layers + 2 * embed}


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16") -> int:
    """Weight bytes a decode step reads: every layer's matrices and the
    head once (the embedding's lookup reads rows)."""
    c = param_counts(m)
    return (c["layers"] + c["head"]) * DTYPE_BYTES[dtype]


def state_bytes_per_row(m: Mapping, state_dtype: str = "float32") -> int:
    """Bytes of ONE sequence's lightning state over the lightning layers
    (one direction: a decode step reads them and writes them)."""
    H, d = m["lightning_nh"], m["lightning_head_dim"]
    return kinds(m)["lightning"] * H * d * d * DTYPE_BYTES[state_dtype]


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds over the sparse layers."""
    return 2 * kinds(m)["sparse"] * m["num_key_value_heads"] * m["head_dim"] * DTYPE_BYTES[dtype]


def compressed_bytes_per_token(m: Mapping, state_dtype: str = "float32") -> float:
    """Compressed-key bytes a cached token brings over the sparse layers
    (one key every kernel_stride tokens, a KV head)."""
    sp = m["sparse_config"]
    return (kinds(m)["sparse"] * m["num_key_value_heads"] * m["head_dim"]
            * DTYPE_BYTES[state_dtype] / sp["kernel_stride"])


def update_kernel_bytes(m: Mapping, rows: int, state_dtype: str = "float32") -> int:
    """What the lightning decode update must move for `rows` LIVE decode
    rows over the lightning layers: every live row's state read and
    written once."""
    return 2 * rows * state_bytes_per_row(m, state_dtype)


def selects(m: Mapping, context: int) -> bool:
    return context > m["sparse_config"]["dense_len"]


def attended_tokens(m: Mapping, context: int) -> int:
    """Keys a row of `context` tokens (its own included) attends in a
    sparse layer: its selected blocks' tokens past dense_len, its whole
    context at or under it."""
    sp = m["sparse_config"]
    if not selects(m, context):
        return context
    return (sp["topk"] - 1) * sp["block_size"] + (context - 1) % sp["block_size"] + 1


def visible_keys(m: Mapping, context: int) -> int:
    """Compressed keys a row of `context` tokens can see."""
    sp = m["sparse_config"]
    return max(0, (context - sp["kernel_size"]) // sp["kernel_stride"] + 1)


def chunk_contexts(start: int, chunk: int) -> range:
    """The contexts of the rows of a prefill chunk after `start` cached tokens."""
    return range(start + 1, start + chunk + 1)


def _selected_rows(m: Mapping, chunk_starts: Sequence[int], chunk: int, decode_contexts):
    """The contexts of the traced rows that took the selected path."""
    rows = [c for c in decode_contexts if selects(m, c)]
    for s in chunk_starts:
        rows += [c for c in chunk_contexts(s, chunk) if selects(m, c)]
    return rows


def _stage2_tokens(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> int:
    """(row, attended token) pairs of the stage-2 launches: every decode
    row (one under dense_len rides the same launch: its whole context)
    and every chunk row past dense_len."""
    tokens = sum(attended_tokens(m, c) for c in decode_contexts)
    for s in chunk_starts:
        tokens += sum(attended_tokens(m, c) for c in chunk_contexts(s, chunk) if selects(m, c))
    return tokens


def stage2_bytes(m: Mapping, chunk_starts, chunk: int, decode_contexts,
                 dtype: str = "bfloat16") -> int:
    """K and V bytes the stage-2 launches must read: the rows of each
    row's attended tokens, a KV head and sparse layer."""
    return _stage2_tokens(m, chunk_starts, chunk, decode_contexts) * kv_bytes_per_token(m, dtype)


def stage2_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> int:
    """Scores and context of the same rows: 2 x D each a query head and
    attended token, a sparse layer."""
    return (_stage2_tokens(m, chunk_starts, chunk, decode_contexts)
            * kinds(m)["sparse"] * 4 * m["head_dim"] * m["num_attention_heads"])


def stage1_bytes(m: Mapping, chunk_starts, chunk: int, decode_contexts,
                 state_dtype: str = "float32") -> int:
    """Compressed-key bytes stage 1 must read: a decode row its visible
    keys, a chunk the keys its LAST row can see once (its rows share a
    table), a KV head and sparse layer."""
    per_key = (kinds(m)["sparse"] * m["num_key_value_heads"] * m["head_dim"]
               * DTYPE_BYTES[state_dtype])
    keys = sum(visible_keys(m, c) for c in decode_contexts if selects(m, c))
    keys += sum(visible_keys(m, s + chunk) for s in chunk_starts if selects(m, s + chunk))
    return keys * per_key


def stage1_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> int:
    """q . c_j over the visible keys of every row that selects: 2 x D a
    query head, key and sparse layer."""
    keys = sum(visible_keys(m, c) for c in _selected_rows(m, chunk_starts, chunk, decode_contexts))
    return keys * kinds(m)["sparse"] * 2 * m["head_dim"] * m["num_attention_heads"]


def roofline_seconds(w, flops: float, nbytes: float) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    bf16 peak and bytes over the HBM peak."""
    peaks = w.counts.peaks(w.device_kind)
    return max(flops / peaks["flops_bf16"], w.counts.hbm_time_s(nbytes, w.device_kind))


def lightning_flops_per_token(m: Mapping) -> int:
    """The recurrence of ONE token in one lightning layer: the state's
    decay, its rank-one update and the read-out, 2 FLOPs each an entry."""
    H, d = m["lightning_nh"], m["lightning_head_dim"]
    return 6 * H * d * d


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the layers held (both mixers'
    projections and gates, the dense MLP), the state, attention's pairs
    and the head apart."""
    return 2 * param_counts(m)["layers"]


def head_flops(m: Mapping) -> int:
    return 2 * param_counts(m)["head"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.longdoc's numerator. The
    lightning layers at the recurrence's count a token (the chunked form's
    extra pairs are the program's choice, not the model's)."""
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    sparse_pairs = sum(attended_tokens(m, c) for c in decode_contexts)
    for s in chunk_starts:
        sparse_pairs += sum(attended_tokens(m, c) for c in chunk_contexts(s, chunk))
    attention = sparse_pairs * kinds(m)["sparse"] * 4 * m["head_dim"] * m["num_attention_heads"]
    return (tokens * (token_matrix_flops(m) + kinds(m)["lightning"] * lightning_flops_per_token(m))
            + attention + stage1_flops(m, chunk_starts, chunk, decode_contexts)
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))


def traced_rows(w):
    """(chunk starts, chunk, decode contexts) of the traced span."""
    chunk = int(w.engine["max_prefill_tokens"])
    return traced_chunk_starts(w, chunk), chunk, traced_decode_contexts(w)
