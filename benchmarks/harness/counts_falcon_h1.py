"""Parameters, bytes and FLOPs a call of the Falcon-H1 family NEEDS, from
shapes (the counterpart of benchmarks/harness/counts_granite.py: PEAKS and
hbm_time_s are counts.py's, the traced steps' readers counts_deepseek.py's,
whose clock-joining this family shares). EVERY block has both mixers: a
state slot and K/V rows in every layer. Every count is a lower bound: the
state is its H x P x N true numbers a layer (the pool stores exactly
those), the convolution's rows, the B and C planes and every activation
are left out of the update kernel's bytes, the K and V bytes are the true
rows of a context (whatever a 128-token block pads), attention FLOPs are
counted over the causal pairs alone, and a FLOP is counted once although
float32 operands run up to six bf16 passes."""

from __future__ import annotations

from typing import Dict, Mapping

from benchmarks.harness.counts import DTYPE_BYTES
from benchmarks.harness.counts_deepseek import (  # noqa: F401  (the readers' imports)
    chunk_pairs, kernel_seconds, traced_chunk_starts, traced_decode_contexts, traced_steps,
)

UPDATE_KERNEL = "%mamba_update_kernel"
DECODE_KERNEL = "%paged_attention_kernel"


def layers(m: Mapping) -> int:
    return int(m["num_hidden_layers"])


def _mamba_dims(m: Mapping):
    H, P, G, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"], m["mamba_d_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def param_counts(m: Mapping) -> Dict[str, int]:
    """Parameters of the configuration AS HELD (the two block norms and the
    final norm apart; the convolution, the per-head vectors and the gated
    norm's gain in): `num_hidden_layers` blocks, `vocab_size` rows of each
    vocabulary matrix."""
    E = m["hidden_size"]
    H, _, _, _, d_in, conv = _mamba_dims(m)
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    mamba = E * (d_in + conv + H) + conv * m["mamba_d_conv"] + conv + 3 * H + d_in + d_in * E
    attention = 2 * E * Hq * D + 2 * E * Hkv * D
    mlp = 3 * E * m["intermediate_size"]
    embed = m["vocab_size"] * E
    block = mamba + attention + mlp
    return {
        "mamba": mamba, "attention": attention, "mlp": mlp, "block": block,
        "embed": embed, "head": embed, "total": layers(m) * block + 2 * embed,
    }


def decode_weight_bytes(m: Mapping, dtype: str = "bfloat16", tp: int = 1) -> int:
    """Weight bytes a decode step reads: every block's matrices and the
    head once (the embedding's lookup reads rows)."""
    c = param_counts(m)
    return (layers(m) * c["block"] + c["head"]) * DTYPE_BYTES[dtype] // tp


def state_bytes_per_row(m: Mapping, state_dtype: str = "float32") -> int:
    """Bytes of ONE sequence's SSM state over the blocks (one direction: a
    decode step reads them and writes them)."""
    H, P, _, N, _, _ = _mamba_dims(m)
    return layers(m) * H * P * N * DTYPE_BYTES[state_dtype]


def slot_bytes(m: Mapping, state_dtype: str = "float32") -> int:
    """... and with the convolution's K-1 rows: a state slot."""
    conv = _mamba_dims(m)[5]
    return state_bytes_per_row(m, state_dtype) + layers(m) * (m["mamba_d_conv"] - 1) * conv \
        * DTYPE_BYTES[state_dtype]


def kv_bytes_per_token(m: Mapping, dtype: str = "bfloat16") -> int:
    """K and V bytes one cached token holds over the blocks."""
    return 2 * layers(m) * m["num_key_value_heads"] * m["head_dim"] * DTYPE_BYTES[dtype]


def update_kernel_bytes(m: Mapping, rows: int, state_dtype: str = "float32") -> int:
    """What mamba_update_kernel must move for `rows` LIVE decode rows over
    the blocks: every live row's state read and written once."""
    return 2 * rows * state_bytes_per_row(m, state_dtype)


def decode_kv_bytes(m: Mapping, contexts, dtype: str = "bfloat16") -> int:
    """What the paged decode launches must read for decode rows of the
    given contexts over the blocks: each row its whole context, once."""
    return sum(int(c) for c in contexts) * kv_bytes_per_token(m, dtype)


def scan_flops_per_token(m: Mapping) -> int:
    """The recurrence of ONE token in one block: the state's decay, its
    rank-one update and the read-out, 2 FLOPs each an entry."""
    H, P, _, N, _, _ = _mamba_dims(m)
    return 6 * H * P * N


def chunk_flops(m: Mapping, tokens: int) -> int:
    """The chunk form of `tokens` tokens from a carried state in one
    block: C B^T a group, the masked product with dt X over the causal
    pairs, the carried state's read-out and the state's update."""
    H, P, G, N, _, _ = _mamba_dims(m)
    pairs = tokens * (tokens + 1) // 2
    return 2 * G * N * pairs + 2 * H * P * pairs + 4 * tokens * H * P * N


def token_matrix_flops(m: Mapping) -> int:
    """Matrix FLOPs of one token through the blocks, the scan, attention's
    pairs and the head apart: both mixers' projections (and the depthwise
    convolution) and the dense MLP."""
    return 2 * layers(m) * param_counts(m)["block"]


def attention_pair_flops(m: Mapping) -> int:
    """One (query token, cached position) pair over the blocks: scores and
    context, 2 x D each a query head."""
    return layers(m) * 4 * m["head_dim"] * m["num_attention_heads"]


def head_flops(m: Mapping) -> int:
    """The head over the vocabulary rows HELD."""
    return 2 * param_counts(m)["head"]


def model_flops(m: Mapping, chunk_starts, chunk: int, decode_contexts) -> float:
    """Model FLOPs of the given prefill chunks (cached tokens before each)
    and decode rows (context of each): step_mfu.dialog's numerator."""
    tokens = len(chunk_starts) * chunk + len(decode_contexts)
    scan = layers(m) * (len(chunk_starts) * chunk_flops(m, chunk)
                        + len(decode_contexts) * scan_flops_per_token(m))
    pairs = sum(chunk_pairs(s, chunk) for s in chunk_starts) + sum(decode_contexts)
    return (tokens * token_matrix_flops(m) + scan + pairs * attention_pair_flops(m)
            + (len(chunk_starts) + len(decode_contexts)) * head_flops(m))
