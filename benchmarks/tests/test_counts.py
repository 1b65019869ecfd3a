"""counts.py against bytes and parameters worked by hand."""
import json
import os

import pytest

from benchmarks.harness import counts

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen25_3b_by_hand():
    m = cfg("qwen2.5-3b")
    # per layer: q 2048x2048, k and v 2048x256 each, o 2048x2048, biases
    # 2048+256+256, mlp 3x2048x11008, two norms
    attn = 2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 2560
    per_layer = attn + 3 * 2048 * 11008 + 2 * 2048
    c = counts.param_counts(m)
    assert c["per_layer"] == per_layer
    assert c["total"] == 36 * per_layer + 151936 * 2048 + 2048
    assert c["total"] == 3_085_938_688  # 3.09 B
    # KV per token: K and V, 36 layers, 2 heads of 128, bf16
    assert counts.kv_bytes_per_token(m) == 2 * 36 * 2 * 128 * 2 == 36864
    # one decode step reads every matrix once plus the tied head
    matrices = 36 * (per_layer - 2 * 2048) + 151936 * 2048
    assert counts.decode_weight_bytes(m) == matrices * 2 + (36 * 2 * 2048 + 2048) * 4


def test_mistral_7b_by_hand():
    m = {
        "hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "vocab_size": 32768, "tie_word_embeddings": False,
    }
    per_layer = (4096 * 4096 * 2 + 2 * 4096 * 1024) + 3 * 4096 * 14336 + 2 * 4096
    c = counts.param_counts(m)
    assert c["total"] == 32 * per_layer + 2 * 32768 * 4096 + 4096 == 7_248_023_552
    assert counts.kv_bytes_per_token(m, tp=4) == 2 * 32 * 8 * 128 * 2 // 4 == 32768


def test_flops_and_peaks():
    m = cfg("qwen2.5-3b")
    matrices = counts.param_counts(m)["layers"] - 36 * 2 * 2048 + 151936 * 2048
    assert counts.decode_step_flops(m, rows=2, context_tokens=10) == (
        2 * matrices * 2 + 4 * 16 * 128 * 36 * 10
    )
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert counts.hbm_time_s(819e9, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
