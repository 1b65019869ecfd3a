"""Pallas kernel correctness vs the jnp oracles, run in interpreter mode on
CPU (the same kernel compiles natively on TPU; bench.py exercises that)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.ops.attention import paged_attention_gather
from xllm_service_tpu.ops.pallas.paged_attention import paged_attention_kernel


def make_case(
    rng, R=4, Hq=8, Hkv=4, D=128, BS=16, MB=8, num_blocks=64, dtype=jnp.float32
):
    q = jnp.asarray(rng.standard_normal((R, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    # distinct random block ids per sequence
    bt = jnp.asarray(
        rng.choice(num_blocks, size=(R, MB), replace=False).astype(np.int32)
    )
    seq_lens = jnp.asarray(
        rng.integers(1, MB * BS + 1, size=(R,)).astype(np.int32)
    )
    return q, k, v, bt, seq_lens


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("gqa", [1, 4])
def test_decode_kernel_matches_gather(seed, gqa):
    rng = np.random.default_rng(seed)
    Hkv = 4
    q, k, v, bt, seq_lens = make_case(rng, Hq=Hkv * gqa, Hkv=Hkv)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = paged_attention_gather(q, k, v, bt, seq_lens, scale)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_kernel_edge_lengths():
    """seq_len = 1 (single token), exactly one block, exactly full table."""
    rng = np.random.default_rng(2)
    q, k, v, bt, _ = make_case(rng, R=3, MB=4, BS=16)
    seq_lens = jnp.asarray([1, 16, 64], jnp.int32)
    scale = 0.125
    ref = paged_attention_gather(q, k, v, bt, seq_lens, scale)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_kernel_inactive_slots_zero():
    """seq_len = 0 rows (inactive decode slots) emit zeros, no DMAs."""
    rng = np.random.default_rng(4)
    q, k, v, bt, _ = make_case(rng, R=4, MB=4, BS=16)
    seq_lens = jnp.asarray([0, 5, 0, 64], jnp.int32)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, 0.125, interpret=True)
    out = np.asarray(out)
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    ref = paged_attention_gather(q, k, v, bt, seq_lens, 0.125)
    np.testing.assert_allclose(out[1], np.asarray(ref)[1], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out[3], np.asarray(ref)[3], atol=2e-5, rtol=2e-5)


def test_decode_kernel_bf16():
    rng = np.random.default_rng(3)
    q, k, v, bt, seq_lens = make_case(rng, dtype=jnp.bfloat16)
    scale = 0.125
    ref = paged_attention_gather(q, k, v, bt, seq_lens, scale)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


@pytest.mark.parametrize("start_pos,true_len", [(0, 24), (16, 13), (0, 1)])
def test_blockwise_prefill_matches_gather(start_pos, true_len):
    """Flash-style blockwise prefill (the serving path) == dense gather
    oracle, incl. prefix-cache offsets and padded tails."""
    from xllm_service_tpu.ops.attention import (
        prefill_attention_blockwise,
        prefill_attention_gather,
    )

    rng = np.random.default_rng(4)
    L, Hq, Hkv, D, BS, NB, CB = 24, 4, 2, 16, 8, 12, 6
    q = jnp.asarray(rng.standard_normal((L, Hq, D)), jnp.float32)
    k_cache = jnp.asarray(rng.standard_normal((NB, Hkv, BS, D)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((NB, Hkv, BS, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(NB)[:CB], jnp.int32)
    scale = D**-0.5
    want = prefill_attention_gather(
        q, k_cache, v_cache, table, jnp.int32(start_pos),
        jnp.int32(true_len), scale,
    )
    got = prefill_attention_blockwise(
        q, k_cache, v_cache, table, jnp.int32(start_pos),
        jnp.int32(true_len), scale,
    )
    valid = np.arange(L) < true_len
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(want)[valid], atol=2e-5, rtol=2e-5
    )


# ------------------------------------------------------- flash prefill

from xllm_service_tpu.ops.attention import prefill_attention_blockwise
from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel


def make_prefill_case(
    rng, P=3, Lpad=48, Hq=8, Hkv=4, D=128, BS=16, MB=8, num_blocks=64,
    dtype=jnp.float32,
):
    q = jnp.asarray(rng.standard_normal((P, Lpad, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    bt = jnp.asarray(
        np.stack([
            rng.choice(np.arange(1, num_blocks), size=MB, replace=False)
            for _ in range(P)
        ]).astype(np.int32)
    )
    return q, k, v, bt


def _blockwise_ref(q, k, v, bt, start_pos, true_len, scale):
    return jax.vmap(
        lambda qi, ti, sp, tl: prefill_attention_blockwise(
            qi, k, v, ti, sp, tl, scale
        )
    )(q, bt, start_pos, true_len)


@pytest.mark.parametrize("gqa", [1, 2])
@pytest.mark.parametrize("tile_q", [8, 16])
def test_flash_prefill_matches_blockwise(gqa, tile_q):
    """Fresh prompts (start_pos=0), ragged lengths, causal — kernel vs
    the blockwise scan oracle, including a tile_q that doesn't divide
    Lpad."""
    rng = np.random.default_rng(0)
    Hkv = 4
    q, k, v, bt = make_prefill_case(rng, Hq=Hkv * gqa, Hkv=Hkv)
    start_pos = jnp.zeros((3,), jnp.int32)
    true_len = jnp.asarray([48, 17, 1], jnp.int32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = _blockwise_ref(q, k, v, bt, start_pos, true_len, scale)
    out = flash_prefill_kernel(
        q, k, v, bt, start_pos, true_len, scale, interpret=True,
        tile_q=tile_q,
    )
    # Rows past true_len are undefined in the oracle output too — compare
    # only valid rows.
    for p, tl in enumerate([48, 17, 1]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


def test_flash_prefill_prefix_hit():
    """start_pos > 0 (chunked prefill / prefix-cache hit): queries attend
    to the cached prefix AND their own chunk, causally."""
    rng = np.random.default_rng(1)
    q, k, v, bt = make_prefill_case(rng, P=2, Lpad=32)
    start_pos = jnp.asarray([16, 40], jnp.int32)
    true_len = jnp.asarray([32, 23], jnp.int32)
    scale = 0.125
    ref = _blockwise_ref(q, k, v, bt, start_pos, true_len, scale)
    out = flash_prefill_kernel(
        q, k, v, bt, start_pos, true_len, scale, interpret=True, tile_q=16
    )
    for p, tl in enumerate([32, 23]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


@pytest.mark.parametrize("window", [12, 40])
def test_flash_prefill_window(window):
    """Sliding-window prefill (ADVICE r3 high): kernel masking AND its
    below-window chunk skip (start_pos deep enough that c0 > 0) match the
    blockwise oracle's HF semantics (position p attends [p-window+1, p])."""
    rng = np.random.default_rng(7)
    q, k, v, bt = make_prefill_case(rng, P=2, Lpad=32)
    start_pos = jnp.asarray([16, 96], jnp.int32)
    true_len = jnp.asarray([32, 23], jnp.int32)
    scale = 0.125
    ref = jax.vmap(
        lambda qi, ti, sp, tl: prefill_attention_blockwise(
            qi, k, v, ti, sp, tl, scale, window=window
        )
    )(q, bt, start_pos, true_len)
    out = flash_prefill_kernel(
        q, k, v, bt, start_pos, true_len, scale, interpret=True, tile_q=16,
        window=window,
    )
    for p, tl in enumerate([32, 23]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


def test_flash_prefill_window_dispatcher():
    """prefill_attention(window>0) down the forced-kernel branch agrees
    with the blockwise path (this dispatch used to raise TypeError)."""
    from xllm_service_tpu.ops.attention import prefill_attention

    rng = np.random.default_rng(8)
    q, k, v, bt = make_prefill_case(rng, P=2, Lpad=32, Hq=8, Hkv=4)
    start_pos = jnp.asarray([0, 48], jnp.int32)
    true_len = jnp.asarray([32, 20], jnp.int32)
    scale = 0.125
    ref = prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, use_kernel=False, window=24
    )
    out = prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, use_kernel=True,
        interpret=True, window=24,
    )
    for p, tl in enumerate([32, 20]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


def test_flash_prefill_int8():
    """int8 cache: the kernel's VMEM grouped dequant matches the
    dequantizing oracle within quantization tolerance. Tolerance budget:
    dequant_tile rounds the scaled tile to bf16 before the score matmul
    (the oracle dequantizes to bf16 too, but multiplies under f32
    promotion), so ~0.4% relative per product accumulates over D=64
    lanes — 5e-3 was borderline, 2e-2 is the honest bound."""
    from xllm_service_tpu.ops import kv_cache as kvc

    rng = np.random.default_rng(2)
    # BS=128: the int8 [G, BS] scale tile carries BS on lanes (chip rule).
    q, k, v, bt = make_prefill_case(rng, P=2, Lpad=32, BS=128, MB=2, num_blocks=16)
    kq = kvc.quantize_pool(k)
    vq = kvc.quantize_pool(v)
    start_pos = jnp.asarray([0, 16], jnp.int32)
    true_len = jnp.asarray([32, 30], jnp.int32)
    scale = 0.125
    ref = _blockwise_ref(q, kq, vq, bt, start_pos, true_len, scale)
    out = flash_prefill_kernel(
        q, kq, vq, bt, start_pos, true_len, scale, interpret=True, tile_q=16
    )
    for p, tl in enumerate([32, 30]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=2e-2, rtol=2e-2,
        )


def test_flash_prefill_bf16():
    rng = np.random.default_rng(3)
    q, k, v, bt = make_prefill_case(rng, dtype=jnp.bfloat16)
    start_pos = jnp.zeros((3,), jnp.int32)
    true_len = jnp.asarray([48, 9, 33], jnp.int32)
    scale = 0.125
    ref = _blockwise_ref(q, k, v, bt, start_pos, true_len, scale)
    out = flash_prefill_kernel(
        q, k, v, bt, start_pos, true_len, scale, interpret=True, tile_q=16
    )
    for p, tl in enumerate([48, 9, 33]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl].astype(np.float32),
            np.asarray(ref)[p, :tl].astype(np.float32),
            atol=2e-2, rtol=2e-2,
        )


def test_prefill_dispatcher_kernel_branch():
    """prefill_attention with interpret=True + forced kernel matches the
    blockwise path it replaces on TPU."""
    from xllm_service_tpu.ops.attention import prefill_attention

    rng = np.random.default_rng(4)
    q, k, v, bt = make_prefill_case(rng, P=2, Lpad=32)
    start_pos = jnp.asarray([0, 8], jnp.int32)
    true_len = jnp.asarray([20, 32], jnp.int32)
    ref = prefill_attention(
        q, k, v, bt, start_pos, true_len, 0.125, use_kernel=False
    )
    out = prefill_attention(
        q, k, v, bt, start_pos, true_len, 0.125, use_kernel=True,
        interpret=True,
    )
    for p, tl in enumerate([20, 32]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


# --------------------------------------------------- MLA flash prefill

from xllm_service_tpu.ops.attention import mla_prefill_blockwise
from xllm_service_tpu.ops.pallas.mla_prefill import mla_flash_prefill_kernel


def make_mla_prefill_case(
    rng, P=2, Lpad=32, Hq=8, C=128, BS=16, MB=8, num_blocks=64
):
    q = jnp.asarray(rng.standard_normal((P, Lpad, Hq, C)), jnp.float32)
    cache = jnp.asarray(
        rng.standard_normal((num_blocks, 1, BS, C)), jnp.float32
    )
    bt = jnp.asarray(
        np.stack([
            rng.choice(np.arange(1, num_blocks), size=MB, replace=False)
            for _ in range(P)
        ]).astype(np.int32)
    )
    return q, cache, bt


def _mla_blockwise_ref(q, cache, bt, start_pos, true_len, scale, kvr):
    return jax.vmap(
        lambda qi, ti, sp, tl: mla_prefill_blockwise(
            qi, cache, ti, sp, tl, scale, kvr
        )
    )(q, bt, start_pos, true_len)


@pytest.mark.parametrize("tile_q", [8, 16])
def test_mla_flash_prefill_matches_blockwise(tile_q):
    """Latent-space flash prefill vs the blockwise oracle: ragged lens,
    prefix hits, absorbed-form output ([.., kv_rank], W_UV applied by the
    caller)."""
    rng = np.random.default_rng(0)
    kvr = 40  # latent rank; C = kvr + rope(16)
    q, cache, bt = make_mla_prefill_case(rng, C=128)
    start_pos = jnp.asarray([0, 24], jnp.int32)
    true_len = jnp.asarray([32, 17], jnp.int32)
    scale = 0.125
    ref = _mla_blockwise_ref(q, cache, bt, start_pos, true_len, scale, kvr)
    out = mla_flash_prefill_kernel(
        q, cache, bt, start_pos, true_len, scale, kvr, interpret=True,
        tile_q=tile_q,
    )
    for p, tl in enumerate([32, 17]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


def test_mla_prefill_dispatcher_kernel_branch():
    from xllm_service_tpu.ops.attention import mla_prefill_attention

    rng = np.random.default_rng(1)
    kvr = 40
    q, cache, bt = make_mla_prefill_case(rng, C=128)
    start_pos = jnp.asarray([0, 8], jnp.int32)
    true_len = jnp.asarray([20, 32], jnp.int32)
    ref = mla_prefill_attention(
        q, cache, bt, start_pos, true_len, 0.125, kvr, use_kernel=False
    )
    out = mla_prefill_attention(
        q, cache, bt, start_pos, true_len, 0.125, kvr, use_kernel=True,
        interpret=True,
    )
    for p, tl in enumerate([20, 32]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )


# ------------------------- multi-query decode (speculative verify) kernel


def _mq_oracle(q, k, v, bt, seq_lens, S, scale):
    """Blockwise prefill as the oracle: query row s of seq r attends to
    seq_lens[r] + s context rows (prefill semantics with start_pos =
    seq_lens - 1, true_len = S for active rows)."""
    from xllm_service_tpu.ops.attention import prefill_attention

    start_pos = jnp.maximum(seq_lens - 1, 0)
    true_len = jnp.where(seq_lens > 0, S, 0)
    return prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, use_kernel=False
    )


@pytest.mark.parametrize("gqa", [1, 4])
@pytest.mark.parametrize("S", [2, 4])
def test_mq_decode_kernel_matches_blockwise(gqa, S):
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    rng = np.random.default_rng(0)
    Hkv = 4
    _, k, v, bt, seq_lens = make_case(rng, Hq=Hkv * gqa, Hkv=Hkv)
    R, MB = bt.shape
    BS = k.shape[2]
    q = jnp.asarray(
        rng.standard_normal((R, S, Hkv * gqa, k.shape[-1])), jnp.float32
    )
    # leave S rows of headroom inside the table for the extra positions
    seq_lens = jnp.minimum(seq_lens, MB * BS - S)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = _mq_oracle(q, k, v, bt, seq_lens, S, scale)
    out = multiquery_paged_attention_kernel(
        q, k, v, bt, seq_lens, scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_mq_decode_kernel_inactive_and_edge():
    """Inactive slots (seq_len = 0) emit zeros; seq_len = 1 and a
    block-boundary-straddling step are exact."""
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    rng = np.random.default_rng(3)
    S = 4
    _, k, v, bt, _ = make_case(rng, R=4, MB=4, BS=16)
    q = jnp.asarray(rng.standard_normal((4, S, 8, 128)), jnp.float32)
    # 14 + 4 > 16 straddles the first block boundary
    seq_lens = jnp.asarray([0, 1, 14, 60], jnp.int32)
    out = multiquery_paged_attention_kernel(
        q, k, v, bt, seq_lens, 0.125, interpret=True
    )
    ref = _mq_oracle(q, k, v, bt, seq_lens, S, 0.125)
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.all(out[0] == 0)
    np.testing.assert_allclose(out[1:], ref[1:], atol=2e-5, rtol=2e-5)


def test_mq_decode_kernel_int8():
    from xllm_service_tpu.ops import kv_cache as kvc
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    rng = np.random.default_rng(5)
    S = 3
    _, k, v, bt, seq_lens = make_case(rng, R=4, Hq=8, Hkv=4, D=128, BS=128,
                                      MB=4, num_blocks=32)
    q = jnp.asarray(rng.standard_normal((4, S, 8, 128)), jnp.float32)
    seq_lens = jnp.minimum(seq_lens, 4 * 128 - S)
    kq = kvc.quantize_pool(k)
    vq = kvc.quantize_pool(v)
    scale = 1.0 / np.sqrt(128)
    ref = _mq_oracle(q, kq, vq, bt, seq_lens, S, scale)
    out = multiquery_paged_attention_kernel(
        q, kq, vq, bt, seq_lens, scale, interpret=True
    )
    # int8 path: the kernel folds scales into scores and runs the pv
    # matmul in bf16; the oracle dequantizes rows in f32 first.
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_mq_dispatcher_env_gate(monkeypatch):
    """prefill_attention routes small-S bf16 shapes through the mq
    kernel (default ON since the round-3 chip validation; int8 stays
    behind XLLM_MQ_ATTENTION_KERNEL=1), and the result matches blockwise.
    D must satisfy the D % 128 == 0 gate or the branch is never taken."""
    from xllm_service_tpu.ops.attention import prefill_attention

    rng = np.random.default_rng(7)
    _, k, v, bt, seq_lens = make_case(rng, D=128)
    R, MB = bt.shape
    q = jnp.asarray(rng.standard_normal((R, 4, 8, 128)), jnp.float32)
    seq_lens = jnp.minimum(seq_lens, MB * 16 - 4)
    start_pos = jnp.maximum(seq_lens - 1, 0)
    true_len = jnp.where(seq_lens > 0, 4, 0)
    scale = 1.0 / np.sqrt(128)
    ref = prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, use_kernel=False
    )
    # Prove the mq branch actually runs: count entries into the kernel
    # (the dispatcher imports it at call time, so the spy is seen).
    calls = []
    from xllm_service_tpu.ops.pallas import paged_attention as pa_mod

    orig = pa_mod.multiquery_paged_attention_kernel

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(
        pa_mod, "multiquery_paged_attention_kernel", spy
    )
    monkeypatch.setenv("XLLM_MQ_ATTENTION_KERNEL", "1")
    out = prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, interpret=True
    )
    assert calls, "mq kernel branch was not taken"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    # bf16-default semantics: env UNSET still takes the mq branch...
    monkeypatch.delenv("XLLM_MQ_ATTENTION_KERNEL", raising=False)
    calls.clear()
    prefill_attention(q, k, v, bt, start_pos, true_len, scale, interpret=True)
    assert calls, "bf16 mq default-on regressed"
    # ...=0 disables it...
    monkeypatch.setenv("XLLM_MQ_ATTENTION_KERNEL", "0")
    calls.clear()
    prefill_attention(q, k, v, bt, start_pos, true_len, scale, interpret=True)
    assert not calls, "XLLM_MQ_ATTENTION_KERNEL=0 must disable the branch"
    # ...the function-wide kill switch covers the mq path too...
    monkeypatch.delenv("XLLM_MQ_ATTENTION_KERNEL", raising=False)
    monkeypatch.setenv("XLLM_PREFILL_ATTENTION_KERNEL", "0")
    calls.clear()
    prefill_attention(q, k, v, bt, start_pos, true_len, scale, interpret=True)
    assert not calls, "PREFILL=0 kill switch must cover the mq branch"
    monkeypatch.delenv("XLLM_PREFILL_ATTENTION_KERNEL", raising=False)
    # ...and int8 caches stay opt-in until mq-int8 chip-validates —
    # with a BS=128 cache so the tile gate itself is satisfied and the
    # decline is genuinely the int8 opt-in.
    from xllm_service_tpu.ops import kv_cache as kvc

    kb = jnp.asarray(rng.standard_normal((5, 2, 128, 128)), jnp.float32)
    vb = jnp.asarray(rng.standard_normal((5, 2, 128, 128)), jnp.float32)
    q8 = jnp.asarray(rng.standard_normal((2, 4, 4, 128)), jnp.float32)
    bt8 = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    sp8 = jnp.asarray([40, 90], jnp.int32)
    tl8 = jnp.asarray([4, 4], jnp.int32)
    calls.clear()
    prefill_attention(
        q8, kb, vb, bt8, sp8, tl8, scale, interpret=True
    )
    assert calls, "bf16 BS=128 control case should take the mq branch"
    calls.clear()
    prefill_attention(
        q8, kvc.quantize_pool(kb), kvc.quantize_pool(vb), bt8, sp8, tl8,
        scale, interpret=True,
    )
    assert not calls, "int8 mq must stay opt-in until chip-validated"


def test_mq_decode_kernel_table_edge_clamp():
    """true_len < S at the end of a sequence: the chunk walk must clamp to
    the table width (no out-of-bounds block-table reads), and rows below
    true_len stay exact — rows past it are garbage the sampler never emits."""
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    rng = np.random.default_rng(11)
    S = 4
    _, k, v, bt, _ = make_case(rng, R=2, MB=4, BS=16)
    q = jnp.asarray(rng.standard_normal((2, S, 8, 128)), jnp.float32)
    # seq 0 sits at the last table row: context for row 0 is the full
    # table; rows 1..3 would walk past it without the clamp.
    seq_lens = jnp.asarray([4 * 16, 30], jnp.int32)
    out = np.asarray(
        multiquery_paged_attention_kernel(
            q, k, v, bt, seq_lens, 0.125, interpret=True
        )
    )
    ref = np.asarray(_mq_oracle(q, k, v, bt, seq_lens, S, 0.125))
    # seq 0: only row 0 is a real query (true_len = 1 at max_seq_len).
    np.testing.assert_allclose(out[0, :1], ref[0, :1], atol=2e-5, rtol=2e-5)
    # seq 1 is far from the edge: all rows exact.
    np.testing.assert_allclose(out[1], ref[1], atol=2e-5, rtol=2e-5)


def _mla_mq_oracle(q, cache, bt, seq_lens, S, scale, kvr):
    from xllm_service_tpu.ops.attention import mla_prefill_attention

    start_pos = jnp.maximum(seq_lens - 1, 0)
    true_len = jnp.where(seq_lens > 0, S, 0)
    return mla_prefill_attention(
        q, cache, bt, start_pos, true_len, scale, kvr, use_kernel=False
    )


@pytest.mark.parametrize("S", [2, 4])
def test_mla_mq_kernel_matches_blockwise(S):
    from xllm_service_tpu.ops.pallas.mla_attention import (
        mla_multiquery_attention_kernel,
    )

    rng = np.random.default_rng(0)
    kvr = 40
    q4, cache, bt = make_mla_prefill_case(rng, P=3, Lpad=S, C=128, MB=8)
    R, MB = bt.shape
    BS = cache.shape[2]
    seq_lens = jnp.asarray([1, 60, MB * BS - S], jnp.int32)
    scale = 0.125
    ref = _mla_mq_oracle(q4, cache, bt, seq_lens, S, scale, kvr)
    out = mla_multiquery_attention_kernel(
        q4, cache, bt, seq_lens, scale, kvr, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
    )


def test_mla_mq_kernel_inactive_and_clamp():
    from xllm_service_tpu.ops.pallas.mla_attention import (
        mla_multiquery_attention_kernel,
    )

    rng = np.random.default_rng(2)
    S, kvr = 4, 40
    q4, cache, bt = make_mla_prefill_case(rng, P=3, Lpad=S, C=128, MB=4)
    BS = cache.shape[2]
    # slot 0 inactive; slot 2 at the very end of its table (clamp path)
    seq_lens = jnp.asarray([0, 17, 4 * BS], jnp.int32)
    out = np.asarray(
        mla_multiquery_attention_kernel(
            q4, cache, bt, seq_lens, 0.125, kvr, interpret=True
        )
    )
    ref = np.asarray(_mla_mq_oracle(q4, cache, bt, seq_lens, S, 0.125, kvr))
    assert np.all(out[0] == 0)
    np.testing.assert_allclose(out[1], ref[1], atol=3e-5, rtol=3e-5)
    # seq 2: only row 0 is real past the table end
    np.testing.assert_allclose(out[2, :1], ref[2, :1], atol=3e-5, rtol=3e-5)


def test_mla_mq_dispatcher_env_gate(monkeypatch):
    from xllm_service_tpu.ops.attention import mla_prefill_attention
    from xllm_service_tpu.ops.pallas import mla_attention as mla_mod

    rng = np.random.default_rng(5)
    S, kvr = 4, 40
    # C=128: the dispatcher's tile-legality gate (attention._kernel_tile_ok)
    # requires a 128-multiple latent lane dim, as the production pool pads.
    q4, cache, bt = make_mla_prefill_case(rng, P=2, Lpad=S, C=128, MB=8)
    seq_lens = jnp.asarray([30, 90], jnp.int32)
    start_pos = jnp.maximum(seq_lens - 1, 0)
    true_len = jnp.full((2,), S, jnp.int32)
    ref = mla_prefill_attention(
        q4, cache, bt, start_pos, true_len, 0.125, kvr, use_kernel=False
    )
    calls = []
    orig = mla_mod.mla_multiquery_attention_kernel

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(mla_mod, "mla_multiquery_attention_kernel", spy)
    monkeypatch.setenv("XLLM_MQ_ATTENTION_KERNEL", "1")
    out = mla_prefill_attention(
        q4, cache, bt, start_pos, true_len, 0.125, kvr, interpret=True
    )
    assert calls, "mla mq kernel branch was not taken"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
    )


def _quantize_mla_cache(cache, kvr, dr):
    from xllm_service_tpu.ops import kv_cache as kvc

    G = kvc.mla_scale_groups(kvr, dr, cache.shape[-1])
    return kvc.quantize_pool(cache, G)


def test_mla_kernel_int8_matches_gather():
    """Int8 latent cache through the MLA decode kernel: sub-channel
    scales stream in their own plane and dequantize in VMEM; parity vs
    the gather oracle on the SAME quantized cache."""
    from xllm_service_tpu.ops.attention import mla_paged_attention_gather
    from xllm_service_tpu.ops.pallas.mla_attention import (
        mla_attention_kernel,
    )

    rng = np.random.default_rng(9)
    kvr, dr = 40, 16  # C = 128 lane-padded, 16 scale groups
    q, cache, bt = make_mla_prefill_case(rng, P=3, Lpad=1, C=128, BS=128, MB=2, num_blocks=16)
    q = q[:, 0]  # [R, Hq, C]
    qc = _quantize_mla_cache(cache, kvr, dr)
    seq_lens = jnp.asarray([1, 60, 128], jnp.int32)
    ref = mla_paged_attention_gather(q, qc, bt, seq_lens, 0.125, kvr)
    out = mla_attention_kernel(
        q, qc, bt, seq_lens, 0.125, kvr, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_mla_mq_kernel_int8_matches_blockwise():
    from xllm_service_tpu.ops.pallas.mla_attention import (
        mla_multiquery_attention_kernel,
    )

    rng = np.random.default_rng(10)
    S, kvr, dr = 3, 40, 16
    q4, cache, bt = make_mla_prefill_case(rng, P=3, Lpad=S, C=128, BS=128, MB=2, num_blocks=16)
    qc = _quantize_mla_cache(cache, kvr, dr)
    BS = cache.shape[2]
    seq_lens = jnp.asarray([1, 60, 2 * BS - S], jnp.int32)  # MB=2 table
    ref = _mla_mq_oracle(q4, qc, bt, seq_lens, S, 0.125, kvr)
    out = mla_multiquery_attention_kernel(
        q4, qc, bt, seq_lens, 0.125, kvr, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_mla_dispatcher_int8_kernel_branch(monkeypatch):
    """mla_paged_attention with the kernel forced on an int8 cache must
    route to the kernel (not silently fall back) and match the gather."""
    from xllm_service_tpu.ops.attention import mla_paged_attention
    from xllm_service_tpu.ops.pallas import mla_attention as mla_mod

    rng = np.random.default_rng(11)
    kvr, dr = 40, 16
    q, cache, bt = make_mla_prefill_case(rng, P=2, Lpad=1, C=128, BS=128, MB=2, num_blocks=16)
    q = q[:, 0]
    qc = _quantize_mla_cache(cache, kvr, dr)
    seq_lens = jnp.asarray([20, 50], jnp.int32)
    ref = mla_paged_attention(
        q, qc, bt, seq_lens, 0.125, kvr, use_kernel=False
    )
    calls = []
    orig = mla_mod.mla_attention_kernel

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(mla_mod, "mla_attention_kernel", spy)
    out = mla_paged_attention(
        q, qc, bt, seq_lens, 0.125, kvr, use_kernel=True, interpret=True
    )
    assert calls, "int8 mla kernel branch was not taken"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_mla_flash_prefill_int8_matches_blockwise():
    """Int8 latent cache through the MLA flash-prefill kernel (scale
    plane + VMEM dequant) vs the blockwise oracle on the SAME quantized
    cache."""
    from xllm_service_tpu.ops.attention import mla_prefill_attention
    from xllm_service_tpu.ops.pallas.mla_prefill import (
        mla_flash_prefill_kernel,
    )

    rng = np.random.default_rng(13)
    kvr, dr = 40, 16
    q, cache, bt = make_mla_prefill_case(
        rng, P=2, Lpad=32, C=128, BS=128, MB=2, num_blocks=16
    )
    qc = _quantize_mla_cache(cache, kvr, dr)
    start_pos = jnp.asarray([0, 8], jnp.int32)
    true_len = jnp.asarray([32, 17], jnp.int32)
    ref = mla_prefill_attention(
        q, qc, bt, start_pos, true_len, 0.125, kvr, use_kernel=False
    )
    out = mla_flash_prefill_kernel(
        q, qc, bt, start_pos, true_len, 0.125, kvr, interpret=True,
        tile_q=16,
    )
    for p, tl in enumerate([32, 17]):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=2e-2, rtol=2e-2,
        )


# ---------------------------- MLA flash prefill, the materialised form


def make_mla_materialised_case(
    rng, P=2, Lpad=32, Hq=8, dn=24, dr=16, dv=20, kvr=40, C=128, BS=16,
    MB=8, num_blocks=64, layers=None,
):
    """Un-absorbed queries, a layer's W_UK / W_UV and a latent pool whose
    rows are zero past kvr + dr, as the model writes them; `layers`: the
    stacked pool."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    # the heads as the projection writes them: [q_nope | rope part, not roped]
    q = f32(rng.standard_normal((P, Lpad, Hq, dn + dr)))
    q_pe = f32(rng.standard_normal((P, Lpad, Hq, dr)))
    w_uk = f32(rng.standard_normal((Hq, kvr, dn)) / np.sqrt(kvr))
    w_uv = f32(rng.standard_normal((Hq, kvr, dv)) / np.sqrt(kvr))
    shape = (num_blocks, 1, BS, C)
    cache = rng.standard_normal(shape if layers is None else (layers,) + shape)
    cache[..., kvr + dr:] = 0.0
    bt = np.stack([
        rng.choice(np.arange(1, num_blocks), size=MB, replace=False)
        for _ in range(P)
    ]).astype(np.int32)
    return q, q_pe, w_uk, w_uv, f32(cache), jnp.asarray(bt)


def _mla_absorbed_ref(q, q_pe, w_uk, w_uv, cache, bt, start_pos,
                      true_len, scale, kvr, layer=None):
    """The oracle: the blockwise scan over the absorbed queries, W_UV
    after it (the heads' value-space outputs)."""
    q_nope = q[..., :w_uk.shape[-1]]
    q_lat = jnp.concatenate(
        [jnp.einsum("plhd,hkd->plhk", q_nope, w_uk), q_pe], axis=-1
    )
    q_lat = jnp.pad(
        q_lat, ((0, 0),) * 3 + ((0, cache.shape[-1] - q_lat.shape[-1]),)
    )
    ctx = jax.vmap(
        lambda qi, ti, sp, tl: mla_prefill_blockwise(
            qi, cache, ti, sp, tl, scale, kvr, layer=layer
        )
    )(q_lat, bt, start_pos, true_len)
    return jnp.einsum("plhk,hkv->plhv", ctx, w_uv)


@pytest.mark.parametrize("head_group,tile_q", [(2, 16), (8, 32)])
@pytest.mark.parametrize(
    "starts,lens,layer",
    [
        ((0, 0), (32, 17), None),       # no cached token; a ragged chunk
        ((21, 37), (32, 20), None),     # not a multiple of the block
        ((32, 64), (32, 32), 2),        # whole blocks; the stacked pool
        ((96, 96), (32, 9), None),      # the end of the largest bucket
        ((16, 48), (32, 0), 1),         # a pad row (true_len 0), stacked
    ],
)
def test_mla_materialised_prefill_matches_blockwise(
    starts, lens, layer, head_group, tile_q
):
    """The materialised flash kernel (keys and values made from the
    latent blocks in VMEM) against the blockwise scan over the absorbed
    queries: the two forms are one algebra."""
    from xllm_service_tpu.ops.pallas.mla_prefill import (
        mla_materialised_prefill_kernel,
    )

    rng = np.random.default_rng(55)
    kvr = 40
    case = make_mla_materialised_case(
        rng, kvr=kvr, layers=None if layer is None else 3
    )
    start_pos = jnp.asarray(starts, jnp.int32)
    true_len = jnp.asarray(lens, jnp.int32)
    ref = _mla_absorbed_ref(
        *case, start_pos, true_len, 0.125, kvr, layer=layer
    )
    out = mla_materialised_prefill_kernel(
        *case, start_pos, true_len, 0.125, kvr, interpret=True, chunk=2,
        tile_q=tile_q, head_group=head_group, layer=layer,
    )
    assert out.shape == ref.shape
    for p, tl in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(out)[p, :tl], np.asarray(ref)[p, :tl],
            atol=3e-5, rtol=3e-5,
        )
        assert not np.asarray(out)[p, tl:].any()  # rows past true_len: zeros


def test_mla_materialised_prefill_dispatcher_in_bf16():
    """ops.attention's dispatcher of the form, over a bfloat16 pool as the
    routes require: within the rounding of the keys and values it makes."""
    from xllm_service_tpu.ops.attention import (
        mla_materialised_prefill_attention,
    )

    rng = np.random.default_rng(56)
    kvr = 40
    case = make_mla_materialised_case(rng, kvr=kvr)
    start_pos = jnp.asarray([8, 40], jnp.int32)
    true_len = jnp.asarray([32, 25], jnp.int32)
    ref = _mla_absorbed_ref(*case, start_pos, true_len, 0.125, kvr)
    *ops, bt = case
    out = mla_materialised_prefill_attention(
        *(a.astype(jnp.bfloat16) for a in ops), bt, start_pos, true_len,
        0.125, kvr, interpret=True,
    )
    assert out.dtype == jnp.bfloat16
    for p, tl in enumerate([32, 25]):
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32))[p, :tl],
            np.asarray(ref)[p, :tl], atol=6e-2, rtol=6e-2,
        )


# ------------------------------------------------ Mosaic layout rules


def test_mosaic_rules_reject_known_bad_layouts():
    """The trace-time layout validator (ops/pallas/mosaic_rules) rejects
    every layout class that passed interpret mode and failed on silicon
    (round 2/3 chip findings); kernels route all DMAs through it, so the
    interpret suites above double as layout-legality checks."""
    import pytest as _pytest

    from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic

    # Round-2 flat scale plane: [1, BS*G] slice = 1 sublane row.
    with _pytest.raises(mosaic.MosaicLayoutError, match="sublane"):
        mosaic.check_copy_shape((1, 16 * 8), jnp.float32, "flat scale row")
    # Round-2 alternative [.., BS, G]: G=8 lanes.
    with _pytest.raises(mosaic.MosaicLayoutError, match="lane"):
        mosaic.check_copy_shape((128, 8), jnp.float32, "scale tile")
    # Round-3 unpadded MLA latent row: 576 lanes.
    with _pytest.raises(mosaic.MosaicLayoutError, match="lane"):
        mosaic.check_copy_shape((1, 1, 128, 576), jnp.bfloat16, "latent")
    # Current layouts pass: packed GQA row, grouped scale tile, padded
    # MLA latent.
    mosaic.check_copy_shape((128, 128), jnp.bfloat16)
    mosaic.check_copy_shape((8, 128), jnp.float32)
    mosaic.check_copy_shape((1, 128, 640), jnp.bfloat16)


def test_mosaic_rules_dynamic_offset_placement():
    """Rule 2: dynamic offsets only on untiled leading dims."""
    import pytest as _pytest

    from jax.experimental import pallas as _pl
    from xllm_service_tpu.ops.pallas import mosaic_rules as mosaic

    class FakeTracer:  # anything that isn't a python int is dynamic
        pass

    blk = FakeTracer()
    # [N, H, BS, D] cache: block id + head on leading dims — legal.
    mosaic.check_slice_indices(4, (blk, 1))
    # Static pl.ds on a tiled dim — legal.
    mosaic.check_slice_indices(3, (blk, _pl.ds(0, 128)))
    # Dynamic offset on the sublane dim — the round-2 failure mode.
    with _pytest.raises(mosaic.MosaicLayoutError, match="dynamic"):
        mosaic.check_slice_indices(2, (blk,))
    with _pytest.raises(mosaic.MosaicLayoutError, match="dynamic"):
        mosaic.check_slice_indices(4, (0, 1, blk))


# ------------------------------------------------------------ stacked pool
# The serving steps hand every GQA kernel the WHOLE stacked pool
# [L, N, Hc, BS, D] plus a layer index (models/llama.py carries the stack
# through its layer scan; docs/KV_CACHE.md). Each kernel must read exactly
# that layer: the other layers of the stack hold different data, so a
# wrong or ignored index cannot pass.

from xllm_service_tpu.ops import attention as attn_ops
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops import kv_write as kvw

_STACK_L = 3


def _stacked_case(rng, cache_kind, Hq=4, Hkv=2, R=2, MB=2, N=10):
    """(q_head_dim, BS, k_stack, v_stack, tables): a pool of _STACK_L
    layers of independent random data in one of the three cache layouts."""
    if cache_kind == "packed64":
        D, BS = 64, 16
        shape = (_STACK_L, N, Hkv // 2, BS, 128)  # two heads per 128 lanes
    else:
        D, BS = 128, (128 if cache_kind == "int8" else 16)
        shape = (_STACK_L, N, Hkv, BS, D)
    mk = lambda: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if cache_kind == "int8":
        k, v = kvc.quantize_pool(mk()), kvc.quantize_pool(mk())
    else:
        k, v = mk().astype(jnp.bfloat16), mk().astype(jnp.bfloat16)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, N))[: R * MB].reshape(R, MB), jnp.int32
    )
    return D, BS, k, v, bt


def _layer_of(cache, layer):
    return jax.tree.map(lambda a: a[layer], cache)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("cache_kind", ["bf16", "int8", "packed64"])
@pytest.mark.parametrize("kernel", ["decode", "multiquery", "flash", "mixed"])
def test_stacked_kernels_read_their_layer(kernel, cache_kind, layer, monkeypatch):
    monkeypatch.setenv("XLLM_PACKED_KV_KERNEL", "1")
    monkeypatch.setenv("XLLM_MQ_ATTENTION_KERNEL", "1")
    rng = np.random.default_rng(17)
    Hq, R = 4, 2
    D, BS, k, v, bt = _stacked_case(rng, cache_kind, Hq=Hq, R=R)
    k4, v4 = _layer_of(k, layer), _layer_of(v, layer)
    scale = D ** -0.5
    lyr = jnp.int32(layer)
    ctx = bt.shape[1] * BS

    def q_of(*lead):
        return jnp.asarray(
            rng.standard_normal((*lead, Hq, D)), jnp.bfloat16
        )

    if kernel == "decode":
        q = q_of(R)
        seq_lens = jnp.asarray([ctx - 3, BS // 2 + 1], jnp.int32)
        out = attn_ops.paged_attention(
            q, k, v, bt, seq_lens, scale, use_kernel=True, interpret=True,
            layer=lyr,
        )
        ref = attn_ops.paged_attention_gather(q, k4, v4, bt, seq_lens, scale)
    elif kernel in ("multiquery", "flash"):
        S = 4 if kernel == "multiquery" else 16
        q = q_of(R, S)
        start = jnp.asarray([ctx - S - 2, 3], jnp.int32)
        true_len = jnp.asarray([S, S], jnp.int32)
        out = attn_ops.prefill_attention(
            q, k, v, bt, start, true_len, scale, interpret=True, layer=lyr,
            use_kernel=None if kernel == "multiquery" else True,
        )
        ref = jax.vmap(
            lambda qi, ti, sp, tl: attn_ops.prefill_attention_gather(
                qi, k4, v4, ti, sp, tl, scale
            )
        )(q, bt, start, true_len)
    else:
        # decode rows and chunks in one mixed step: the pair of kernels side
        # by side over the same stack (interpret mode through the seam)
        monkeypatch.setattr(attn_ops, "_interpret", lambda: True)
        q_dec, q_pf = q_of(R), q_of(R, 16)
        seq_lens = jnp.asarray([ctx - 3, BS // 2 + 1], jnp.int32)
        start = jnp.asarray([ctx - 18, 2], jnp.int32)
        true_len = jnp.asarray([16, 13], jnp.int32)
        out = attn_ops.mixed_attention(
            q_dec, q_pf, k, v, bt, seq_lens, bt, start, true_len, scale, layer=lyr
        )
        ref = (
            attn_ops.paged_attention_gather(q_dec, k4, v4, bt, seq_lens, scale),
            jax.vmap(
                lambda qi, ti, sp, tl: attn_ops.prefill_attention_gather(
                    qi, k4, v4, ti, sp, tl, scale
                )
            )(q_pf, bt, start, true_len),
        )
        live = (jnp.arange(16)[None, :] < true_len[:, None])[:, :, None, None]
        out, ref = (
            jnp.concatenate([d.reshape(-1), jnp.where(live, p, 0).reshape(-1)])
            for d, p in (out, ref)
        )
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)


def test_stacked_fallbacks_index_the_stack():
    """The gather / blockwise fallbacks take the same (stack, layer)
    operands and gather blocks out of the stack: same numbers as on the
    layer sliced out by hand."""
    rng = np.random.default_rng(5)
    D, BS, k, v, bt = _stacked_case(rng, "int8")
    q = jnp.asarray(rng.standard_normal((2, 4, D)), jnp.float32)
    sl = jnp.asarray([BS + 9, 4], jnp.int32)
    for layer in range(_STACK_L):
        a = attn_ops.paged_attention_gather(
            q, k, v, bt, sl, 0.1, layer=jnp.int32(layer)
        )
        b = attn_ops.paged_attention_gather(
            q, _layer_of(k, layer), _layer_of(v, layer), bt, sl, 0.1
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        a = attn_ops.prefill_attention_blockwise(
            q, k, v, bt[0], jnp.int32(7), jnp.int32(2), 0.1,
            layer=jnp.int32(layer),
        )
        b = attn_ops.prefill_attention_blockwise(
            q, _layer_of(k, layer), _layer_of(v, layer), bt[0],
            jnp.int32(7), jnp.int32(2), 0.1,
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- the in-place write
# ops/kv_write.py: both routes (the XLA scatter into the carried stack, and
# the Pallas tile write of ops/pallas/kv_write.py that replaces it on the
# chip) against rows placed one by one in numpy: the right layer, block,
# offset and scale lane, and nothing else touched. Garbage block 0 is left
# out: the scatter parks dead rows there, the kernel writes nothing.


def _write_case(rng, cache_kind, S, CB=4, N=12, Hc=2, D=128):
    BS = 128 if cache_kind == "int8" else 32
    base = jnp.asarray(
        rng.standard_normal((_STACK_L, N, Hc, BS, D)), jnp.float32
    )
    if cache_kind == "int8":
        mk = kvc.quantize_pool
    else:
        dt = jnp.bfloat16 if cache_kind == "bf16" else jnp.float32
        mk = lambda a: kvc.PagedKV(a.astype(dt), None)
    tables = rng.permutation(np.arange(1, N))[: S * 2].reshape(S, 2)
    tables = np.concatenate([tables, np.zeros_like(tables)], 1)[:, :CB]
    return BS, mk(base), mk(base * 0.5), jnp.asarray(tables, jnp.int32)


def _placed(cache, rows, tables, start, length, width, layer):
    """The oracle: `cache` with each live row put in its slot by hand."""
    data = np.array(cache.data)
    scale = None if cache.scale is None else np.array(cache.scale)
    BS = data.shape[-2]
    if scale is None:
        new = np.asarray(rows.astype(cache.data.dtype))
    else:
        # jitted like the write: eager rounds a few values differently
        new, new_scale = map(
            np.asarray,
            jax.jit(kvc.quantize_rows, static_argnums=1)(
                rows, scale.shape[-2]
            ),
        )
    for s in range(len(start)):
        for j in range(int(length[s])):
            pos = int(start[s]) + j
            blk, off = int(tables[s, pos // BS]), pos % BS
            data[layer, blk, :, off] = new[s * width + j]
            if scale is not None:
                scale[layer, blk, :, :, off] = new_scale[s * width + j]
    return data, scale


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize(
    "width,start,length",
    [
        (1, [3, 40, 0, 31], [1, 1, 0, 1]),  # decode rows, one inactive
        (4, [30, 5, 0, 17], [4, 2, 0, 4]),  # verify rows across a block edge
        (40, [7, 32, 0, 1], [40, 17, 0, 33]),  # chunks, unaligned starts
    ],
)
@pytest.mark.parametrize("cache_kind", ["bf16", "int8", "f32"])
@pytest.mark.parametrize("route", ["scatter", "kernel"])
def test_write_kv_lands_rows_in_the_stack(
    route, cache_kind, width, start, length, layer
):
    rng = np.random.default_rng(width)
    S = len(start)
    BS, kc, vc, tables = _write_case(rng, cache_kind, S)
    scale_up = BS // 32  # same block crossings at BS 128 as at BS 32
    start = np.asarray(start, np.int32) * scale_up
    length = np.asarray(length, np.int32)
    rows = jnp.asarray(
        rng.standard_normal((S * width, 2, 128)),
        jnp.float32 if cache_kind == "f32" else jnp.bfloat16,
    )
    plan = kvw.write_plan(
        kc, tables, jnp.asarray(start), jnp.asarray(length), width,
        interpret=route == "kernel",
    )
    assert (plan.units is not None) == (route == "kernel")
    assert (plan.scale_units is not None) == (
        route == "kernel" and cache_kind == "int8"
    )
    out = jax.jit(
        lambda k, v: kvw.write_kv(k, v, plan, rows, rows * 2, jnp.int32(layer))
    )(kc, vc)
    np_tables = np.asarray(tables)
    for cache, new, got in zip((kc, vc), (rows, rows * 2), out):
        data, scale = _placed(
            cache, new, np_tables, start, length, width, layer
        )
        np.testing.assert_array_equal(np.asarray(got.data)[:, 1:], data[:, 1:])
        assert not np.array_equal(data[layer], np.asarray(cache.data)[layer])
        if scale is not None:
            np.testing.assert_array_equal(
                np.asarray(got.scale)[:, 1:], scale[:, 1:]
            )
        if route == "kernel":  # dead units write nothing, not even garbage
            np.testing.assert_array_equal(
                np.asarray(got.data)[:, 0], np.asarray(cache.data)[:, 0]
            )


def _fresh(kernel, static=("scale", "interpret", "chunk", "window")):
    """A jit of its own over a copy of a kernel's wrapper: what a test has
    patched in the kernel's module is traced, not served from the trace an
    earlier test left (jit keeps its traces by the function under it, so
    the copy, not the wrapper's own function, goes under this one)."""
    import functools

    inner = kernel.__wrapped__

    @functools.wraps(inner)
    def again(*args, **kwargs):
        return inner(*args, **kwargs)

    return jax.jit(again, static_argnames=static)


_LIVE = {
    "none": [0, 0, 0, 0, 0, 0],
    "one-last": [0, 0, 0, 0, 0, 1],
    "interleaved": [0, 1, 0, 1, 1, 0],
    "all": [1, 1, 1, 1, 1, 1],
}


def _live_case(cache_kind, width, pattern, seed=5):
    """Six slots, those `_LIVE[pattern]` marks writing `width` rows (the
    chunk plans: a whole chunk, a part of one, or one row) from unaligned
    starts; the rows nobody writes are NaN."""
    rng = np.random.default_rng(seed)
    live = np.asarray(_LIVE[pattern], np.int32)
    S = len(live)
    BS, kc, vc, tables = _write_case(rng, cache_kind, S, N=2 * S + 1)
    start = np.asarray([3, 40, 7, 31, 17, 5], np.int32) * (BS // 32)
    length = live * np.asarray([width, 1, width, max(width // 2, 1), width, 1])
    length = np.minimum(length, width).astype(np.int32)
    rows = rng.standard_normal((S, width, 2, 128)).astype(np.float32)
    rows[np.arange(width)[None, :] >= length[:, None]] = np.nan
    rows = jnp.asarray(rows.reshape(S * width, 2, 128), jnp.bfloat16)
    return kc, vc, tables, jnp.asarray(start), jnp.asarray(length), rows


@pytest.mark.parametrize("pattern", list(_LIVE))
@pytest.mark.parametrize("width", [1, 40], ids=["decode", "chunk"])
@pytest.mark.parametrize("cache_kind", ["bf16", "int8"])
def test_write_kv_follows_the_live_rows(cache_kind, width, pattern):
    """The launch walks the live units alone (they stand first in the
    plan and take their new rows through `order`): whichever slots are
    live, the pools equal the scatter route's outside garbage block 0 and
    are finite EVERYWHERE, though every row nobody writes is NaN (the
    scatter parks those in block 0; the kernel writes nothing there)."""
    kc, vc, tables, start, length, rows = _live_case(cache_kind, width, pattern)
    write = lambda plan: jax.jit(
        lambda k, v: kvw.write_kv(k, v, plan, rows, rows * 2, jnp.int32(1))
    )(kc, vc)
    plan = kvw.write_plan(kc, tables, start, length, width, interpret=True)
    units = plan.units
    assert int(units.live) == int(np.sum(np.asarray(units.hi > units.lo)))
    assert np.all(np.asarray(units.hi > units.lo)[: int(units.live)])
    assert sorted(np.asarray(units.order)) == list(range(len(units.order)))
    got = write(plan)
    want = write(kvw.write_plan(kc, tables, start, length, width))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:], np.asarray(w)[:, 1:])
        assert np.all(np.isfinite(np.asarray(g, np.float32)))
    if pattern != "none":
        assert not np.array_equal(np.asarray(got[0].data), np.asarray(kc.data))


def _tallied(monkeypatch, module, body_name):
    """Patch a kernel's body to note each grid step it runs at (interpret
    mode: `jax.debug.callback` inside the interpreted grid loop); returns
    the list the steps land in."""
    from jax.experimental import pallas as pl

    steps, body = [], getattr(module, body_name)

    def counted(*refs, **kw):
        jax.debug.callback(lambda i: steps.append(int(i)), pl.program_id(0))
        return body(*refs, **kw)

    monkeypatch.setattr(module, body_name, counted)
    return steps


@pytest.mark.parametrize("pattern", list(_LIVE))
def test_kv_write_grid_is_the_live_units(pattern, monkeypatch):
    """The work follows the rows: the launch runs one grid step a LIVE
    unit (one, and it dead, where nothing is live), not one a slot."""
    from xllm_service_tpu.ops.pallas import kv_write as kvp

    steps = _tallied(monkeypatch, kvp, "_kv_write_kernel")
    monkeypatch.setattr(kvw, "kv_write_kernel", _fresh(
        kvp.kv_write_kernel, ("tile", "axis", "interpret")
    ))
    kc, vc, tables, start, length, rows = _live_case("bf16", 1, pattern)
    plan = kvw.write_plan(kc, tables, start, length, 1, interpret=True)
    jax.block_until_ready(
        kvw.write_kv(kc, vc, plan, rows, rows, jnp.int32(0))
    )
    jax.effects_barrier()
    live = sum(_LIVE[pattern])
    assert sorted(steps) == list(range(max(live, 1)))


@pytest.mark.parametrize("rows_a_block", [None, 2], ids=["one-block", "blocks-of-2"])
@pytest.mark.parametrize("pattern", ["every-order", "all-dead", "one-live", "edges"])
def test_decode_grid_is_the_live_rows(pattern, rows_a_block, monkeypatch):
    """The decode kernel's row axis runs a grid step a LIVE row, and one
    for the first row of each block of rows where that row is dead (the
    block's zeros)."""
    from xllm_service_tpu.ops.pallas import paged_attention as pa

    steps = _tallied(monkeypatch, pa, "_decode_kernel")
    if rows_a_block:
        monkeypatch.setattr(pa, "_row_block", lambda rows, _: rows_a_block)
    run, _, lens, _, (k, v), _ = _schedule_case("decode", pattern, fresh=True)
    jax.block_until_ready(run(k, v))
    jax.effects_barrier()
    first = np.arange(len(lens)) % (rows_a_block or len(lens)) == 0
    visits = int(((lens > 0) | first).sum())
    # (Hkv 2: both KV heads ride one grid step, so a row is one step)
    assert sorted(steps) == list(range(visits))
    assert visits < len(lens) or pattern == "edges"


def test_kv_write_kernel_per_shard():
    """Under a tp shard context the write launches once per shard over its
    own heads (shard_map), like the attention kernels."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(9)
    BS, kc, vc, tables = _write_case(rng, "bf16", 2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    start, length = jnp.asarray([5, 30]), jnp.asarray([1, 1])
    rows = jnp.asarray(rng.standard_normal((2, 2, 128)), jnp.bfloat16)
    ref = kvw.write_kv(
        kc, vc, kvw.write_plan(kc, tables, start, length, 1), rows, rows,
        jnp.int32(1),
    )
    attn_ops.set_shard_context(mesh)
    try:
        plan = kvw.write_plan(kc, tables, start, length, 1, interpret=True)
        assert plan.units is not None and plan.ctx is not None
        pool = NamedSharding(mesh, P(None, None, "tp"))
        with mesh:
            out = jax.jit(
                lambda k, v: kvw.write_kv(k, v, plan, rows, rows, jnp.int32(1)),
            )(jax.device_put(kc, pool), jax.device_put(vc, pool))
    finally:
        attn_ops.set_shard_context(None)
    for r, o in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


# ------------------------------------- the decode kernel's DMA schedule
# paged_attention.py fetches a row's live blocks only and hands its DMA
# pipeline from one live (row, KV head) grid step to the next (slot parity
# and "first chunk in flight" in SMEM, the successor through `next_live`).
# The patterns below put dead, one-chunk and many-chunk steps next to one
# another in every order, for every variant of the one body.

from xllm_service_tpu.ops.pallas.paged_attention import (
    multiquery_paged_attention_kernel,
)

# z dead, a 1, b BS, c C*BS-1, d C*BS, e C*BS+1, f the table's full width
_HANDOVER = {
    # a de Bruijn walk over {dead, one chunk, many chunks}: all nine pairs
    "every-order": "zzazebcfez",
    "dead-first": "zzzade",
    "dead-last": "deazzz",
    "interleaved": "zbzczfza",
    "runs": "aazzzeezzd",
    "all-dead": "zzzz",
    "one-live": "zzfz",
    "edges": "abcdef",
}
_MQ_S = 4


def _schedule_case(variant, pattern, seed=0, fresh=False):
    """(run, oracle, seq_lens, reached, pools): `run(k, v)` launches the
    variant's kernel in interpret mode (`fresh`: traced anew), `oracle()`
    is its plain twin on the clean pools, `reached[n]` says whether any
    query of the case can see block n. Tables are whole (distinct blocks
    in every column, so the columns past a row's context point at blocks
    nobody reaches)."""
    rng = np.random.default_rng(seed)
    int8 = variant == "int8"
    BS, C, MB = (128, 2, 5) if int8 else (16, 4, 10)  # MB % C != 0
    Hq, Hkv, D = 4, 2, 128
    sym = {"z": 0, "a": 1, "b": BS, "c": C * BS - 1, "d": C * BS,
           "e": C * BS + 1, "f": MB * BS}
    lens = np.asarray([sym[s] for s in _HANDOVER[pattern]], np.int32)
    R = len(lens)
    N = R * MB + 1
    S = _MQ_S if variant == "multiquery" else 1
    window = BS + 3 if variant == "window" else 0
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k, v = mk(N, Hkv, BS, D), mk(N, Hkv, BS, D)
    if int8:
        k, v = kvc.quantize_pool(k), kvc.quantize_pool(v)
    bt = 1 + rng.permutation(N - 1).reshape(R, MB).astype(np.int32)
    q = mk(R, S, Hq, D) if S > 1 else mk(R, Hq, D)
    scale = D ** -0.5
    seq_lens, table = jnp.asarray(lens), jnp.asarray(bt)

    reached = np.zeros(N, bool)
    for r, n in enumerate(lens):
        if n:
            hi = min(-(-(n + S - 1) // BS), MB)
            lo = max(n - window, 0) // BS if window else 0
            reached[bt[r, lo:hi]] = True

    launch = multiquery_paged_attention_kernel if S > 1 else paged_attention_kernel
    if fresh:
        launch = _fresh(launch)
    if S > 1:
        run = lambda k_, v_: launch(
            q, k_, v_, table, seq_lens, scale, interpret=True, chunk=C
        )
        oracle = lambda: _mq_oracle(q, k, v, table, seq_lens, S, scale)
    else:
        run = lambda k_, v_: launch(
            q, k_, v_, table, seq_lens, scale, interpret=True, chunk=C,
            window=window,
        )
        oracle = lambda: paged_attention_gather(
            q, k, v, table, seq_lens, scale, window=window
        )
    return run, oracle, lens, reached, (k, v), (S, BS * MB)


@pytest.mark.parametrize("pattern", list(_HANDOVER))
@pytest.mark.parametrize("variant", ["decode", "multiquery", "int8", "window"])
@pytest.mark.parametrize("rows_a_block", [None, 2], ids=["one-block", "blocks-of-2"])
def test_decode_schedule_hands_over(variant, pattern, rows_a_block, monkeypatch):
    """Every pattern with the rows' q and o tiles in ONE block (these
    shapes' own) and in blocks of two rows (every pattern has an even
    number), as rows wider than `_row_block`'s budget are cut (mimo's
    window layers: 16 of 64): the grid walks the live rows and each
    block's first row, so a block with no live row, first, last or in
    between, is visited by that one dead row, for its zeros alone."""
    if rows_a_block:
        from xllm_service_tpu.ops.pallas import paged_attention as pa

        monkeypatch.setattr(pa, "_row_block", lambda rows, _: rows_a_block)
    run, oracle, lens, _, (k, v), (S, width) = _schedule_case(
        variant, pattern, fresh=bool(rows_a_block)
    )
    out = np.asarray(run(k, v), np.float32)
    ref = np.asarray(oracle(), np.float32)
    tol = 2e-2 if variant == "int8" else 3e-5
    for r, n in enumerate(lens):
        if n == 0:
            assert np.all(out[r] == 0)
        elif S > 1:
            # the table-edge clamp: query rows past the table are garbage
            # the sampler never emits
            real = min(S, width - n + 1)
            np.testing.assert_allclose(
                out[r, :real], ref[r, :real], atol=tol, rtol=tol
            )
        else:
            np.testing.assert_allclose(out[r], ref[r], atol=tol, rtol=tol)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("pattern", ["every-order", "edges"])
@pytest.mark.parametrize("variant", ["decode", "multiquery", "int8", "window"])
def test_decode_kernel_never_reads_a_dead_block(variant, pattern):
    """NaN in garbage block 0 and in every block no query can see changes
    no output and leaves every output finite: such a block is not fetched,
    and what a slot holds in its place is finite (p is exactly 0 there, but
    0 * NaN is NaN in p @ v). An int8 pool takes the NaN in its scales."""
    run, _, _, reached, (k, v), _ = _schedule_case(variant, pattern, seed=1)
    dead = jnp.asarray(~reached)  # block 0 is in no table

    def poison(cache):
        if variant == "int8":
            return kvc.PagedKV(
                jnp.where(dead[:, None, None, None], 127, cache.data),
                jnp.where(dead[:, None, None, None], jnp.nan, cache.scale),
            )
        return jnp.where(dead[:, None, None, None], jnp.nan, cache)

    clean = np.asarray(run(k, v))
    dirty = np.asarray(run(poison(k), poison(v)))
    assert np.all(np.isfinite(dirty))
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("cache_kind", ["bf16", "int8"])
def test_two_launches_in_one_program_leave_nothing_behind(cache_kind):
    """Two calls in one jit on different layers of a stack give what each
    gives alone: the last live step of a launch prefetches nothing, so no
    DMA, semaphore or SMEM word of one launch reaches the next."""
    rng = np.random.default_rng(23)
    Hq, R = 4, 4
    D, BS, k, v, bt = _stacked_case(rng, cache_kind, Hq=Hq, R=R, MB=2, N=12)
    q = jnp.asarray(rng.standard_normal((2, R, Hq, D)), jnp.bfloat16)
    lens = jnp.asarray([[2 * BS, 0, 1, BS + 1], [0, BS, 2 * BS, 0]], jnp.int32)
    call = lambda i, layer: paged_attention_kernel(
        q[i], k, v, bt, lens[i], D ** -0.5, interpret=True, chunk=1,
        layer=jnp.int32(layer),
    )
    both = jax.jit(lambda: (call(0, 0), call(1, 2)))()
    for got, (i, layer) in zip(both, [(0, 0), (1, 2)]):
        # (to a bf16 ulp: XLA:CPU fuses the interpreted body differently
        # when two launches share a program)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(call(i, layer), np.float32),
            atol=8e-3, rtol=8e-3,
        )
        ref = paged_attention_gather(
            q[i], _layer_of(k, layer), _layer_of(v, layer), bt, lens[i],
            D ** -0.5,
        )
        live = np.asarray(lens[i]) > 0  # a dead row is zeros, not the oracle's
        assert not np.asarray(got, np.float32)[~live].any()
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live], np.asarray(ref, np.float32)[live],
            atol=3e-2, rtol=3e-2,
        )
