"""Unified ragged paged-attention (ISSUE 9, docs/KERNELS.md).

Three layers of differential coverage:

1. KERNEL: ops/pallas/ragged_paged_attention.py in interpret mode vs the
   ragged_attention_blockwise oracle over fuzzed mixed batches — ragged
   prefill lengths (incl. unaligned tails), decode rows, dead rows,
   prefix hits (pos0 > 0), GQA ratios, bf16 + int8 KV, sliding window,
   and the packed-cache dispatcher path.

2. ENGINE: mixed-step engines (the default ragged step builder) emit
   streams BYTE-IDENTICAL to split-step engines — greedy and seeded
   sampling, overlap and sync modes, chunked prefill, prefix hits,
   staggered and concurrent arrivals. This is the contract that lets the
   fused hot loop replace the alternating prefill/decode steps: the
   model's mixed_step keeps each half's split-program shapes
   (models/llama.py docstring), so fusing the dispatch cannot change
   what a client receives.

3. HATCHES: EngineConfig.enable_mixed_step routing,
   automatic split fallback for guided + speculative + prefill_only, and
   the XLLM_RAGGED_ATTENTION_KERNEL=1 interpret-mode engine e2e (the
   Pallas branch actually serving an engine run on CPU).
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops.attention import (
    ragged_attention_blockwise,
    ragged_paged_attention,
)
from xllm_service_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention_kernel,
)
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

# --------------------------------------------------------------- kernel


def make_mixed_case(rng, seg_lens, Hq=8, Hkv=4, D=128, BS=16, MB=8,
                    num_blocks=64, dtype=jnp.float32):
    """A mixed batch over a shared KV pool: per-row random valid length
    (<= capacity; decode rows always 1 unless killed) and a random
    absolute start (prefix hits / decode context)."""
    B = len(seg_lens)
    T = sum(seg_lens)
    q = jnp.asarray(rng.standard_normal((T, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    bt = jnp.asarray(
        rng.choice(
            np.arange(1, num_blocks), size=(B, MB), replace=False
        ).astype(np.int32)
    )
    q_len = np.zeros((B,), np.int32)
    pos0 = np.zeros((B,), np.int32)
    for b, cap in enumerate(seg_lens):
        q_len[b] = 1 if cap == 1 else rng.integers(1, cap + 1)
        pos0[b] = rng.integers(0, MB * BS - q_len[b] + 1)
    return q, k, v, bt, jnp.asarray(q_len), jnp.asarray(pos0)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("gqa", [1, 4])
def test_ragged_kernel_fuzzed_mixed_batches(seed, gqa):
    """Fuzzed decode+prefill mixes (unaligned tails, prefix offsets)
    match the blockwise oracle."""
    rng = np.random.default_rng(seed)
    Hkv = 4
    # decode singletons interleaved with ragged prefill capacities
    seg_lens = (1, 1, int(rng.integers(2, 33)), 1, int(rng.integers(2, 33)))
    q, k, v, bt, q_len, pos0 = make_mixed_case(
        rng, seg_lens, Hq=Hkv * gqa, Hkv=Hkv
    )
    scale = q.shape[-1] ** -0.5
    ref = ragged_attention_blockwise(
        q, k, v, bt, q_len, pos0, seg_lens, scale
    )
    out = ragged_paged_attention_kernel(
        q, k, v, bt, q_len, pos0, seg_lens, scale, interpret=True, tile_q=16
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ragged_kernel_dead_rows_zero():
    """q_len 0 rows (inactive decode slots / padded prefill lanes) emit
    zeros; live rows are untouched by their presence."""
    rng = np.random.default_rng(3)
    seg_lens = (1, 1, 16, 8)
    q, k, v, bt, q_len, pos0 = make_mixed_case(rng, seg_lens)
    q_len = jnp.asarray([1, 0, 16, 0], jnp.int32)
    # The override raises row lengths past what the helper drew pos0 for;
    # re-clamp so every row's context still fits its MB*BS block table.
    pos0 = jnp.minimum(pos0, 8 * 16 - q_len)
    scale = 0.125
    out = np.asarray(ragged_paged_attention_kernel(
        q, k, v, bt, q_len, pos0, seg_lens, scale, interpret=True, tile_q=16
    ))
    ref = np.asarray(ragged_attention_blockwise(
        q, k, v, bt, q_len, pos0, seg_lens, scale
    ))
    assert np.all(out[1] == 0) and np.all(out[18:] == 0)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ragged_kernel_tiles_cross_row_boundaries():
    """A tile smaller than one row's segment AND a tile holding many
    rows both reduce exactly (the row-iteration/online-softmax no-op
    merge argument in the kernel docstring)."""
    rng = np.random.default_rng(4)
    seg_lens = (1,) * 12 + (40,)  # tile_q=16: tiles mix decode rows,
    q, k, v, bt, q_len, pos0 = make_mixed_case(rng, seg_lens, MB=4)
    scale = 0.125
    ref = ragged_attention_blockwise(
        q, k, v, bt, q_len, pos0, seg_lens, scale
    )
    out = ragged_paged_attention_kernel(
        q, k, v, bt, q_len, pos0, seg_lens, scale, interpret=True, tile_q=16
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ragged_kernel_bf16():
    rng = np.random.default_rng(5)
    seg_lens = (1, 24, 1, 9)
    q, k, v, bt, q_len, pos0 = make_mixed_case(
        rng, seg_lens, dtype=jnp.bfloat16
    )
    scale = 0.125
    ref = ragged_attention_blockwise(
        q, k, v, bt, q_len, pos0, seg_lens, scale
    )
    out = ragged_paged_attention_kernel(
        q, k, v, bt, q_len, pos0, seg_lens, scale, interpret=True, tile_q=16
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_ragged_kernel_int8():
    """int8 KV: pool-native grouped scales stream and dequantize in VMEM
    (same tolerance budget as the flash-prefill int8 case — dequant_tile
    rounds to bf16 before the score matmul)."""
    rng = np.random.default_rng(6)
    # BS=128: int8 [G, BS] scale tiles carry BS on lanes (chip rule).
    seg_lens = (1, 1, 24, 17)
    q, k, v, bt, q_len, pos0 = make_mixed_case(
        rng, seg_lens, BS=128, MB=2, num_blocks=16
    )
    kq, vq = kvc.quantize_pool(k), kvc.quantize_pool(v)
    scale = 0.125
    ref = ragged_attention_blockwise(
        q, kq, vq, bt, q_len, pos0, seg_lens, scale
    )
    out = ragged_paged_attention_kernel(
        q, kq, vq, bt, q_len, pos0, seg_lens, scale, interpret=True,
        tile_q=16,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2
    )


def test_ragged_kernel_sliding_window():
    rng = np.random.default_rng(7)
    seg_lens = (1, 32, 1)
    q, k, v, bt, q_len, pos0 = make_mixed_case(rng, seg_lens)
    scale = 0.125
    for window in (8, 24):
        ref = ragged_attention_blockwise(
            q, k, v, bt, q_len, pos0, seg_lens, scale, window=window
        )
        out = ragged_paged_attention_kernel(
            q, k, v, bt, q_len, pos0, seg_lens, scale, interpret=True,
            tile_q=16, window=window,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


def test_ragged_dispatcher_packed_cache(monkeypatch):
    """head_dim < 128 rides the packed-pair cache layout through the
    dispatcher (kernel_io_for/pack_queries) — kernel branch forced via
    use_kernel + interpret, packed shapes opted in."""
    monkeypatch.setenv("XLLM_PACKED_KV_KERNEL", "1")
    rng = np.random.default_rng(8)
    Hq, Hkv, D, BS, MB, NB = 4, 2, 32, 16, 4, 32
    seg_lens = (1, 12, 1)
    T = sum(seg_lens)
    q = jnp.asarray(rng.standard_normal((T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((NB, Hkv, BS, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB, Hkv, BS, D)), jnp.float32)
    kp = kvc.as_paged(kvc.pack_pool(k)) if hasattr(kvc, "pack_pool") else None
    if kp is None:
        pytest.skip("no packed-pool helper in this build")
    vp = kvc.as_paged(kvc.pack_pool(v))
    bt = jnp.asarray(
        rng.choice(np.arange(1, NB // 4), size=(3, MB),
                   replace=False).astype(np.int32)
    )
    q_len = jnp.asarray([1, 12, 1], jnp.int32)
    pos0 = jnp.asarray([20, 0, 5], jnp.int32)
    scale = D ** -0.5
    ref = ragged_paged_attention(
        q, kp, vp, bt, q_len, pos0, seg_lens, scale, use_kernel=False
    )
    out = ragged_paged_attention(
        q, kp, vp, bt, q_len, pos0, seg_lens, scale, use_kernel=True,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
    )


# --------------------------------------------------------------- engine

BS = 16


def _cfg(**kw):
    base = dict(
        model="llama3-tiny",
        num_blocks=96,
        max_running_requests=8,
        max_seq_len=512,
        block_size=BS,
        prefill_buckets=[32, 64, 128],
    )
    base.update(kw)
    return EngineConfig(**base)


def _run_engine(cfg, requests, stagger=False, ex_cfg=None):
    """Drive `requests` [(rid, tokens, sampling)] through an engine;
    returns {rid: [token_ids]} with per-request completion waits."""
    eng = InferenceEngine(
        cfg, executor=ModelExecutor(ex_cfg or _cfg(), init_seed=11)
    )
    eng.start()
    results, events = {}, []
    try:
        for rid, toks, s in requests:
            out_toks = []
            results[rid] = out_toks
            ev = threading.Event()
            events.append(ev)

            def cb(out, out_toks=out_toks, ev=ev):
                for so in out.outputs:
                    out_toks.extend(so.token_ids)
                if out.finished:
                    ev.set()
                return True

            eng.add_request(EngineRequest(
                request_id=rid, prompt_token_ids=list(toks),
                sampling=s, callback=cb,
            ))
            if stagger:
                assert ev.wait(120.0)
        for ev in events:
            assert ev.wait(120.0)
    finally:
        eng.stop()
    return results


def _requests(n=5, greedy=True, base_len=9, seed0=100):
    reqs = []
    for i in range(n):
        toks = [
            int(t) for t in
            np.random.default_rng(seed0 + i).integers(
                0, 512, base_len + 11 * i
            )
        ]
        s = (
            SamplingParams(temperature=0.0, max_new_tokens=6)
            if greedy else
            SamplingParams(
                temperature=0.9, top_k=40, top_p=0.95, seed=7 + i,
                max_new_tokens=6,
            )
        )
        reqs.append((f"r{i}", toks, s))
    return reqs


@pytest.mark.parametrize("greedy", [True, False])
def test_mixed_equals_split_byte_identical(greedy):
    """The acceptance differential: a mixed-step engine's emitted streams
    == a split-step engine's, token for token, greedy AND seeded
    sampling, concurrent arrivals."""
    reqs = _requests(greedy=greedy)
    mixed = _run_engine(_cfg(enable_mixed_step=True), reqs)
    split = _run_engine(_cfg(enable_mixed_step=False), reqs)
    assert mixed == split


def test_mixed_equals_split_sync_mode():
    """Sync engines force split stepping; the overlapped mixed engine
    must still match them byte-for-byte (overlap ≡ sync ≡ split)."""
    reqs = _requests(n=4)
    mixed = _run_engine(_cfg(enable_mixed_step=True), reqs)
    syncd = _run_engine(_cfg(sync_engine=True), reqs)
    assert mixed == syncd


def test_mixed_equals_split_chunked_prefill():
    """Prompts spanning several prefill chunks (max_prefill_tokens caps
    each cut): the pipelined chunk walk must land the same KV and the
    same streams as split mode, staggered and concurrent."""
    reqs = _requests(n=3, base_len=3 * BS + 5)
    for stagger in (False, True):
        mixed = _run_engine(
            _cfg(enable_mixed_step=True, max_prefill_tokens=2 * BS),
            reqs, stagger=stagger,
        )
        split = _run_engine(
            _cfg(enable_mixed_step=False, max_prefill_tokens=2 * BS),
            reqs, stagger=stagger,
        )
        assert mixed == split


def test_mixed_equals_split_prefix_hit():
    """A re-sent prompt hits the prefix cache in both modes and the
    follow-up stream stays identical (pos0 > 0 rows in the mixed batch)."""
    shared = [int(t) for t in np.random.default_rng(55).integers(
        0, 512, 4 * BS)]
    reqs = [
        ("warm", shared + [1, 2, 3],
         SamplingParams(temperature=0.0, max_new_tokens=4)),
        ("hit", shared + [4, 5, 6],
         SamplingParams(temperature=0.0, max_new_tokens=4)),
    ]
    mixed = _run_engine(_cfg(enable_mixed_step=True), reqs, stagger=True)
    split = _run_engine(_cfg(enable_mixed_step=False), reqs, stagger=True)
    assert mixed == split


def test_burst_shares_mixed_dispatches():
    """The mixed-mode analogue of the split burst test: 6 concurrent
    one-chunk prompts ride few fused dispatches (each carrying several
    prefill rows), not one dispatch per request."""
    cfg = _cfg(enable_mixed_step=True)
    eng = InferenceEngine(cfg, executor=ModelExecutor(_cfg(), init_seed=11))
    rng = np.random.default_rng(9)
    events = []
    for i in range(6):
        ev = threading.Event()
        events.append(ev)

        def cb(out, ev=ev):
            if out.finished:
                ev.set()
            return True

        eng.add_request(EngineRequest(
            request_id=f"b{i}",
            prompt_token_ids=[int(t) for t in rng.integers(0, 512, 20 + i)],
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
            callback=cb,
        ))
    eng.start()
    try:
        for ev in events:
            assert ev.wait(120.0)
    finally:
        eng.stop()
    assert eng.mixed_steps >= 1
    # All 6 same-bucket prompts fused into at most 2 prefill-carrying
    # dispatches (PREFILL_GROUP_MAX bounds one; the budget may split).
    assert eng.mixed_steps <= 2, f"burst used {eng.mixed_steps} mixed steps"


# -------------------------------------------------------------- hatches


def test_env_hatch_overrides_config():
    """enable_mixed_step routes the step builder, read live from the
    engine's own config (the executor's may differ: the engine's rules)."""
    eng = InferenceEngine(
        _cfg(enable_mixed_step=False),
        executor=ModelExecutor(_cfg(), init_seed=11),
    )
    assert not eng.mixed_step_enabled
    eng.cfg.enable_mixed_step = True
    assert eng.mixed_step_enabled
    eng = InferenceEngine(
        _cfg(enable_mixed_step=True),
        executor=ModelExecutor(_cfg(enable_mixed_step=False), init_seed=11),
    )
    assert eng.mixed_step_enabled


def test_speculative_rides_pipeline():
    """Speculative decoding no longer forces sync stepping (ISSUE 13):
    the composed path is the default, and sync_engine=True degrades it
    back to depth-0 verify steps."""
    eng = InferenceEngine(
        _cfg(speculative_tokens=3),
        executor=ModelExecutor(_cfg(), init_seed=11),
    )
    assert not eng._force_sync
    eng.cfg.sync_engine = True
    assert eng._force_sync  # live per-step decision: the flip lands
    eng.cfg.sync_engine = False
    eng2 = InferenceEngine(
        _cfg(speculative_tokens=3, sync_engine=True),
        executor=ModelExecutor(_cfg(), init_seed=11),
    )
    assert eng2._force_sync
    eng2.cfg.sync_engine = False
    assert not eng2._force_sync  # and back, over a True config


def test_guided_request_rides_mixed_batch():
    """A guided request admitted under mixed stepping rides the mixed
    batch (final chunk under an in-graph mask row) and decodes
    host-paced inside the pipeline (ISSUE 13) — and plain requests
    around it still finish."""
    reqs = _requests(n=2)
    cfg = _cfg(enable_mixed_step=True)
    eng = InferenceEngine(cfg, executor=ModelExecutor(_cfg(), init_seed=11))
    eng.start()
    done = []
    try:
        for rid, toks, s in reqs:
            ev = threading.Event()
            done.append(ev)

            def cb(out, ev=ev):
                if out.finished:
                    ev.set()
                return True

            eng.add_request(EngineRequest(
                request_id=rid, prompt_token_ids=toks, sampling=s,
                callback=cb,
            ))
        ev = threading.Event()
        done.append(ev)

        def gcb(out, ev=ev):
            if out.finished:
                ev.set()
            return True

        eng.add_request(EngineRequest(
            request_id="guided",
            prompt_token_ids=[1, 2, 3, 4],
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8),
            callback=gcb,
            guided="json",
        ))
        for ev in done:
            assert ev.wait(120.0)
    finally:
        eng.stop()


def test_ragged_kernel_engine_e2e_interpret(monkeypatch):
    """The Pallas ragged kernel actually SERVES an engine run (interpret
    mode on CPU, packed tiny-model cache opted in) and the greedy streams
    match the reference-path mixed engine. llama3-packed-tiny is the one
    tiny geometry that is kernel-eligible: head_dim 64 with 2 kv heads
    packs pairwise into 128-lane cache rows (kv_pack_factor P=2);
    llama3-tiny's D=32/Hkv=2 can never pack (P=4 doesn't divide 2)."""
    reqs = _requests(n=3)
    cfg = _cfg(enable_mixed_step=True, model="llama3-packed-tiny")
    monkeypatch.setenv("XLLM_PACKED_KV_KERNEL", "1")
    ref = _run_engine(
        cfg, reqs, ex_cfg=_cfg(model="llama3-packed-tiny")
    )
    monkeypatch.setenv("XLLM_RAGGED_ATTENTION_KERNEL", "1")
    monkeypatch.setenv("XLLM_RAGGED_INTERPRET", "1")
    eng = InferenceEngine(
        cfg,
        executor=ModelExecutor(
            _cfg(model="llama3-packed-tiny"), init_seed=11
        ),
    )
    assert eng._kernel_names["mixed"] == "ragged"
    eng.start()
    results, events = {}, []
    try:
        for rid, toks, s in reqs:
            out_toks = []
            results[rid] = out_toks
            ev = threading.Event()
            events.append(ev)

            def cb(out, out_toks=out_toks, ev=ev):
                for so in out.outputs:
                    out_toks.extend(so.token_ids)
                if out.finished:
                    ev.set()
                return True

            eng.add_request(EngineRequest(
                request_id=rid, prompt_token_ids=list(toks), sampling=s,
                callback=cb,
            ))
        for ev in events:
            assert ev.wait(300.0)
    finally:
        eng.stop()
    assert eng.mixed_steps >= 1
    assert results == ref


# ------------------------------------------------------------ hatch lint


class TestKernelHatchLint:
    def test_lint_clean(self):
        """Every XLLM_*_KERNEL hatch in ops/ is documented with its
        default in docs/ARCHITECTURE.md (and no stale rows) — flipped
        defaults can't drift undocumented (ISSUE 9 satellite)."""
        import os
        import sys

        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts"),
        )
        import check_kernel_hatches

        assert check_kernel_hatches.main() == 0
