"""The mixed step (ISSUE 9, docs/KERNELS.md): decode rows and prefill
chunks in one dispatch, attention as the decode and the prefill launch
side by side.

Three layers of differential coverage:

1. ATTENTION: ops.attention.mixed_attention's pair ON THE KERNEL ROUTE
   (the decode kernel and the flash kernel in interpret mode, through the
   `_interpret` seam: the route every benchmark cell runs on the chip)
   against the gather and the blockwise scan over fuzzed mixed batches —
   ragged chunk lengths (incl. unaligned tails), decode rows, dead rows,
   prefix hits (start > 0), GQA ratios, bf16 + int8 KV, sliding window.

2. ENGINE: mixed-step engines (the default step builder) emit streams
   BYTE-IDENTICAL to split-step engines — greedy and seeded sampling,
   overlap and sync modes, chunked prefill, prefix hits, staggered and
   concurrent arrivals. This is the contract that lets the fused hot loop
   replace the alternating prefill/decode steps: the model's mixed_step
   keeps each half's split-program shapes (models/llama.py docstring),
   so fusing the dispatch cannot change what a client receives.

3. HATCHES: EngineConfig.enable_mixed_step routing, automatic split
   fallback for guided + speculative + prefill_only, and an engine run
   SERVED by the pair of kernels in interpret mode on the CPU.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

# ------------------------------------------------------------ attention


def make_mixed_case(rng, R, chunks, Hq=8, Hkv=4, D=128, BS=16, MB=8,
                    num_blocks=64, dtype=jnp.float32):
    """A mixed batch over a shared KV pool: R decode rows at random
    contexts and len(chunks) prefill rows, each with a random valid
    length (<= its capacity) and a random absolute start (prefix hits),
    padded to the widest capacity. Returns (q_dec, q_pf, k, v, dec_tables,
    dec_seq_lens, pf_tables, pf_start, pf_len)."""
    P, Lpad = len(chunks), max(chunks)
    q_dec = jnp.asarray(rng.standard_normal((R, Hq, D)), dtype)
    q_pf = jnp.asarray(rng.standard_normal((P, Lpad, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((num_blocks, Hkv, BS, D)), dtype)
    bt = rng.choice(
        np.arange(1, num_blocks), size=(R + P, MB), replace=False
    ).astype(np.int32)
    seq_lens = rng.integers(1, MB * BS + 1, R).astype(np.int32)
    pf_len = np.asarray([rng.integers(1, cap + 1) for cap in chunks], np.int32)
    pf_start = np.asarray(
        [rng.integers(0, MB * BS - n + 1) for n in pf_len], np.int32
    )
    return (q_dec, q_pf, k, v, jnp.asarray(bt[:R]), jnp.asarray(seq_lens),
            jnp.asarray(bt[R:]), jnp.asarray(pf_start), jnp.asarray(pf_len))


def _pair_and_reference(monkeypatch, case, scale, window=0):
    """(decode out, prefill out) of mixed_attention on the kernel route
    (interpret mode) and of the gather / the blockwise scan."""
    q_dec, q_pf, k, v, dt, sl, pt, ps, pl = case
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    routes = attention.attention_routes(k, q_dec.shape[-2], q_dec.shape[-1])
    assert routes.decode and routes.prefill and routes.interpret
    out = attention.mixed_attention(
        q_dec, q_pf, k, v, dt, sl, pt, ps, pl, scale, window=window
    )
    ref = (
        attention.paged_attention_gather(q_dec, k, v, dt, sl, scale, window=window),
        jax.vmap(
            lambda q, t, s, n: attention.prefill_attention_blockwise(
                q, k, v, t, s, n, scale, window=window
            )
        )(q_pf, pt, ps, pl),
    )
    valid = (jnp.arange(q_pf.shape[1])[None, :] < pl[:, None])[:, :, None, None]
    return [np.asarray(o, np.float32) for o in (out[0], jnp.where(valid, out[1], 0))], \
        [np.asarray(o, np.float32) for o in (ref[0], jnp.where(valid, ref[1], 0))]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("gqa", [1, 4])
def test_mixed_pair_fuzzed_mixed_batches(monkeypatch, seed, gqa):
    """Fuzzed decode+prefill mixes (unaligned tails, prefix offsets):
    the pair of kernels matches the gather and the blockwise scan."""
    rng = np.random.default_rng(seed)
    Hkv = 4
    chunks = (int(rng.integers(2, 33)), int(rng.integers(2, 33)))
    case = make_mixed_case(rng, 3, chunks, Hq=Hkv * gqa, Hkv=Hkv)
    out, ref = _pair_and_reference(monkeypatch, case, case[0].shape[-1] ** -0.5)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


def test_mixed_pair_dead_rows_zero(monkeypatch):
    """Inactive decode slots (seq_len 0) and padded prefill lanes (len 0)
    emit zeros from the decode kernel and nothing a live row can see; live
    rows are untouched by their presence."""
    rng = np.random.default_rng(3)
    q_dec, q_pf, k, v, dt, sl, pt, ps, pl = make_mixed_case(rng, 4, (16, 8))
    sl = sl.at[1].set(0).at[3].set(0)
    pl = pl.at[1].set(0)
    out, ref = _pair_and_reference(
        monkeypatch, (q_dec, q_pf, k, v, dt, sl, pt, ps, pl), 0.125
    )
    assert np.all(out[0][1] == 0) and np.all(out[0][3] == 0)
    assert np.all(out[1][1] == 0)
    live = np.asarray(sl) > 0
    np.testing.assert_allclose(out[0][live], ref[0][live], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out[1], ref[1], atol=2e-5, rtol=2e-5)


def test_mixed_pair_chunk_spans_query_tiles():
    """A chunk wider than one query tile of the flash kernel beside many
    decode rows: every tile reduces over its own context exactly."""
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    rng = np.random.default_rng(4)
    q_dec, q_pf, k, v, dt, sl, pt, ps, pl = make_mixed_case(rng, 12, (40,), MB=4)
    out = flash_prefill_kernel(q_pf, k, v, pt, ps, pl, 0.125, interpret=True, tile_q=16)
    ref = attention.prefill_attention_blockwise(q_pf[0], k, v, pt[0], ps[0], pl[0], 0.125)
    n = int(pl[0])
    np.testing.assert_allclose(
        np.asarray(out[0, :n]), np.asarray(ref[:n]), atol=2e-5, rtol=2e-5
    )


def test_mixed_pair_bf16(monkeypatch):
    rng = np.random.default_rng(5)
    case = make_mixed_case(rng, 2, (24, 9), dtype=jnp.bfloat16)
    out, ref = _pair_and_reference(monkeypatch, case, 0.125)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o, r, atol=3e-2, rtol=3e-2)


def test_mixed_pair_int8(monkeypatch):
    """int8 KV: pool-native grouped scales stream and dequantize in VMEM
    in both kernels (dequant_tile rounds to bf16 before the score matmul:
    the flash-prefill int8 case's tolerance)."""
    rng = np.random.default_rng(6)
    # BS=128: int8 [G, BS] scale tiles carry BS on lanes (chip rule).
    q_dec, q_pf, k, v, *rest = make_mixed_case(
        rng, 2, (24, 17), BS=128, MB=2, num_blocks=16
    )
    case = (q_dec, q_pf, kvc.quantize_pool(k), kvc.quantize_pool(v), *rest)
    out, ref = _pair_and_reference(monkeypatch, case, 0.125)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o, r, atol=2e-2, rtol=2e-2)


def test_mixed_pair_sliding_window(monkeypatch):
    rng = np.random.default_rng(7)
    case = make_mixed_case(rng, 2, (32,))
    for window in (8, 24):
        out, ref = _pair_and_reference(monkeypatch, case, 0.125, window=window)
        for o, r in zip(out, ref):
            np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


def test_mixed_pair_use_kernel_forces_the_reference(monkeypatch):
    """`use_kernel=False` sends both halves to the gather and the
    blockwise scan whatever the platform says: bit for bit the
    dispatchers' own fallbacks."""
    rng = np.random.default_rng(8)
    q_dec, q_pf, k, v, dt, sl, pt, ps, pl = make_mixed_case(rng, 3, (12,))
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    forced = attention.mixed_attention(
        q_dec, q_pf, k, v, dt, sl, pt, ps, pl, 0.125, use_kernel=False
    )
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    plain = attention.mixed_attention(q_dec, q_pf, k, v, dt, sl, pt, ps, pl, 0.125)
    for f, p_ in zip(forced, plain):
        assert np.array_equal(np.asarray(f), np.asarray(p_))


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


def test_kernel_pair_engine_e2e_interpret(monkeypatch):
    """The pair of Pallas kernels the cells run (the decode kernel and
    the flash kernel) actually SERVES an engine run (interpret mode on
    CPU through the `_interpret` seam, packed tiny-model cache opted in)
    and the greedy streams match the reference-path mixed engine.
    llama3-packed-tiny is the one tiny geometry that is kernel-eligible:
    head_dim 64 with 2 kv heads packs pairwise into 128-lane cache rows
    (kv_pack_factor P=2); llama3-tiny's D=32/Hkv=2 can never pack (P=4
    doesn't divide 2)."""
    reqs = _requests(n=3)
    cfg = _cfg(enable_mixed_step=True, model="llama3-packed-tiny")
    monkeypatch.setenv("XLLM_PACKED_KV_KERNEL", "1")
    ref = _run_engine(
        cfg, reqs, ex_cfg=_cfg(model="llama3-packed-tiny")
    )
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    eng = InferenceEngine(
        cfg,
        executor=ModelExecutor(
            _cfg(model="llama3-packed-tiny"), init_seed=11
        ),
    )
    assert eng._kernel_names["mixed"] == "paged+flash"
    assert eng.executor.whole_table  # every launch walks its row's context
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
