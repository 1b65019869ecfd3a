"""Batched/overlapped prefill (round-1 weak item 4).

Admission prefills concurrent waiting requests in shared compiled steps:
same-bucket prompts ride ONE device call, so TTFT under a burst stacks
sub-linearly instead of one-jit-call-per-request. Parity with the
sequential path must be exact (greedy).
"""

import threading
import time

import numpy as np

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor, PrefillItem


def _cfg(**kw):
    base = dict(
        model="llama3-tiny",
        num_blocks=96,
        max_running_requests=16,
        max_seq_len=256,
        prefill_buckets=[32, 64],
    )
    base.update(kw)
    return EngineConfig(**base)


def test_prefill_batch_matches_sequential():
    """prefill_batch over mixed-length items == one-at-a-time prefill."""
    exe_a = ModelExecutor(_cfg(), init_seed=3)
    exe_b = ModelExecutor(_cfg(), init_seed=3)

    rng = np.random.default_rng(0)
    items = []
    base_block = 1
    for i, n in enumerate([5, 17, 33, 9]):
        table = np.zeros((exe_a.max_blocks_per_seq,), np.int32)
        nb = (n + 1 + exe_a.block_size - 1) // exe_a.block_size
        table[:nb] = np.arange(base_block, base_block + nb)
        base_block += nb
        items.append(
            PrefillItem(
                token_ids=rng.integers(0, 512, n).astype(np.int32),
                start_pos=0,
                block_table=table,
            )
        )

    seq_results = [
        exe_a.prefill(it.token_ids, it.start_pos, it.block_table)
        for it in items
    ]
    batch_results = exe_b.prefill_batch(items)
    # Tokens must match exactly; logprobs only to float tolerance (the P=1
    # and P=4 programs reduce in different orders).
    assert [t for t, _ in seq_results] == [t for t, _ in batch_results]
    np.testing.assert_allclose(
        [l for _, l in seq_results], [l for _, l in batch_results], atol=1e-4
    )
    # Caches identical outside garbage block 0 (masked/padded rows collide
    # there with nondeterministic winners — by design).
    np.testing.assert_array_equal(
        np.asarray(exe_a.k_cache.data)[:, 1:], np.asarray(exe_b.k_cache.data)[:, 1:]
    )


def test_burst_shares_compiled_steps():
    """8 concurrent same-bucket prompts are admitted in at most 2 batched
    prefill calls (not 8 sequential ones)."""
    exe = ModelExecutor(_cfg(), init_seed=1)
    calls = []
    orig = exe._prefill_group

    def counting(group):
        calls.append(len(group))
        return orig(group)

    exe._prefill_group = counting

    # Split stepping: this test counts _prefill_group calls, i.e. the
    # SPLIT batched-prefill plumbing (the escape hatch since ISSUE 9).
    # The mixed-step equivalent (a burst riding few fused dispatches) is
    # covered in tests/test_mixed_step.py.
    eng = InferenceEngine(_cfg(enable_mixed_step=False), executor=exe)
    done = []
    rng = np.random.default_rng(7)
    # Enqueue BEFORE starting the engine so one _admit sees the full burst.
    for i in range(8):
        ev = threading.Event()
        done.append(ev)

        def cb(out, ev=ev):
            if out.finished:
                ev.set()
            return True

        eng.add_request(
            EngineRequest(
                request_id=f"b{i}",
                prompt_token_ids=[int(t) for t in rng.integers(0, 512, 20 + i)],
                sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
                callback=cb,
            )
        )
    eng.start()
    try:
        for ev in done:
            assert ev.wait(120.0)
    finally:
        eng.stop()
    assert sum(calls) == 8  # every request prefilled exactly once
    assert len(calls) <= 2, f"burst used {len(calls)} prefill steps: {calls}"
    assert max(calls) == 8


def test_engine_batched_greedy_parity():
    """Concurrent requests through the batching engine produce the same
    greedy streams as the same requests run one at a time."""
    prompts = [
        [int(t) for t in np.random.default_rng(i).integers(0, 512, 8 + 3 * i)]
        for i in range(5)
    ]

    def run(concurrent: bool):
        eng = InferenceEngine(_cfg(), executor=ModelExecutor(_cfg(), init_seed=4))
        eng.start()
        results = {}
        try:
            events = []
            for i, p in enumerate(prompts):
                toks = []
                results[i] = toks
                ev = threading.Event()
                events.append(ev)

                def cb(out, toks=toks, ev=ev):
                    for s in out.outputs:
                        toks.extend(s.token_ids)
                    if out.finished:
                        ev.set()
                    return True

                eng.add_request(
                    EngineRequest(
                        request_id=f"r{i}",
                        prompt_token_ids=p,
                        sampling=SamplingParams(temperature=0.0, max_new_tokens=6),
                        callback=cb,
                    )
                )
                if not concurrent:
                    assert ev.wait(120.0)
            for ev in events:
                assert ev.wait(120.0)
        finally:
            eng.stop()
        return results

    assert run(False) == run(True)


def test_chunked_prefill_interleaves_decode():
    """A prompt longer than max_prefill_tokens prefills across MULTIPLE
    engine steps (strict per-step budget), with decode steps for running
    sequences in between — one long prompt must not stall every running
    request's token cadence (SURVEY §7 hard part 3). Output must equal the
    dense-oracle continuation regardless of chunk boundaries."""
    import threading

    import jax.numpy as jnp

    from xllm_service_tpu.models import llama
    from xllm_service_tpu.models.configs import get_model_config
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine

    cfg = EngineConfig(
        model="llama3-tiny",
        dtype="float32",
        block_size=16,
        num_blocks=96,
        max_running_requests=4,
        max_seq_len=512,
        prefill_buckets=[32, 64, 128, 256, 512],
        max_prefill_tokens=48,  # long prompt => several chunks
    )
    ex = ModelExecutor(cfg, init_seed=3)
    eng = InferenceEngine(cfg, executor=ex)
    mcfg = get_model_config("llama3-tiny")

    def oracle(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = llama.forward_dense(
                ex.params, mcfg, jnp.asarray(seq, jnp.int32)[None]
            )
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    rng = np.random.default_rng(12)
    short_prompt = rng.integers(1, 500, (8,)).tolist()
    long_prompt = rng.integers(1, 500, (200,)).tolist()  # ~5 chunks of 48

    events = []  # ("short"|"long", token) in emission order
    short_done, long_done = threading.Event(), threading.Event()

    def cb(name, done):
        def _cb(out):
            for so in out.outputs:
                for t in so.token_ids:
                    events.append((name, t))
            if out.finished:
                done.set()
            return True

        return _cb

    eng.start()
    try:
        eng.add_request(
            EngineRequest(
                request_id="short",
                prompt_token_ids=short_prompt,
                sampling=SamplingParams(temperature=0.0, max_new_tokens=24),
                callback=cb("short", short_done),
            )
        )
        # Let the short request begin decoding, then add the long one.
        deadline = time.monotonic() + 60
        while (
            sum(1 for n, _ in events if n == "short") < 2
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        idx_at_add = len(events)  # marker: long request exists from here
        eng.add_request(
            EngineRequest(
                request_id="long",
                prompt_token_ids=long_prompt,
                sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
                callback=cb("long", long_done),
            )
        )
        assert short_done.wait(120) and long_done.wait(120)
    finally:
        eng.stop()

    # Correctness: both streams equal their oracle continuations.
    short_toks = [t for n, t in events if n == "short"]
    long_toks = [t for n, t in events if n == "long"]
    assert short_toks == oracle(short_prompt, 24)
    assert long_toks == oracle(long_prompt, 4)

    # Interleaving: between the long request's ARRIVAL (idx_at_add) and
    # its FIRST token, the short request kept producing — one decode step
    # runs after each of the >= 4 prefill chunks; without chunking the
    # whole 200-token prefill lands in one step and at most ~1 short
    # token could sneak into that window.
    first_long = events.index(("long", long_toks[0]))
    assert first_long >= idx_at_add
    short_during_prefill = sum(
        1 for n, _ in events[idx_at_add:first_long] if n == "short"
    )
    assert short_during_prefill >= 3, events[idx_at_add:first_long]
