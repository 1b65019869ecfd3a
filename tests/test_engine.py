"""Continuous-batching engine e2e on CPU with the tiny model: greedy output
must equal the dense-oracle continuation; prefix caching, concurrency,
preemption, and cancellation are exercised."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import get_model_config
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor


def make_engine(num_blocks=64, max_running=4, block_size=16, max_seq_len=256):
    cfg = EngineConfig(
        model="llama3-tiny",
        dtype="float32",
        block_size=block_size,
        num_blocks=num_blocks,
        max_running_requests=max_running,
        max_seq_len=max_seq_len,
        prefill_buckets=[32, 64, 128, 256],
    )
    ex = ModelExecutor(cfg)
    return InferenceEngine(cfg, executor=ex), ex


class Collector:
    def __init__(self):
        self.tokens = []
        self.outputs = []
        self.finished = threading.Event()

    def __call__(self, out):
        self.outputs.append(out)
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
        if out.finished:
            self.finished.set()
        return True


@pytest.fixture(scope="module")
def engine_and_oracle():
    eng, ex = make_engine()
    mcfg = get_model_config("llama3-tiny")

    def oracle(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            logits = llama.forward_dense(
                ex.params, mcfg, jnp.asarray(seq, jnp.int32)[None]
            )
            seq.append(int(jnp.argmax(logits[0, -1])))
        return seq[len(prompt):]

    return eng, oracle


def run_to_completion(eng, collectors, max_steps=200):
    for _ in range(max_steps):
        if not eng.has_work():
            break
        eng.step()
    assert all(c.finished.is_set() for c in collectors)


def test_greedy_matches_oracle(engine_and_oracle):
    eng, oracle = engine_and_oracle
    rng = np.random.RandomState(0)
    prompt = list(rng.randint(0, 500, size=23))
    c = Collector()
    eng.add_request(
        EngineRequest(
            "r1", prompt, SamplingParams(temperature=0.0, max_new_tokens=8), c
        )
    )
    run_to_completion(eng, [c])
    assert c.tokens == oracle(prompt, 8)
    assert c.outputs[-1].usage.num_generated_tokens == 8
    # All blocks released after finish.
    assert eng.block_mgr.usage == 0 or eng.block_mgr.num_free_blocks > 0
    assert not eng._running


def test_concurrent_requests_match_oracle(engine_and_oracle):
    eng, oracle = engine_and_oracle
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, 500, size=n)) for n in (10, 33, 17, 25, 41)]
    collectors = [Collector() for _ in prompts]
    for i, (p, c) in enumerate(zip(prompts, collectors)):
        eng.add_request(
            EngineRequest(
                f"c{i}", p, SamplingParams(temperature=0.0, max_new_tokens=6), c
            )
        )
    run_to_completion(eng, collectors)
    for p, c in zip(prompts, collectors):
        assert c.tokens == oracle(p, 6), "batched decode diverged from oracle"


def test_prefix_cache_hit_gives_same_output(engine_and_oracle):
    eng, oracle = engine_and_oracle
    rng = np.random.RandomState(2)
    shared = list(rng.randint(0, 500, size=37))  # > 2 blocks of 16
    c1, c2 = Collector(), Collector()
    eng.add_request(
        EngineRequest("p1", shared, SamplingParams(temperature=0.0, max_new_tokens=4), c1)
    )
    run_to_completion(eng, [c1])
    ev = eng.take_cache_event()
    assert ev.stored_cache  # blocks were committed
    eng.add_request(
        EngineRequest("p2", shared, SamplingParams(temperature=0.0, max_new_tokens=4), c2)
    )
    run_to_completion(eng, [c2])
    assert c1.tokens == c2.tokens == oracle(shared, 4)


def test_cancellation():
    eng, _ = make_engine()
    rng = np.random.RandomState(3)
    c = Collector()
    eng.add_request(
        EngineRequest(
            "x1",
            list(rng.randint(0, 500, size=12)),
            SamplingParams(temperature=0.0, max_new_tokens=1000),
            c,
        )
    )
    eng.step()  # prefill + first token
    eng.cancel("x1")
    eng.step()
    assert c.finished.is_set()
    assert c.outputs[-1].cancelled
    assert not eng._running


def test_preemption_under_block_pressure():
    # Tiny pool: two long-running requests must share via preemption.
    eng, _ = make_engine(num_blocks=8, max_running=2, block_size=16, max_seq_len=96)
    rng = np.random.RandomState(4)
    cs = [Collector(), Collector()]
    for i, c in enumerate(cs):
        eng.add_request(
            EngineRequest(
                f"pr{i}",
                list(rng.randint(0, 500, size=20)),
                SamplingParams(temperature=0.0, max_new_tokens=40),
                c,
            )
        )
    run_to_completion(eng, cs, max_steps=500)
    for c in cs:
        assert c.outputs[-1].finished
        assert c.outputs[-1].usage.num_generated_tokens == 40
        # Preemption must not inflate the emitted token count or the
        # reported prompt length.
        assert len(c.tokens) == 40
        assert c.outputs[-1].usage.num_prompt_tokens == 20


def test_oversized_request_rejected_not_stalled():
    eng, _ = make_engine(num_blocks=4, max_running=2, block_size=16, max_seq_len=200)
    rng = np.random.RandomState(6)
    big, small = Collector(), Collector()
    # Needs ceil(91/16)=6 blocks > 3 usable: must be rejected, not stall.
    eng.add_request(
        EngineRequest("big", list(rng.randint(0, 500, size=90)),
                      SamplingParams(max_new_tokens=5), big)
    )
    eng.add_request(
        EngineRequest("small", list(rng.randint(0, 500, size=10)),
                      SamplingParams(temperature=0.0, max_new_tokens=3), small)
    )
    run_to_completion(eng, [big, small], max_steps=100)
    assert big.outputs[-1].status.code.name == "RESOURCE_EXHAUSTED"
    assert small.outputs[-1].finished and len(small.tokens) == 3


def test_sync_engine_config_escape_hatch(engine_and_oracle):
    """sync_engine=True restores fully synchronous stepping (no in-flight
    step ever) and emits the same greedy stream as the overlapped default
    (which the rest of this module exercises)."""
    _, oracle = engine_and_oracle
    cfg = EngineConfig(
        model="llama3-tiny",
        dtype="float32",
        block_size=16,
        num_blocks=64,
        max_running_requests=4,
        max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
        sync_engine=True,
    )
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg))
    assert eng.cfg.sync_engine and eng._force_sync
    rng = np.random.RandomState(0)
    prompt = list(rng.randint(0, 500, size=23))
    c = Collector()
    eng.add_request(
        EngineRequest(
            "sync1", prompt,
            SamplingParams(temperature=0.0, max_new_tokens=8), c,
        )
    )
    run_to_completion(eng, [c])
    assert c.tokens == oracle(prompt, 8)
    assert eng.overlap_steps == 0 and eng._inflight is None


def test_overlap_default_engages_pipeline(engine_and_oracle):
    """The default engine runs the one-step-lookahead pipeline: decode
    steps are dispatched while the previous step is still in flight."""
    eng, oracle = engine_and_oracle
    assert not eng._force_sync
    rng = np.random.RandomState(8)
    prompt = list(rng.randint(0, 500, size=19))
    c = Collector()
    before = eng.overlap_steps
    eng.add_request(
        EngineRequest(
            "ov1", prompt,
            SamplingParams(temperature=0.0, max_new_tokens=8), c,
        )
    )
    run_to_completion(eng, [c])
    assert c.tokens == oracle(prompt, 8)
    assert eng.overlap_steps > before
    assert eng._inflight is None  # fully drained at idle


def test_engine_thread_loop():
    eng, _ = make_engine()
    eng.start()
    try:
        rng = np.random.RandomState(5)
        c = Collector()
        eng.add_request(
            EngineRequest(
                "t1",
                list(rng.randint(0, 500, size=9)),
                SamplingParams(temperature=0.7, top_k=10, max_new_tokens=5, seed=1),
                c,
            )
        )
        assert c.finished.wait(timeout=60)
        assert len(c.tokens) == 5
    finally:
        eng.stop()


def test_warmup_compiles_before_start():
    """warmup_on_start pre-compiles every prefill bucket + the decode step
    against the garbage block; serving afterwards is unchanged."""
    import threading

    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.runtime.executor import ModelExecutor

    cfg = EngineConfig(
        model="llama3-tiny", num_blocks=32, block_size=16,
        max_running_requests=4, max_seq_len=128, prefill_buckets=[32, 64],
        warmup_on_start=True,
    )
    exe = ModelExecutor(cfg, init_seed=2)
    groups = []
    orig = exe._prefill_group
    exe._prefill_group = lambda g: groups.append(len(g)) or orig(g)
    eng = InferenceEngine(cfg, executor=exe)
    eng.start()  # warmup runs here
    try:
        # Every bucket is warmed, including the prefix-hit CB variants up
        # to the full context width (round-2 review: a first request with
        # fewer context blocks than its length bucket must not compile).
        assert len(groups) >= len(exe.prefill_buckets)
        per_bucket_cbs: dict = {}
        for lpad, cb in exe.warmup():  # idempotent: shapes already built
            per_bucket_cbs.setdefault(lpad, set()).add(cb)
        assert set(per_bucket_cbs) == set(exe.prefill_buckets)
        assert all(
            max(cbs) == exe.max_blocks_per_seq
            for cbs in per_bucket_cbs.values()
        )
        ev = threading.Event()
        toks = []

        def cb(out):
            for s in out.outputs:
                toks.extend(s.token_ids)
            if out.finished:
                ev.set()
            return True

        eng.add_request(
            EngineRequest(
                request_id="w0",
                prompt_token_ids=[(i * 5 + 1) % 512 for i in range(20)],
                sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
                callback=cb,
            )
        )
        assert ev.wait(120.0)
        assert len(toks) == 4
    finally:
        eng.stop()
