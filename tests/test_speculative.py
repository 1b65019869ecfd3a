"""Speculative decoding (prompt-lookup n-gram drafts + one-pass verify).

The acceptance rule is EXACT for point-mass drafts (ops/sampling.py
speculative_sample): sampling t_j ~ p_j on the sequential per-step key
schedule and emitting while t_j equals the draft has the same joint law as
sequential decoding — so every test here asserts bit-identical token
streams between a speculative engine and a plain one, across greedy,
temperature/top-p sampling, and penalties. Throughput comes from accepted
drafts; correctness never depends on them.
"""

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor, SamplingBatch


def _cfg(spec=0, model="llama3-tiny", **kw):
    base = dict(
        model=model,
        dtype="float32",
        block_size=16,
        num_blocks=96,
        max_running_requests=4,
        max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
        speculative_tokens=spec,
    )
    base.update(kw)
    return EngineConfig(**base)


class Collector:
    def __init__(self):
        self.tokens = []
        self.logprobs = []
        self.done = False

    def __call__(self, out):
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
            if so.logprobs:
                self.logprobs.extend(
                    lp.data.logprob for lp in so.logprobs
                )
        if out.finished:
            self.done = True
        return True


def _run(engine, requests, max_steps=400):
    cols = []
    for rid, prompt, sampling in requests:
        c = Collector()
        cols.append(c)
        engine.add_request(EngineRequest(rid, list(prompt), sampling, c))
    for _ in range(max_steps):
        if not engine.has_work():
            break
        engine.step()
    assert all(c.done for c in cols)
    return cols


# A prompt whose continuation is likely to revisit its own n-grams: a
# strict repetition of a short period. Drafting only needs the HISTORY to
# repeat for proposals to exist; the tests never rely on them accepting.
REPEAT_PROMPT = [7, 11, 13, 17] * 8
RANDOM_PROMPT = list(np.random.RandomState(42).randint(0, 500, size=29))


@pytest.mark.parametrize("spec", [2, 3])
def test_spec_equals_plain_greedy(spec):
    plain = _run(
        InferenceEngine(_cfg(0), executor=ModelExecutor(_cfg(0))),
        [("r", REPEAT_PROMPT, SamplingParams(temperature=0.0,
                                             max_new_tokens=24))],
    )
    eng = InferenceEngine(_cfg(spec), executor=ModelExecutor(_cfg(spec)))
    fast = _run(
        eng,
        [("r", REPEAT_PROMPT, SamplingParams(temperature=0.0,
                                             max_new_tokens=24))],
    )
    assert fast[0].tokens == plain[0].tokens
    assert len(fast[0].tokens) == 24
    # Accounting: every active slot-step emits at least one token, and the
    # device-side emission count covers everything the host consumed —
    # except the FIRST generated token, which comes from the prefill step,
    # so verify steps emit max_new_tokens - 1 of the 24.
    assert eng.spec_steps > 0
    assert eng.spec_tokens_emitted >= eng.spec_slot_steps
    assert eng.spec_tokens_emitted >= 23
    assert eng.spec_slot_steps <= 23


def test_spec_equals_plain_sampled():
    sp = SamplingParams(
        temperature=0.8, top_p=0.9, top_k=40, seed=123, max_new_tokens=20,
        logprobs=True,
    )
    plain = _run(
        InferenceEngine(_cfg(0), executor=ModelExecutor(_cfg(0))),
        [("r", RANDOM_PROMPT, sp)],
    )
    fast = _run(
        InferenceEngine(_cfg(3), executor=ModelExecutor(_cfg(3))),
        [("r", RANDOM_PROMPT, sp)],
    )
    assert fast[0].tokens == plain[0].tokens
    np.testing.assert_allclose(
        fast[0].logprobs, plain[0].logprobs, rtol=1e-4, atol=1e-5
    )


def test_spec_equals_plain_with_penalties():
    sp = SamplingParams(
        temperature=0.7, seed=7, max_new_tokens=18,
        presence_penalty=0.8, frequency_penalty=0.4,
    )
    plain = _run(
        InferenceEngine(_cfg(0), executor=ModelExecutor(_cfg(0))),
        [("r", REPEAT_PROMPT, sp)],
    )
    fast = _run(
        InferenceEngine(_cfg(3), executor=ModelExecutor(_cfg(3))),
        [("r", REPEAT_PROMPT, sp)],
    )
    assert fast[0].tokens == plain[0].tokens


def test_spec_concurrent_mixed_sampling():
    """Several concurrent requests with different sampling configs run
    through the same [R, S] verify step; each stream must match its plain
    twin exactly."""
    reqs = [
        ("a", REPEAT_PROMPT,
         SamplingParams(temperature=0.0, max_new_tokens=15)),
        ("b", RANDOM_PROMPT,
         SamplingParams(temperature=1.0, seed=5, max_new_tokens=11)),
        ("c", [3, 1, 4, 1, 5, 9, 2, 6] * 4,
         SamplingParams(temperature=0.5, top_k=20, seed=9,
                        max_new_tokens=13)),
    ]
    plain = _run(
        InferenceEngine(_cfg(0), executor=ModelExecutor(_cfg(0))), reqs
    )
    fast = _run(
        InferenceEngine(_cfg(2), executor=ModelExecutor(_cfg(2))), reqs
    )
    for p, f in zip(plain, fast):
        assert f.tokens == p.tokens


def test_spec_mla_family():
    """DeepSeek/MLA family goes through its own prefill_batch_step; the
    verify pass must be exact there too."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    plain = _run(
        InferenceEngine(
            _cfg(0, model="deepseek-tiny"),
            executor=ModelExecutor(_cfg(0, model="deepseek-tiny")),
        ),
        [("r", REPEAT_PROMPT, sp)],
    )
    fast = _run(
        InferenceEngine(
            _cfg(3, model="deepseek-tiny"),
            executor=ModelExecutor(_cfg(3, model="deepseek-tiny")),
        ),
        [("r", REPEAT_PROMPT, sp)],
    )
    assert fast[0].tokens == plain[0].tokens


def test_verify_accepts_oracle_drafts():
    """Feed the verify step drafts equal to the model's own greedy
    continuation: every draft must accept (n_emit == S) and the emitted
    tokens must equal the continuation. Wrong drafts emit exactly one
    corrected token. This pins the acceptance mechanics independent of the
    proposer."""
    ex = ModelExecutor(_cfg(0))
    eng = InferenceEngine(_cfg(0), executor=ex)
    prompt = RANDOM_PROMPT
    c = Collector()
    eng.add_request(
        EngineRequest(
            "r", list(prompt),
            SamplingParams(temperature=0.0, max_new_tokens=6), c,
        )
    )
    for _ in range(12):
        if not eng.has_work():
            break
        eng.step()
    assert c.done
    continuation = c.tokens  # greedy continuation from the plain engine

    # Fresh executor (same seed => same params), prefill the prompt, then
    # one verify step with the oracle continuation as drafts.
    ex2 = ModelExecutor(_cfg(0))
    bs = ex2.block_size
    nb = (len(prompt) + 8 + bs - 1) // bs
    table = np.zeros((ex2.max_blocks_per_seq,), np.int32)
    table[:nb] = np.arange(1, nb + 1)
    first, _ = ex2.prefill(
        np.asarray(prompt, np.int32), 0, table, temperature=0.0
    )
    assert first == continuation[0]

    S = 4
    R = ex2.R
    token_ids = np.zeros((R, S), np.int32)
    token_ids[0, 0] = first
    token_ids[0, 1:] = continuation[1:S]
    positions = np.zeros((R,), np.int32)
    positions[0] = len(prompt)
    tables = np.zeros((R, ex2.max_blocks_per_seq), np.int32)
    tables[0] = table
    active = np.zeros((R,), bool)
    active[0] = True
    batch = SamplingBatch(
        np.zeros((R,), np.float32),
        np.zeros((R,), np.int32),
        np.ones((R,), np.float32),
        np.zeros((R,), np.uint32),
        np.full((R,), 1, np.int32),  # first token already emitted
        np.zeros((R,), np.float32),
        np.zeros((R,), np.float32),
    )
    tokens, _, n_emit = ex2.verify(
        token_ids, positions, tables, active, batch
    )
    assert int(n_emit[0]) == S
    assert list(tokens[0]) == continuation[1: S + 1]

    # Garbage drafts: exactly one (corrected) token, and it's the oracle's.
    ex3 = ModelExecutor(_cfg(0))
    f3, _ = ex3.prefill(
        np.asarray(prompt, np.int32), 0, table, temperature=0.0
    )
    bad = token_ids.copy()
    bad[0, 1:] = [0, 0, 0]
    assert continuation[1] != 0  # the draft really is wrong
    tokens, _, n_emit = ex3.verify(
        bad, positions, tables, active, batch
    )
    assert int(n_emit[0]) == 1
    assert int(tokens[0, 0]) == continuation[1]


def test_propose_drafts_ngram():
    eng = InferenceEngine(_cfg(2), executor=ModelExecutor(_cfg(2)))

    class FakeSeq:
        pass

    s = FakeSeq()
    s.tokens = [5, 6, 7, 8, 5, 6, 7]
    # suffix 3-gram [5, 6, 7] matches at 0 -> followed by [8, 5]
    assert list(eng._propose_drafts(s, 2)) == [8, 5]
    # k beyond history pads with the last followed token
    assert list(eng._propose_drafts(s, 5)) == [8, 5, 6, 7, 7]
    # no repeat anywhere: falls back to repeating the last token
    s.tokens = [1, 2, 3, 4, 5]
    assert list(eng._propose_drafts(s, 2)) == [5, 5]


def test_spec_stop_token_truncates():
    """An EOS inside the accepted run must finish the request at the EOS,
    discarding the rest of the accepted tokens — same final stream as the
    plain engine."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=40)
    plain_eng = InferenceEngine(
        _cfg(0), executor=ModelExecutor(_cfg(0))
    )
    plain = _run(plain_eng, [("r", REPEAT_PROMPT, sp)])
    # pick the 5th generated token as a stop token: the plain run stops
    # right there, and the speculative run must match even if its verify
    # step accepted past it.
    stop_tok = plain[0].tokens[5]
    sp2 = SamplingParams(
        temperature=0.0, max_new_tokens=40, stop_token_ids=(stop_tok,)
    )
    p2 = _run(
        InferenceEngine(_cfg(0), executor=ModelExecutor(_cfg(0))),
        [("r", REPEAT_PROMPT, sp2)],
    )
    f2 = _run(
        InferenceEngine(_cfg(3), executor=ModelExecutor(_cfg(3))),
        [("r", REPEAT_PROMPT, sp2)],
    )
    assert f2[0].tokens == p2[0].tokens
    assert f2[0].tokens[-1] == stop_tok
