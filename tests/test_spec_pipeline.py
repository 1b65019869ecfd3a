"""Composed fast paths (ISSUE 13, docs/ENGINE_PIPELINE.md): seeded
differential proof that speculative + guided decoding INSIDE the
overlapped mixed pipeline emits BYTE-IDENTICAL token streams to
the sync+split verify engine — the pre-ISSUE-13 configuration — across
greedy and seeded sampling, guided and unguided, accept-heavy /
reject-heavy / mixed-acceptance workloads, cancels and preemptions
mid-verify, plus the depth routing and the live mid-run depth flip
(flush-at-transition). Both engines build from the
same init_seed, so any stream divergence is a pipeline bug, not weight
noise. The soundness argument under test: point-mass speculative
acceptance makes the emitted stream draft-independent, so the pipelined
dispatch may propose drafts from one-step-stale host history while the
verify inputs (last accepted token, position, step base) are gathered
on-device from the in-flight step's variable accepted counts."""

import numpy as np

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor


def _cfg(composed=True, spec=3, **kw):
    """composed=True: the default engine (overlap + mixed + spec
    pipeline). composed=False: the sync+split verify twin."""
    base = dict(
        model="llama3-tiny",
        dtype="float32",
        block_size=16,
        num_blocks=96,
        max_running_requests=4,
        max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
        speculative_tokens=spec,
        sync_engine=not composed,
        enable_mixed_step=composed,
    )
    base.update(kw)
    return EngineConfig(**base)


def _mk(composed, eos=(), **kw):
    cfg = _cfg(composed, **kw)
    return InferenceEngine(
        cfg, executor=ModelExecutor(cfg, init_seed=0), eos_token_ids=eos
    )


class C:
    def __init__(self, reject_after=None):
        self.tokens = []
        self.done = False
        self.cancelled = False
        self.reject_after = reject_after

    def __call__(self, out):
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
        if out.finished:
            self.done = True
            self.cancelled = bool(out.cancelled)
            return True
        if (
            self.reject_after is not None
            and len(self.tokens) >= self.reject_after
        ):
            return False
        return True


def _drive(eng, max_steps=3000):
    for _ in range(max_steps):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()
    assert eng._inflight is None  # pipeline fully drained


# Accept-heavy history (short period repeats -> n-gram hits), pure-random
# (drafts nearly always reject), and a mixed-acceptance middle ground.
ACCEPT_PROMPT = [7, 11, 13, 17] * 8
REJECT_PROMPT = list(np.random.RandomState(42).randint(0, 500, size=29))
MIXED_PROMPT = [3, 1, 4, 1, 5, 9, 2, 6] * 4


def _add_mixed(eng, tag=""):
    """Deterministic mixed workload over the acceptance spectrum:
    greedy + seeded-sampled + penalties + bias/min_p, with a staggered
    second wave landing mid-decode (its prefill chunks ride the fused
    verify dispatch on the composed engine)."""
    rng = np.random.RandomState(7)
    cols = {}
    specs = [
        ("accept", ACCEPT_PROMPT,
         SamplingParams(temperature=0.0, max_new_tokens=18)),
        ("reject", REJECT_PROMPT,
         SamplingParams(temperature=0.9, top_k=20, seed=7,
                        max_new_tokens=12)),
        ("mixedacc", MIXED_PROMPT,
         SamplingParams(temperature=0.5, top_k=20, seed=9,
                        max_new_tokens=13, presence_penalty=0.5,
                        frequency_penalty=0.3)),
        ("biased", list(rng.randint(0, 500, size=23)),
         SamplingParams(temperature=0.0, max_new_tokens=7,
                        logit_bias=((5, 4.0), (9, -2.0)), min_p=0.05)),
    ]
    for name, prompt, sp in specs:
        c = C()
        cols[name] = c
        eng.add_request(EngineRequest(f"{tag}{name}", list(prompt), sp, c))
    for _ in range(3):  # second wave lands mid-decode, deterministically
        eng.step()
    c = C()
    cols["late"] = c
    eng.add_request(EngineRequest(
        f"{tag}late", list(rng.randint(0, 500, size=31)),
        SamplingParams(temperature=0.7, seed=3, max_new_tokens=8), c,
    ))
    return cols


def test_composed_matches_sync_split_accept_fuzz():
    """overlap+spec+mixed ≡ sync+spec+split across accept-all /
    reject-all / mixed-accept workloads, greedy + seeded + penalized +
    biased — and the composed engine actually composed (overlapped
    verify dispatches, fused prefill rows, zero sync verify steps)."""
    out = {}
    for composed in (False, True):
        eng = _mk(composed)
        cols = _add_mixed(eng)
        _drive(eng)
        assert all(c.done for c in cols.values())
        out[composed] = {k: c.tokens for k, c in cols.items()}
        if composed:
            assert eng.overlap_steps > 0
            assert eng.spec_pipeline_steps > 0
            assert eng.spec_sync_steps == 0
            assert eng.mixed_steps > 0  # wave-2 chunks fused with verify
            assert eng.spec_tokens_emitted >= eng.spec_slot_steps
        else:
            assert eng.spec_pipeline_steps == 0
            assert eng.spec_sync_steps > 0
    assert out[True] == out[False]


def test_composed_matches_sync_split_guided():
    """Guided (json) + unguided sequences concurrently, greedy and
    seeded: guided slots ride the pipeline HOST-PACED (per-slot, exact
    automaton masks) instead of flushing the engine, and the streams
    stay byte-identical to the sync+split twin."""
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    out = {}
    for composed in (False, True):
        eng = _mk(composed, eos=(2,))
        tok = ByteTokenizer()
        tb = tok.token_bytes_table(eng.executor.cfg.vocab_size)
        eng.set_guided_context(json_fsm.token_mask_table(tb, [2]), tb,
                               eos_ids=[2])
        cols = {}
        rng = np.random.RandomState(5)
        for i, guided in enumerate([None, "json", "json", None]):
            c = C()
            cols[i] = c
            eng.add_request(EngineRequest(
                f"g{i}", list(rng.randint(1, 500, size=11 + 3 * i)),
                SamplingParams(
                    temperature=0.8 if i % 2 else 0.0, seed=i,
                    max_new_tokens=10,
                ),
                c, guided=guided,
            ))
        _drive(eng)
        assert all(c.done for c in cols.values())
        out[composed] = {k: c.tokens for k, c in cols.items()}
        if composed:
            # The pipeline stayed up while guided slots were live: masks
            # applied in-graph, the per-slot pacing fallback engaged,
            # and no engine-wide sync step ran.
            assert eng.overlap_steps > 0
            assert eng.guided_ingraph_steps > 0
            assert eng.guided_paced_skips > 0
            assert eng.spec_sync_steps == 0
    assert out[True] == out[False]


def test_composed_matches_sync_split_cancel_mid_verify():
    out = {}
    for composed in (False, True):
        eng = _mk(composed)
        keep, cancelled = C(), C(reject_after=3)
        eng.add_request(EngineRequest(
            "keep", list(ACCEPT_PROMPT),
            SamplingParams(temperature=0.0, max_new_tokens=12), keep,
        ))
        eng.add_request(EngineRequest(
            "cxl", list(REJECT_PROMPT),
            SamplingParams(temperature=0.6, seed=4, max_new_tokens=40),
            cancelled,
        ))
        _drive(eng)
        assert keep.done and cancelled.done and cancelled.cancelled
        out[composed] = (keep.tokens, cancelled.tokens)
        if composed:
            # the cancel was discovered one step late at least once
            assert eng.late_stop_discards >= 1
    assert out[True] == out[False]


def test_composed_matches_sync_split_preemption_mid_verify():
    out = {}
    for composed in (False, True):
        # Tiny pool forces recompute-preemption mid-decode; the composed
        # engine's 2S-wide capacity pass preempts under the same rules.
        eng = _mk(composed, num_blocks=8, max_running_requests=2,
                  max_seq_len=96)
        rng = np.random.RandomState(4)
        cols = [C(), C()]
        for i, c in enumerate(cols):
            eng.add_request(EngineRequest(
                f"pr{i}", list(rng.randint(0, 500, size=20)),
                SamplingParams(temperature=0.0, max_new_tokens=40), c,
            ))
        _drive(eng)
        assert all(c.done for c in cols)
        assert eng.preemptions > 0  # the path under test actually ran
        out[composed] = [c.tokens for c in cols]
        assert all(len(t) == 40 for t in out[composed])
    assert out[True] == out[False]


def test_composed_matches_sync_split_stop_token():
    """A stop token inside an ACCEPTED run truncates identically on
    both paths (over-emission past the stop is a late-stop discard on
    the composed engine)."""
    probe = _mk(False)
    c = C()
    probe.add_request(EngineRequest(
        "probe", list(ACCEPT_PROMPT),
        SamplingParams(temperature=0.0, max_new_tokens=40), c,
    ))
    _drive(probe)
    stop_tok = c.tokens[5]
    out = {}
    for composed in (False, True):
        eng = _mk(composed)
        c = C()
        eng.add_request(EngineRequest(
            "stopped", list(ACCEPT_PROMPT),
            SamplingParams(
                temperature=0.0, max_new_tokens=40,
                stop_token_ids=(stop_tok,),
            ),
            c,
        ))
        _drive(eng)
        assert c.done
        out[composed] = c.tokens
    assert out[True] == out[False]
    assert out[True][-1] == stop_tok


# ------------------------------------------------------------- hatches


def test_spec_pipeline_hatch_routing():
    """sync_engine=True degrades a composed config to depth-0 verify
    stepping; the decision is LIVE (read per step from the config, no
    engine restart) and takes the fused prefill with it."""
    eng = _mk(True)
    assert not eng._force_sync and eng.mixed_step_enabled
    eng.cfg.sync_engine = True
    assert eng._force_sync and not eng.mixed_step_enabled
    eng.cfg.sync_engine = False
    assert not eng._force_sync
    eng2 = _mk(True, sync_engine=True)
    assert eng2._force_sync
    eng2.cfg.sync_engine = False
    assert not eng2._force_sync and eng2.mixed_step_enabled
    # the split builder keeps the pipeline: depth and fusing are apart
    eng3 = _mk(True, enable_mixed_step=False)
    assert not eng3._force_sync and not eng3.mixed_step_enabled


def test_live_hatch_flip_flushes_and_stays_exact():
    """Satellite: flip sync_engine mid-run on a composed engine — the
    in-flight step is flushed at the transition (the flush-at-
    transition path), the stream completes byte-identical to an
    all-sync run, and flipping back re-engages the pipeline."""
    ref = _mk(False)
    c = C()
    ref.add_request(EngineRequest(
        "r", list(MIXED_PROMPT),
        SamplingParams(temperature=0.7, seed=11, max_new_tokens=24), c,
    ))
    _drive(ref)

    eng = _mk(True)
    c2 = C()
    eng.add_request(EngineRequest(
        "r", list(MIXED_PROMPT),
        SamplingParams(temperature=0.7, seed=11, max_new_tokens=24), c2,
    ))
    for _ in range(4):
        eng.step()
    assert eng._inflight is not None  # pipeline engaged
    eng.cfg.sync_engine = True
    eng.step()  # transition iteration: flushes, then steps at depth 0
    assert eng._inflight is None
    sync_steps_mid = eng.spec_sync_steps
    assert sync_steps_mid > 0
    eng.step()
    eng.cfg.sync_engine = False
    pipe_before = eng.spec_pipeline_steps
    _drive(eng)
    assert eng.spec_pipeline_steps > pipe_before  # pipeline re-engaged
    assert c2.done
    assert c2.tokens == c.tokens


# ------------------------------------- plain (non-spec) guided overlap


def test_guided_rides_overlap_pipeline_no_flush():
    """Non-speculative engines: a live guided sequence no longer forces
    engine-wide sync — unguided slots keep overlapping at full rate,
    guided slots run host-paced, streams match the sync twin
    byte-for-byte (extends tests/test_async_engine.py's guided
    differential, which predates the per-slot rule)."""
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    out = {}
    for composed in (False, True):
        eng = _mk(composed, spec=0, eos=(2,))
        tok = ByteTokenizer()
        tb = tok.token_bytes_table(eng.executor.cfg.vocab_size)
        eng.set_guided_context(json_fsm.token_mask_table(tb, [2]), tb,
                               eos_ids=[2])
        cols = {}
        rng = np.random.RandomState(9)
        for i, guided in enumerate(["json", None, None]):
            c = C()
            cols[i] = c
            eng.add_request(EngineRequest(
                f"q{i}", list(rng.randint(1, 500, size=13 + 2 * i)),
                SamplingParams(
                    temperature=0.6 if i % 2 else 0.0, seed=i + 1,
                    max_new_tokens=12,
                ),
                c, guided=guided,
            ))
        _drive(eng)
        assert all(c.done for c in cols.values())
        out[composed] = {k: c.tokens for k, c in cols.items()}
        if composed:
            assert eng.overlap_steps > 0
            assert eng.guided_ingraph_steps > 0
            assert eng.guided_paced_skips > 0
    assert out[True] == out[False]


def test_guided_schema_rides_pipeline():
    """json_schema (dynamic mask rows) through the composed pipeline:
    host-paced slots derive exact schema states, dynamic rows flush
    through the staged-write path, streams match sync+split."""
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    schema = {"type": "object", "properties": {"a": {"type": "integer"}},
              "required": ["a"], "additionalProperties": False}
    out = {}
    for composed in (False, True):
        eng = _mk(composed, eos=(2,))
        tok = ByteTokenizer()
        tb = tok.token_bytes_table(eng.executor.cfg.vocab_size)
        eng.set_guided_context(json_fsm.token_mask_table(tb, [2]), tb,
                               eos_ids=[2])
        c = C()
        eng.add_request(EngineRequest(
            "s", list(np.random.RandomState(3).randint(1, 500, size=15)),
            SamplingParams(temperature=0.0, max_new_tokens=14), c,
            guided="json_schema", schema=schema,
        ))
        _drive(eng)
        assert c.done
        out[composed] = c.tokens
    assert out[True] == out[False]


# ---------------------------------------- the kernel pair (interpret)


def test_spec_mixed_ragged_kernel_interpret(monkeypatch):
    """Verify rows REALLY are prefill-shaped rows (q_len = k+1): the
    composed engine's fused verify+prefill dispatch routes them through
    the multi-query kernel and the chunks through the flash kernel
    (interpret mode, the `_interpret` seam) on the one kernel-eligible
    tiny geometry, and the greedy stream matches the reference-path
    composed engine (same builder, blockwise attention)."""
    def cfg():
        return _cfg(True, model="llama3-packed-tiny")

    def run():
        eng = InferenceEngine(
            cfg(), executor=ModelExecutor(cfg(), init_seed=11)
        )
        c = C()
        eng.add_request(EngineRequest(
            "r", list(ACCEPT_PROMPT),
            SamplingParams(temperature=0.0, max_new_tokens=16), c,
        ))
        c2 = C()
        eng.add_request(EngineRequest(
            "r2", list(MIXED_PROMPT),
            SamplingParams(temperature=0.0, max_new_tokens=10), c2,
        ))
        _drive(eng)
        assert c.done and c2.done
        return (c.tokens, c2.tokens), eng

    monkeypatch.setenv("XLLM_PACKED_KV_KERNEL", "1")
    ref, _ = run()
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    got, eng = run()
    (routes,) = eng.executor._attention_routes()
    assert routes.verify and routes.prefill and routes.interpret
    assert eng.spec_pipeline_steps > 0
    assert got == ref


def test_propose_drafts_index_incremental():
    """The rolling-suffix index proposes the same drafts the legacy
    sliding-window scan did, and extends incrementally as the sequence
    grows (satellite: O(ngram_max) per step)."""
    eng = _mk(True)

    class FakeSeq:
        pass

    s = FakeSeq()
    s.tokens = [5, 6, 7, 8, 5, 6, 7]
    assert list(eng._propose_drafts(s, 2)) == [8, 5]
    # Incremental growth: appending tokens extends the index; the newest
    # suffix matches the now-registered earlier occurrence.
    s.tokens = s.tokens + [8, 5]
    assert list(eng._propose_drafts(s, 3)) == [6, 7, 8]
    # The index covers ends only up to len-2: the suffix never matches
    # itself even after repeated calls on the same history.
    assert list(eng._propose_drafts(s, 3)) == [6, 7, 8]
