"""Overlapped decode pipeline (docs/ENGINE_PIPELINE.md): seeded
differential proof that the one-step-lookahead engine emits BYTE-IDENTICAL
token streams to the sync_engine=True escape hatch across plain decode,
guided decode, mid-stream cancel, and preemption — plus a race-stress
invariant fuzz in the tests/test_race_stress.py style. Both engines build
from the same init_seed, so any stream divergence is a pipeline bug, not
weight noise."""

import random
import threading
import time

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor


def _cfg(sync, **kw):
    base = dict(
        model="llama3-tiny",
        dtype="float32",
        block_size=16,
        num_blocks=64,
        max_running_requests=4,
        max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
        sync_engine=sync,
    )
    base.update(kw)
    return EngineConfig(**base)


def _mk(sync, eos=(), **kw):
    cfg = _cfg(sync, **kw)
    return InferenceEngine(
        cfg, executor=ModelExecutor(cfg, init_seed=0), eos_token_ids=eos
    )


class C:
    """Stream collector; reject_after=N returns False from the callback
    after N tokens (the deterministic mid-stream cancel path)."""

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


def _add_mixed(eng, tag=""):
    """Deterministic mixed workload: greedy + seeded-sampled + penalties +
    logit_bias + min_p, varying lengths, with a staggered second wave."""
    rng = np.random.RandomState(42)
    cols = {}
    specs = [
        ("greedy", SamplingParams(temperature=0.0, max_new_tokens=9), 23),
        ("sampled", SamplingParams(
            temperature=0.9, top_k=20, seed=7, max_new_tokens=12,
        ), 37),
        ("penalized", SamplingParams(
            temperature=0.8, seed=11, max_new_tokens=10,
            presence_penalty=0.5, frequency_penalty=0.3,
        ), 17),
        ("biased", SamplingParams(
            temperature=0.0, max_new_tokens=7,
            logit_bias=((5, 4.0), (9, -2.0)), min_p=0.05,
        ), 29),
    ]
    for name, sp, plen in specs:
        c = C()
        cols[name] = c
        eng.add_request(EngineRequest(
            f"{tag}{name}", list(rng.randint(0, 500, size=plen)), sp, c,
        ))
    for _ in range(3):  # second wave lands mid-decode, deterministically
        eng.step()
    c = C()
    cols["late"] = c
    eng.add_request(EngineRequest(
        f"{tag}late", list(rng.randint(0, 500, size=31)),
        SamplingParams(temperature=0.7, seed=3, max_new_tokens=8), c,
    ))
    return cols


def test_overlap_matches_sync_plain():
    out = {}
    for sync in (True, False):
        eng = _mk(sync)
        cols = _add_mixed(eng)
        _drive(eng)
        assert all(c.done for c in cols.values())
        out[sync] = {k: c.tokens for k, c in cols.items()}
        if not sync:
            # the pipeline actually engaged: steps dispatched while the
            # previous step was still in flight
            assert eng.overlap_steps > 0
            assert eng.host_gap_steps > 0
    assert out[True] == out[False]


def test_overlap_matches_sync_guided():
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    out = {}
    for sync in (True, False):
        eng = _mk(sync, eos=(2,))
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
        out[sync] = {k: c.tokens for k, c in cols.items()}
    assert out[True] == out[False]


def test_overlap_matches_sync_cancel():
    out = {}
    for sync in (True, False):
        eng = _mk(sync)
        rng = np.random.RandomState(9)
        keep, cancelled = C(), C(reject_after=3)
        eng.add_request(EngineRequest(
            "keep", list(rng.randint(0, 500, size=21)),
            SamplingParams(temperature=0.0, max_new_tokens=10), keep,
        ))
        eng.add_request(EngineRequest(
            "cxl", list(rng.randint(0, 500, size=19)),
            SamplingParams(temperature=0.6, seed=4, max_new_tokens=40),
            cancelled,
        ))
        _drive(eng)
        assert keep.done and cancelled.done and cancelled.cancelled
        out[sync] = (keep.tokens, cancelled.tokens)
    assert out[True] == out[False]


def test_overlap_matches_sync_preemption():
    out = {}
    for sync in (True, False):
        # Tiny pool forces recompute-preemption mid-decode.
        eng = _mk(sync, num_blocks=8, max_running_requests=2,
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
        out[sync] = [c.tokens for c in cols]
        assert all(len(t) == 40 for t in out[sync])
    assert out[True] == out[False]


def test_one_step_late_stop_discards_exactly_the_extra_token():
    """A token-dependent stop (stop_token_ids) is discovered one step late
    in overlap mode: the stream still ends exactly at the stop token and
    the single over-produced in-flight sample is counted as discarded."""
    rng = np.random.RandomState(2)
    prompt = list(rng.randint(0, 500, size=23))

    eng = _mk(True)
    probe = C()
    eng.add_request(EngineRequest(
        "probe", prompt, SamplingParams(temperature=0.0, max_new_tokens=8),
        probe,
    ))
    _drive(eng)
    stop_tok = probe.tokens[4]

    out = {}
    for sync in (True, False):
        eng = _mk(sync)
        c = C()
        eng.add_request(EngineRequest(
            "stopped", prompt,
            SamplingParams(
                temperature=0.0, max_new_tokens=50,
                stop_token_ids=(stop_tok,),
            ),
            c,
        ))
        _drive(eng)
        assert c.done
        out[sync] = c.tokens
        if not sync:
            assert eng.late_stop_discards >= 1
    assert out[True] == out[False]
    assert out[False][-1] == stop_tok
    assert len(out[False]) == 5


_DEPTH_FLAVOURS = {
    "decode": dict(enable_mixed_step=False),
    "mixed": dict(enable_mixed_step=True),
    "speculative": dict(enable_mixed_step=True, speculative_tokens=3),
}


def _handed_over(eng, cols):
    """An owner that takes a step's outputs at the step listener, as the
    instance's push callbacks do: the callbacks only collect (inside a
    step whose end hands over), the listener delivers the list in booking
    order. Returns the callbacks to give the requests."""
    held = []

    def collecting(col):
        def cb(out):
            assert eng.step_open()  # every callback runs inside a step
            held.append((col, out))
            return True

        return cb

    def hand_over():
        batch = held[:]
        del held[:]
        for col, out in batch:
            col(out)

    eng.add_step_listener(hand_over)
    # nothing waits for a later step: empty whenever step() has returned
    step = eng.step
    eng.step = lambda: (step(), held == [] or pytest.fail("held"))[0]
    return [collecting(col) for col in cols]


@pytest.mark.parametrize(
    "handed", [False, True], ids=["callback", "handed-over"]
)
@pytest.mark.parametrize("seeded", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("flavour", sorted(_DEPTH_FLAVOURS))
def test_depth0_and_depth1_emit_the_same_streams(flavour, seeded, handed):
    """The engine has ONE step loop; sync_engine only sets its depth.
    At depth 0 every step is drained before the next dispatch: nothing
    overlaps, nothing is discarded late, and the streams are depth 1's
    (which does discard: the early stop below costs it a sample). An
    owner that collects in its callbacks and delivers at the step
    listener (`handed`) sees the same streams: plain, mixed and
    speculative (several tokens a row) steps, at both depths."""
    kw = _DEPTH_FLAVOURS[flavour]
    rng = np.random.RandomState(5)
    # a repetitive prompt (drafts accept) beside two random ones
    prompts = [
        [7, 8, 9, 10] * 6,
        list(rng.randint(0, 500, size=37)),
        list(rng.randint(0, 500, size=70)),
    ]
    sp = dict(temperature=0.8, top_k=30, seed=13) if seeded else dict(
        temperature=0.0
    )
    ref = _mk(True, **kw)
    c = C()
    ref.add_request(EngineRequest(
        "probe", prompts[1], SamplingParams(max_new_tokens=12, **sp), c,
    ))
    _drive(ref)
    stop_tok = c.tokens[5]  # a token-dependent stop, mid-stream

    streams = {}
    for sync in (True, False):
        eng = _mk(sync, **kw)
        cols = [C() for _ in prompts]
        cbs = _handed_over(eng, cols) if handed else cols
        for i, prompt in enumerate(prompts):
            eng.add_request(EngineRequest(
                f"r{i}", list(prompt),
                SamplingParams(
                    max_new_tokens=10 + 4 * i,
                    stop_token_ids=(stop_tok,) if i == 1 else (),
                    **sp,
                ),
                cbs[i],
            ))
            eng.step()  # staggered: later prompts land beside decode rows
        _drive(eng)
        assert all(col.done for col in cols)
        streams[sync] = [col.tokens for col in cols]
        if sync:
            assert eng.late_stop_discards == 0
            assert eng.overlap_steps == 0
            assert eng.mixed_steps == 0  # depth 0 prefills split
            assert eng.spec_pipeline_steps == 0
            assert (eng.spec_sync_steps > 0) == (flavour == "speculative")
        else:
            assert eng.overlap_steps > 0
            assert eng.late_stop_discards >= 1
            assert eng.spec_sync_steps == 0
            assert (eng.mixed_steps > 0) == (flavour != "decode")
    assert streams[True] == streams[False]
    assert streams[True][1][-1] == stop_tok


def test_sync_escape_hatch_env():
    """sync_engine=True forces depth-0 stepping over a default engine,
    live (and False re-engages the pipeline over a sync_engine=True
    one)."""
    eng = _mk(False)
    assert not eng._force_sync
    eng.cfg.sync_engine = True
    assert eng._force_sync
    eng = _mk(True)
    assert eng.cfg.sync_engine and eng._force_sync
    eng.cfg.sync_engine = False
    assert not eng._force_sync


def test_engine_loop_runs_below_a_frame_that_reserves_its_chunk():
    """The loop's frames (and JAX's, traced and lowered from it) must not
    straddle CPython's 16 KiB frame chunks: _loop enters through a frame
    whose declared operand stack makes the interpreter open one chunk
    big enough for all of them (engine._on_roomy_stack)."""
    import sys

    eng = _mk(False)
    seen = []

    def probe():
        f = sys._getframe()
        while f is not None:
            seen.append((f.f_code.co_name, f.f_code.co_stacksize))
            f = f.f_back

    eng._loop_owned = probe
    eng._loop()
    names = [n for n, _ in seen]
    assert names[:3] == ["probe", "_on_roomy_stack", "_loop"]
    assert dict(seen)["_on_roomy_stack"] >= 1 << 16


def test_async_engine_fuzz_invariants():
    """tests/test_race_stress.py-style invariant fuzz against the
    overlapped (default) engine: racing add/cancel/callback-rejection from
    client threads, tight pool. After drain: every request terminal, all
    block refcounts zero, all slots free, no in-flight step left."""
    cfg = _cfg(False, num_blocks=48, max_running_requests=4,
               max_seq_len=128, prefill_buckets=[32, 64, 128])
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=7))
    eng.start()
    rng = random.Random(123)
    np_rng = np.random.default_rng(123)
    trackers = []

    class T:
        def __init__(self, rid, cancel_after=None):
            self.rid = rid
            self.lock = threading.Lock()
            self.n = 0
            self.terminal = None
            self.post_terminal = 0
            self.cancel_after = cancel_after
            self.done = threading.Event()

        def __call__(self, out):
            with self.lock:
                if self.terminal is not None:
                    self.post_terminal += 1
                    return False
                for so in out.outputs:
                    self.n += len(so.token_ids)
                if out.finished:
                    self.terminal = "done"
                    self.done.set()
                    return True
                if self.cancel_after is not None and self.n >= self.cancel_after:
                    eng.cancel(self.rid)
            return True

    try:
        def client(base):
            for i in range(8):
                rid = f"af-c{base}-{i}"
                kind = rng.random()
                t = T(rid, 2 if kind < 0.25 else None)
                trackers.append(t)
                eng.add_request(EngineRequest(
                    request_id=rid,
                    prompt_token_ids=np_rng.integers(
                        1, 500, (int(np_rng.integers(3, 90)),)
                    ).tolist(),
                    sampling=SamplingParams(
                        temperature=rng.choice([0.0, 0.8]),
                        seed=rng.randrange(2**31),
                        max_new_tokens=int(np_rng.integers(1, 10)),
                    ),
                    callback=t,
                ))
                if kind > 0.85:
                    time.sleep(rng.random() * 0.02)
                    eng.cancel(rid)
                time.sleep(rng.random() * 0.01)

        threads = [
            threading.Thread(target=client, args=(b,)) for b in range(3)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        deadline = time.monotonic() + 120
        for t in trackers:
            assert t.done.wait(max(0.1, deadline - time.monotonic())), (
                f"request {t.rid} never reached a terminal state"
            )
        # Let the loop retire the trailing in-flight step.
        deadline = time.monotonic() + 10
        while eng.has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()

    for t in trackers:
        assert t.post_terminal == 0, t.rid
    bm = eng.block_mgr
    assert bm.num_referenced_blocks == 0
    assert bm.num_free_blocks == bm.num_blocks - 1
    assert not eng._running
    assert len(eng._free_slots) == cfg.max_running_requests
    assert not eng._waiting
    assert eng._inflight is None
