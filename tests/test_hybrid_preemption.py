"""Hybrid online/offline scheduling at the ENGINE level (north-star
config 5; the reference carries an `offline` flag it never consumes —
request.h:38): an online burst preempts RUNNING offline decodes
(recompute-style) instead of queueing behind them, and the offline work
resumes and completes once the burst drains. Every case runs for a
paged-cache family and for a family with a state slot BESIDE its K/V
blocks (`solar-tiny`: a preempted sequence gives back its slot and its
blocks, and recomputes its state when it resumes) and for a family with a
SECOND paged pool (`mimo-tiny`: a preempted sequence gives back the blocks
of both) and for a family whose EVERY layer holds a state slot and K/V
blocks (`falcon-h1-tiny`) and for a family whose sparse layers keep
compressed-key rows beside their K/V blocks and whose other layers hold a
lightning state slot (`minicpm-sala-tiny`: its sequences run past
dense_len, so a resumed one selects its pages again) and for a window family
whose window is SEVERAL blocks and whose two kinds differ in query heads
(`laguna-tiny`: a preempted sequence gives back up to four window blocks)."""

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor


MODELS = ["llama3-tiny", "solar-tiny", "mimo-tiny", "falcon-h1-tiny", "minicpm-sala-tiny",
          "laguna-tiny"]


def _engine(model, R=4, num_blocks=64):
    cfg = EngineConfig(
        # (the sparse family's page is its selection's block)
        # (... and laguna-tiny's window of 24 is three blocks of 8)
        model=model, dtype="float32",
        block_size=8 if model in ("minicpm-sala-tiny", "laguna-tiny") else 16,
        num_blocks=num_blocks, max_running_requests=R, max_seq_len=256,
        prefill_buckets=[32, 64, 128],
        # a state family's engines step synchronously, as tests/test_granite.py's
        sync_engine=model != "llama3-tiny",
    )
    return InferenceEngine(cfg, executor=ModelExecutor(cfg))


def _all_given_back(eng):
    """No row, no state slot and no block is held (a state family's rows
    ARE its slots)."""
    assert len(eng._free_slots) == eng.R
    assert eng.block_mgr.num_referenced_blocks == 0
    window = getattr(eng.block_mgr, "window", None)  # a window family's second pool
    assert window is None or window.num_referenced_blocks == 0


def _req(rid, outs, offline=False, max_new=64, prompt=None):
    def cb(o):
        for s in o.outputs:
            outs.setdefault(rid, []).extend(s.token_ids)
        if o.finished:
            outs.setdefault("_finished", []).append(rid)
        return True

    rng = np.random.default_rng(abs(hash(rid)) % 2**32)
    return EngineRequest(
        request_id=rid,
        prompt_token_ids=list(prompt or rng.integers(1, 400, 12)),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new),
        callback=cb,
        offline=offline,
    )


@pytest.mark.parametrize("model", MODELS)
def test_online_burst_preempts_running_offline(model):
    """Fill every slot with long offline decodes, then burst online work:
    online requests get slots via preemption (first tokens within a few
    steps, NOT after the offline work drains), and the preempted offline
    sequences resume and run to completion afterwards."""
    eng = _engine(model, R=4)
    outs = {}
    for i in range(4):
        eng.add_request(_req(f"off{i}", outs, offline=True, max_new=60))
    # let the offline work occupy all slots and decode a while
    for _ in range(10):
        eng.step()
    assert len(eng._running) == 4
    assert all(s.req.offline for s in eng._running.values())

    for i in range(4):
        eng.add_request(_req(f"on{i}", outs, offline=False, max_new=8))
    steps_to_first = None
    for step in range(1, 200):
        eng.step()
        if steps_to_first is None and all(
            outs.get(f"on{i}") for i in range(4)
        ):
            steps_to_first = step
            break
    # every online request produced a token within a handful of steps —
    # far fewer than the ~50 remaining offline decode steps it would have
    # had to wait without preemption
    assert steps_to_first is not None and steps_to_first <= 6, steps_to_first
    # online work was admitted by evicting offline decodes
    assert any(
        not s.req.offline for s in eng._running.values()
    )

    # drain everything: the preempted offline sequences must resume
    # (recompute path) and complete with their full token budget
    for _ in range(600):
        if not eng.has_work():
            break
        eng.step()
    finished = set(outs.get("_finished", []))
    assert {f"on{i}" for i in range(4)} <= finished
    assert {f"off{i}" for i in range(4)} <= finished
    for i in range(4):
        assert len(outs[f"off{i}"]) == 60, len(outs[f"off{i}"])
    assert eng.preemptions >= 1
    _all_given_back(eng)
    if eng.executor.has_state_pool:
        assert eng.state_recomputes >= 1


@pytest.mark.parametrize("model", MODELS)
def test_preempted_offline_resume_is_exact(model):
    """A preempted-then-resumed offline sequence emits the same greedy
    continuation as an undisturbed run (recompute preserves history)."""
    prompt = list(np.random.default_rng(5).integers(1, 400, 12))

    ref_outs = {}
    eng = _engine(model, R=4)
    eng.add_request(_req("solo", ref_outs, offline=True, max_new=40,
                         prompt=prompt))
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()

    outs = {}
    eng2 = _engine(model, R=4)
    eng2.add_request(_req("victim", outs, offline=True, max_new=40,
                          prompt=prompt))
    for _ in range(6):
        eng2.step()
    # online burst forces preemption of the offline victim
    for i in range(4):
        eng2.add_request(_req(f"b{i}", outs, offline=False, max_new=6))
    for _ in range(400):
        if not eng2.has_work():
            break
        eng2.step()
    assert outs["victim"] == ref_outs["solo"]
    _all_given_back(eng2)


@pytest.mark.parametrize("model", MODELS)
def test_offline_admits_behind_online_queue(model):
    """With both classes waiting, online admits first regardless of
    arrival order."""
    eng = _engine(model, R=1, num_blocks=16)
    outs = {}
    eng.add_request(_req("off", outs, offline=True, max_new=4))
    eng.add_request(_req("on", outs, offline=False, max_new=4))
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()
    fin = outs["_finished"]
    assert fin.index("on") < fin.index("off")