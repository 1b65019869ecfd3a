"""One hand-over a step (docs/ENGINE_PIPELINE.md "The drain"): the real
InferenceEngine (tiny model, CPU) behind an InstanceServer. The served push
callbacks only collect while a step books its rows; the engine's step
listener hands the step's outputs to the push queue as ONE list. Steps are
driven inline here, and nothing drains the push queue: a test reads it.
"""

import queue
import threading

import pytest

from xllm_service_tpu.api.fake_engine import FakeEngine
from xllm_service_tpu.api.instance import InstanceServer
from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.common.types import RequestOutput, SequenceOutput
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest

from tests._step_engine import StepEngine

ROWS = 4


def _instance(**kw):
    cfg = dict(
        model="llama3-tiny", dtype="float32", block_size=16, num_blocks=64,
        max_running_requests=ROWS, max_seq_len=128, prefill_buckets=[32],
        instance_name="handover0", instance_type="MIX",
    )
    cfg.update(kw)
    return InstanceServer(EngineConfig(**cfg))


def _serve(inst, i, max_new=12, wrap=None, prompt_len=9):
    """One forwarded request, as `_handle_forwarded` registers it."""
    srid, rid = f"srid{i}", f"rid{i}"
    with inst._srid_mu:
        inst._srid_map[srid] = [rid]
    inst._srid_track(srid, prompt_len, 0)
    cb = inst._make_push_callback(srid)
    inst.engine.add_request(EngineRequest(
        request_id=rid,
        prompt_token_ids=[3 + (7 * i + j) % 200 for j in range(prompt_len)],
        sampling=SamplingParams(
            temperature=0.0, max_new_tokens=max_new, ignore_eos=True
        ),
        callback=wrap(cb) if wrap else cb,
    ))
    return srid


def _pushed(inst):
    """The push queue's items so far (each a list of outputs)."""
    items = []
    while True:
        try:
            items.append(inst._push_q.get_nowait())
        except queue.Empty:
            return items


def _drive(inst, streams, max_steps=400):
    """Step until idle; after EVERY step nothing is left collected.
    Appends what was pushed to `streams` {srid: [outputs]}; returns the
    pushed items step by step."""
    eng, by_step = inst.engine, []
    for _ in range(max_steps):
        if not eng.has_work():
            break
        eng.step()
        assert inst._pending_outs == []
        items = _pushed(inst)
        by_step.append(items)
        for item in items:
            for out in item:
                streams.setdefault(out.service_request_id, []).append(out)
    assert not eng.has_work()
    return by_step


def _check_whole(outs, n_tokens):
    """One output a token, in order, the finished one last and only."""
    assert [o.usage.num_generated_tokens for o in outs] == list(
        range(1, n_tokens + 1)
    )
    assert all(len(o.outputs[0].token_ids) == 1 for o in outs)
    assert [o.finished for o in outs] == [False] * (n_tokens - 1) + [True]
    assert all(isinstance(o.outputs[0].text, str) for o in outs)


def test_a_step_of_r_rows_is_r_callbacks_one_listener_call_one_put():
    inst = _instance()
    eng = inst.engine
    seen = []  # what a wrapper around the callback sees, when

    def tap(cb):
        def on_output(out):
            # at booking time: before the hand-over, with ids and finished
            seen.append((
                out.request_id, list(out.outputs[0].token_ids),
                out.finished, len(inst._pending_outs), inst._push_q.qsize(),
            ))
            return cb(out)

        return on_output

    calls = []
    eng.add_step_listener(lambda: calls.append(len(inst._pending_outs)))
    srids = [_serve(inst, i, max_new=10, wrap=tap) for i in range(ROWS)]
    plain = []  # a request whose callback is a plain function: untouched
    eng.add_request(EngineRequest(
        request_id="plain",
        prompt_token_ids=[5, 6, 7],
        sampling=SamplingParams(
            temperature=0.0, max_new_tokens=30, ignore_eos=True
        ),
        callback=lambda out: plain.append(
            (out, len(inst._pending_outs))
        ) or True,
    ))
    streams = {}
    by_step = _drive(inst, streams)
    for srid in srids:
        _check_whole(streams[srid], 10)
    # the plain request never rode the hand-over, and saw every output
    assert "plain" not in {o.request_id for outs in streams.values()
                           for o in outs}
    assert len(plain) == 4 + 26  # 4 slots: it waited for one, then ran
    # every step put at most ONE item; a step of R decode rows put R
    # outputs in booking order (the slots' order in the step)
    assert all(len(items) <= 1 for items in by_step)
    full = [items[0] for items in by_step if items and len(items[0]) == ROWS]
    assert len(full) >= 5
    for item in full:
        assert len({o.request_id for o in item}) == ROWS
    # the wrapper saw every output at its row's booking, while nothing of
    # the step had left: the collected list grew 0..R-1 under it and the
    # queue (which this test drains after each step) stayed empty
    assert {s[3] for s in seen} == set(range(ROWS))
    assert all(s[4] == 0 for s in seen)
    assert len(seen) == ROWS * 10
    assert all(len(s[1]) == 1 for s in seen)
    # the listener ran once a step that emitted, with the step's outputs
    # still collected (this second listener runs after the instance's)
    assert calls and all(c == 0 for c in calls)
    pushes = sum(len(items) for items in by_step)
    assert len(calls) >= pushes
    # the histogram: one observation a hand-over, the outputs its value
    m = inst._metrics_body()
    count = next(float(line.split()[-1]) for line in m.splitlines()
                 if line.startswith("xllm_engine_handover_outputs_count"))
    total = next(float(line.split()[-1]) for line in m.splitlines()
                 if line.startswith("xllm_engine_handover_outputs_sum"))
    assert count == pushes and total == ROWS * 10


def test_booking_order_within_the_step_is_the_list_order():
    inst = _instance()
    order = []

    def tap(cb):
        def on_output(out):
            order.append(id(out))
            return cb(out)

        return on_output

    for i in range(ROWS):
        _serve(inst, i, max_new=6, wrap=tap)
    pushed = []
    for items in _drive(inst, {}):
        for item in items:
            pushed.extend(id(o) for o in item)
    assert pushed == order and len(order) == ROWS * 6


@pytest.mark.parametrize("what", [
    "finish", "engine_cancel", "callback_false", "reject",
    "detokenizer_raises", "listener_raises",
])
def test_a_disturbance_in_mid_step_leaves_the_other_rows_whole(what):
    inst = _instance()
    eng = inst.engine
    free_slots, free_blocks = len(eng._free_slots), eng.block_mgr.num_free_blocks
    n = 14
    wrap = {}
    if what == "callback_false":
        def cancelling(cb):
            count = []

            def on_output(out):
                count.append(1)
                cb(out)
                return len(count) < 4  # the tap cancels at its 4th output

            return on_output

        wrap[1] = cancelling
    srids = [
        _serve(inst, i, max_new=5 if (what == "finish" and i == 1) else n,
               wrap=wrap.get(i))
        for i in range(ROWS)
    ]
    if what == "detokenizer_raises":
        tok = inst.tokenizer

        class Raising:
            def decode(self, ids, skip_special_tokens=True):
                if armed:
                    armed.pop()
                    raise RuntimeError("detokenizer fault")
                return tok.decode(ids, skip_special_tokens)

            def __getattr__(self, name):
                return getattr(tok, name)

        armed = []
        inst.tokenizer = Raising()
    if what == "listener_raises":
        boom = []

        def raising_listener():
            if eng.decode_dispatches == 5 and not boom:
                boom.append(1)
                raise RuntimeError("listener fault")

        eng.add_step_listener(raising_listener)
    streams = {}
    for _ in range(6):
        eng.step()
        assert inst._pending_outs == []
    for item in _pushed(inst):
        for out in item:
            streams.setdefault(out.service_request_id, []).append(out)
    if what == "engine_cancel":
        eng.cancel("rid1")
    if what == "reject":
        with inst._srid_mu:
            inst._srid_map["srid-long"] = ["rid-long"]
        eng.add_request(EngineRequest(
            request_id="rid-long", prompt_token_ids=[4] * 200,
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
            callback=inst._make_push_callback("srid-long"),
        ))
        eng.step()  # the reject leaves with THIS step's outputs
        assert inst._pending_outs == []
        items = _pushed(inst)
        rej = [o for item in items for o in item
               if o.service_request_id == "srid-long"]
        assert len(rej) == 1 and rej[0].finished and not rej[0].status.ok()
        for item in items:
            for out in item:
                if out.service_request_id != "srid-long":
                    streams.setdefault(out.service_request_id, []).append(out)
    if what == "detokenizer_raises":
        armed.append(1)  # the next decode raises: one row's, in mid-list
    _drive(inst, streams)
    victim = None if what in ("reject", "listener_raises") else "srid1"
    if what == "detokenizer_raises":
        assert not armed  # it did raise
        # whichever row's text was decoded next is the victim
        victim = next(
            s for s in srids if not streams[s][-1].status.ok()
        )
    for srid in srids:
        if srid != victim:
            _check_whole(streams[srid], n)
    if what == "finish":
        _check_whole(streams["srid1"], 5)
    elif what in ("engine_cancel", "callback_false"):
        outs = streams["srid1"]
        assert outs[-1].finished and outs[-1].cancelled
        assert not any(o.finished for o in outs[:-1])
        assert 3 <= len(outs) - 1 < n
    elif what == "detokenizer_raises":
        outs = streams[victim]
        failed = [o for o in outs if not o.status.ok() and not o.cancelled]
        assert len(failed) == 1 and failed[0].finished
        assert len(outs) < n  # it did not run on
    elif what == "listener_raises":
        assert boom  # it raised, the loop lived, every stream is whole
    # slot and blocks back, and the manifest reaped, once all is done
    assert len(eng._free_slots) == free_slots
    assert eng.block_mgr.num_free_blocks == free_blocks
    assert inst._srid_map == {} and inst._srid_info == {}
    assert inst._pending_outs == []


def test_a_freed_slot_is_there_for_the_next_admission():
    """A finish in mid-step frees slot and blocks at its row's booking,
    before the hand-over and before the next step admits."""
    inst = _instance()
    eng = inst.engine
    for i in range(ROWS):
        _serve(inst, i, max_new=4 if i == 2 else 40)
    _serve(inst, 9, max_new=3)  # waits: every slot is taken
    streams = {}
    for _ in range(12):
        eng.step()
        for item in _pushed(inst):
            for out in item:
                streams.setdefault(out.service_request_id, []).append(out)
        if "srid9" in streams:
            break
    assert streams["srid2"][-1].finished
    assert "srid9" in streams  # admitted as soon as srid2's slot was free
    _drive(inst, streams)
    _check_whole(streams["srid9"], 3)


@pytest.mark.parametrize("engine", ["fake", "step"])
def test_an_engine_that_marks_no_boundary_pushes_at_once(engine):
    eng = FakeEngine() if engine == "fake" else StepEngine(steps=3)
    inst = InstanceServer(
        EngineConfig(model="llama3-tiny", instance_name=f"nb-{engine}"),
        engine=eng,
    )
    assert inst._step_open is None
    cb = inst._make_push_callback("s0")
    out = RequestOutput(
        request_id="r0", outputs=[SequenceOutput(token_ids=[70, 71])]
    )
    assert cb(out) is True
    assert _pushed(inst) == [[out]] and inst._pending_outs == []
    assert out.service_request_id == "s0" and out.outputs[0].text == "CD"


def test_a_callback_from_another_thread_pushes_at_once():
    """Only the stepping thread, inside a step, collects."""
    inst = _instance()
    eng = inst.engine
    assert not eng.step_open()
    cb = inst._make_push_callback("s0")
    inside = []

    def tap(out):
        # the engine thread is in a step here; a foreign thread's
        # callback must not touch the collected list
        t = threading.Thread(target=lambda: (
            inside.append(eng.step_open()),
            cb(RequestOutput(request_id="x", outputs=[
                SequenceOutput(token_ids=[68])])),
        ))
        t.start()
        t.join()
        inside.append(eng.step_open())
        inside.append(inst._push_q.qsize())
        return True

    eng.add_request(EngineRequest(
        request_id="r", prompt_token_ids=[5, 6, 7],
        sampling=SamplingParams(temperature=0.0, max_new_tokens=1),
        callback=tap,
    ))
    for _ in range(4):
        eng.step()
    assert inside == [False, True, 1]
    assert not eng.step_open()


def test_served_end_to_end_and_stop_leaves_nothing_collected():
    """Through a master: the engine thread's loop, the push thread taking
    lists, the instance's /metrics carrying the histogram; stop()."""
    from tests.test_api_e2e import http_get, sse_post, wait_until
    from xllm_service_tpu.api import Master
    from xllm_service_tpu.common.config import ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    store = MemoryStore(clock=lambda: 0.0)
    master = Master(ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.2, master_lease_ttl_s=1.0, block_size=16,
    ), store=store)
    master.start()
    inst = InstanceServer(
        EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=16,
            num_blocks=64, max_running_requests=ROWS, max_seq_len=128,
            prefill_buckets=[32], instance_name="handover-e2e",
            instance_type="MIX",
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.2,
    )
    inst.start()
    try:
        assert wait_until(
            lambda: sum(master.scheduler.instance_mgr.counts()) == 1
        )
        results = [None] * ROWS

        def one(i):
            results[i] = sse_post(
                master.http_address, "/v1/completions",
                {"model": "llama3-tiny", "prompt": f"hello {i}",
                 "max_tokens": 12, "temperature": 0.0, "stream": True,
                 "ignore_eos": True},
                timeout=300.0,
            )

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(ROWS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for events in results:
            assert events[-1] == "[DONE]"
            chunks = [e for e in events[:-1] if e.get("choices")]
            # one chunk a token, none merged
            assert len([c for c in chunks
                        if c["choices"][0].get("text") is not None]) >= 12
        _, body = http_get(inst.address, "/metrics")
        body = body if isinstance(body, str) else str(body)
        lines = [ln for ln in body.splitlines()
                 if ln.startswith("xllm_engine_handover_outputs")]
        count = next(float(ln.split()[-1]) for ln in lines
                     if ln.startswith("xllm_engine_handover_outputs_count"))
        total = next(float(ln.split()[-1]) for ln in lines
                     if ln.startswith("xllm_engine_handover_outputs_sum"))
        assert total == ROWS * 12 and count < total  # steps, not tokens
    finally:
        inst.stop()
        master.stop()
        store.close()
    assert inst._pending_outs == []
