"""A pushed batch of tokens crosses the master on one thread (ISSUE 36).

Three layers of the same promise:
  * `Strand` / `HopThreads` alone (service/ordered_streams.py);
  * the scheduler: two pushers for one request, a failure and a fence from
    other threads, a delivery that raises;
  * end to end over real sockets, master + instance over a step-synchronous
    fake engine (tests/_step_engine.py): order, `[DONE]`, the counters, the
    process's thread count, and a client that stops reading, on both HTTP
    backends.

Nothing here asserts on a time.
"""

import json
import socket
import sys
import threading
import time

import pytest

from xllm_service_tpu.api import Master
from xllm_service_tpu.api.evserve.loadgen import run_sse_load
from xllm_service_tpu.api.instance import InstanceServer
from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
from xllm_service_tpu.common.types import FinishReason, StatusCode, Usage
from xllm_service_tpu.coordination import MemoryStore
from xllm_service_tpu.service import ClientStream, ServiceRequest
from xllm_service_tpu.service.ordered_streams import HopThreads, Strand

from tests._step_engine import StepEngine
from tests.test_api_e2e import wait_until
from tests.test_service import sched_env, step  # noqa: F401 (fixture)


# --------------------------------------------------------------------- #
# the strand and the hop threads alone
# --------------------------------------------------------------------- #

class TestStrand:
    def test_idle_strand_runs_on_the_callers_thread(self):
        ran = []
        assert Strand().submit(lambda: ran.append(threading.get_ident()))
        assert ran == [threading.get_ident()]

    def test_busy_strand_appends_and_the_holder_runs_it_in_order(self):
        s, seen = Strand(), []
        inside, release = threading.Event(), threading.Event()

        def first():
            inside.set()
            release.wait(5.0)
            seen.append(("first", threading.get_ident()))

        holder = threading.Thread(target=lambda: s.submit(first))
        holder.start()
        assert inside.wait(5.0)
        for i in range(3):
            assert not s.submit(
                lambda i=i: seen.append((i, threading.get_ident()))
            )
        assert seen == []  # nothing ran on this thread, nothing ran early
        release.set()
        holder.join(5.0)
        assert [x for x, _ in seen] == ["first", 0, 1, 2]
        assert {t for _, t in seen} == {holder.ident}
        assert s.submit(lambda: None)  # idle again

    def test_a_call_that_raises_does_not_wedge_the_strand(self):
        s, seen = Strand(), []

        def boom():
            s.submit(lambda: seen.append("queued behind the failure"))
            raise RuntimeError("delivery failed")

        assert s.submit(boom)
        assert seen == ["queued behind the failure"]
        assert s.submit(lambda: seen.append("after"))
        assert seen[-1] == "after"

    def test_submitting_from_inside_the_strand_is_safe(self):
        s, seen = Strand(), []

        def outer():
            assert not s.submit(lambda: seen.append("inner"))
            seen.append("outer")

        assert s.submit(outer)
        assert seen == ["outer", "inner"]

    def test_hop_runs_the_strand_elsewhere_and_keeps_its_order(self):
        hop, s, seen = HopThreads(4, name="t-hop"), Strand(), []
        done = threading.Event()
        for i in range(50):
            assert not s.submit(
                lambda i=i: seen.append((i, threading.get_ident())), hop
            )
        s.submit(done.set, hop)
        assert done.wait(5.0)
        assert [i for i, _ in seen] == list(range(50))
        assert threading.get_ident() not in {t for _, t in seen}
        hop.shutdown()

    def test_hop_threads_start_as_needed_and_a_blocked_one_holds_no_other(
        self,
    ):
        hop = HopThreads(3, name="t-hop")
        assert hop.num_threads == 0
        gate, ran = threading.Event(), threading.Event()
        hop.submit(lambda: gate.wait(10.0))  # a write that blocks
        hop.submit(ran.set)
        assert ran.wait(5.0)  # not queued behind the blocked call
        assert hop.num_threads == 2
        for _ in range(5):
            again = threading.Event()
            hop.submit(again.set)
            assert again.wait(5.0)
        assert hop.num_threads == 2  # the idle one is reused
        gate.set()
        hop.shutdown()


# --------------------------------------------------------------------- #
# the scheduler: pushers, a failure and a fence from different threads
# --------------------------------------------------------------------- #

class RecordingStream(ClientStream):
    """Records every call in order and notices two at once."""

    def __init__(self, raise_at=None):
        self.events = []
        self._inside = False
        self.overlapped = False
        self._raise_at = raise_at

    def _enter(self, what, payload=None):
        if self._inside:
            self.overlapped = True
        self._inside = True
        time.sleep(0)  # give another thread its chance to trespass
        self.events.append((what, payload))
        self._inside = False
        return True

    def write(self, payload):
        n = sum(1 for w, _ in self.events if w == "chunk")
        if self._raise_at is not None and n == self._raise_at:
            self._raise_at = None
            raise RuntimeError("a delivery that raises")
        return self._enter("chunk", payload["choices"][0]["text"])

    def write_done(self):
        return self._enter("done")

    def finish(self, payload):
        return self._enter("final", payload)

    def finish_with_error(self, code, message):
        return self._enter("error", code)

    def texts(self):
        return [p for w, p in self.events if w == "chunk"]


def _register(sched, n, prefix="q", **stream_kw):
    streams = {}
    for i in range(n):
        srid = f"{prefix}{i}"
        req = ServiceRequest(
            service_request_id=srid, prompt="abc", stream=True
        )
        assert sched.schedule(req).ok()
        streams[srid] = RecordingStream(**stream_kw.get(srid, {}))
        sched.record_new_request(req, streams[srid])
    return streams


def _counter(sched, ran):
    return sched.metrics.get("xllm_service_deliveries_total").labels(
        ran=ran
    ).get()


def _batches(sched):
    """(pushed batches, outputs in them) off the batch-size histogram."""
    _, total, n = sched.metrics.get(
        "xllm_service_generations_batch_size"
    )._only().snapshot()
    return n, int(total)


class TestSchedulerOrdering:
    R, N = 12, 60

    def test_two_pushers_a_failure_and_a_fence(self, sched_env):
        """Two threads push interleaved batches for the same requests (a
        PD pair's two pushers) while a third fails some requests and a
        fourth fences one. Every stream keeps each pusher's order, no two
        calls into one stream overlap, nothing follows a finish or a
        failure, and the fence returns only after everything admitted
        before it has been written."""
        sched, _ = sched_env
        streams = _register(sched, self.R)
        srids = list(streams)
        failed = srids[:3]           # failed from a third thread
        finished = srids[3:6]        # pusher A finishes them half way
        fenced = srids[6]            # fenced from a fourth thread
        fenced_state = sched._requests[fenced]
        go = threading.Barrier(4)

        def pusher(tag):
            go.wait()
            for j in range(self.N):
                batch = [step(s, f"{tag}{j}", [j]) for s in srids]
                if tag == "a" and j == self.N // 2:
                    batch += [
                        step(s, "end", [0], finished=True,
                             reason=FinishReason.STOP, usage=Usage(3, j))
                        for s in finished
                    ]
                sched.handle_generations(batch)

        def failer():
            go.wait()
            for s in failed:
                time.sleep(0.002)
                sched.fail_request(s, StatusCode.UNAVAILABLE, "died")

        snapshot = {}

        def fencer():
            go.wait()
            time.sleep(0.004)
            sched._bump_attempt(fenced_state)
            sched._drain_strand(fenced_state)
            snapshot["n"] = len(streams[fenced].events)

        threads = [
            threading.Thread(target=pusher, args=("a",)),
            threading.Thread(target=pusher, args=("b",)),
            threading.Thread(target=failer),
            threading.Thread(target=fencer),
        ]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)  # trade the interpreter far more often
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)

        for srid, st in streams.items():
            assert not st.overlapped, srid
            for tag in "ab":
                mine = [int(x[1:]) for x in st.texts() if x[0] == tag]
                assert mine == sorted(mine), (srid, tag)
                assert len(set(mine)) == len(mine), (srid, tag)
            kinds = [w for w, _ in st.events]
            for terminal in ("done", "error"):
                if terminal in kinds:
                    assert kinds.index(terminal) == len(kinds) - 1, srid
        for srid in failed:
            assert streams[srid].events[-1] == ("error", StatusCode.UNAVAILABLE)
        for srid in finished:
            assert [w for w, _ in streams[srid].events][-1] == "done"
            assert streams[srid].texts()[-1] == "end"
        # The fence: pushes under the old wire id were refused after the
        # bump, and what had been admitted before it was written before
        # `_drain_strand` returned, so the stream did not grow afterwards.
        assert len(streams[fenced].events) == snapshot["n"]
        # The untouched requests got every token of both pushers.
        for srid in srids[7:]:
            assert len(streams[srid].texts()) == 2 * self.N
        assert _counter(sched, "inline") + _counter(sched, "queued") > 0
        assert sched._hop.num_threads == 0  # no stream here can block

    def test_a_delivery_that_raises_wedges_neither_request_nor_batch(
        self, sched_env
    ):
        sched, _ = sched_env
        streams = _register(sched, 3, prefix="x", x1={"raise_at": 1})
        for j in range(4):
            cont = sched.handle_generations(
                [step(s, f"t{j}", [j]) for s in streams]
            )
            assert cont == {s: True for s in streams}
        assert streams["x0"].texts() == ["t0", "t1", "t2", "t3"]
        assert streams["x2"].texts() == ["t0", "t1", "t2", "t3"]
        # the chunk whose write raised is lost; the request goes on
        assert streams["x1"].texts() == ["t0", "t2", "t3"]
        assert _counter(sched, "inline") == 12
        assert _counter(sched, "queued") == 0

    def test_one_batch_stale_unknown_and_finished_outputs(self, sched_env):
        """The continue map of a batch is what the singular call gave
        output by output: False for an unknown request, a stale attempt,
        and whatever follows a request's finish inside the same batch."""
        sched, _ = sched_env
        streams = _register(sched, 2, prefix="m")
        sched._bump_attempt(sched._requests["m1"])
        cont = sched.handle_generations([
            step("m0", "a", [1]),
            step("nobody", "x", [1]),
            step("m1", "stale", [1]),
            step("m1#r1", "live", [1]),
            step("m0", "b", [2], finished=True, reason=FinishReason.STOP,
                 usage=Usage(3, 2)),
        ])
        assert cont == {
            "m0": True, "nobody": False, "m1": False, "m1#r1": True,
        }
        assert streams["m0"].texts() == ["a", "b"]
        assert streams["m1"].texts() == ["live"]
        assert sched.handle_generations([step("m0", "late", [3])]) == {
            "m0": False
        }
        assert _batches(sched) == (2, 6)  # two batches, six outputs

    def test_a_stream_whose_writes_can_block_takes_a_thread_hop(
        self, sched_env
    ):
        """What the code can observe decides: a blocking stream's delivery
        runs on a hop thread (counted `queued`), and while one such write
        is stuck the same batch's other requests are delivered."""
        sched, _ = sched_env
        streams = _register(sched, 3, prefix="w")
        stuck, gate = streams["w1"], threading.Event()
        stuck.writes_can_block = True
        plain_write = stuck.write
        stuck.write = lambda p: gate.wait(10.0) and plain_write(p)
        me = threading.get_ident()
        seen_on = []
        w0_write = streams["w0"].write
        streams["w0"].write = lambda p: (
            seen_on.append(threading.get_ident()), w0_write(p)
        )[1]
        for j in range(3):
            sched.handle_generations(
                [step(s, f"t{j}", [j]) for s in streams]
            )
        assert streams["w0"].texts() == ["t0", "t1", "t2"]
        assert streams["w2"].texts() == ["t0", "t1", "t2"]
        assert set(seen_on) == {me}
        assert stuck.texts() == []
        assert _counter(sched, "inline") == 6
        assert _counter(sched, "queued") == 3
        assert sched._hop.num_threads == 1
        gate.set()
        assert wait_until(lambda: stuck.texts() == ["t0", "t1", "t2"])


# --------------------------------------------------------------------- #
# end to end: master + instance over a step-synchronous engine
# --------------------------------------------------------------------- #

STREAMS, STEPS = 96, 50
WORKERS = 8


def _cluster(backend, **cfg_kw):
    store = MemoryStore(clock=lambda: 0.0)  # frozen: leases never lapse
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.5, master_lease_ttl_s=2.0,
        load_balance_policy="RR", block_size=16,
        http_backend=backend, http_workers=WORKERS, **cfg_kw,
    )
    master = Master(cfg, store=store)
    master.start()
    engine = StepEngine(steps=STEPS)
    srv = InstanceServer(
        EngineConfig(model="fake-echo", instance_name="mix0",
                     instance_type="MIX", block_size=16),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.5,
        engine=engine,
    )
    srv.start()
    assert wait_until(
        lambda: sum(master.scheduler.instance_mgr.counts()) == 1
    )
    return store, master, srv, engine


def _teardown(store, master, srv):
    srv.stop()
    master.stop()
    store.close()


def _bodies(n, tokens=STEPS):
    return [
        {
            "model": "fake-echo",
            "prompt": f"{i:03d}-abcdefghijklmnopqrstuvwxyz",
            "max_tokens": tokens, "temperature": 0.0, "stream": True,
        }
        for i in range(n)
    ]


def _check_streams(bodies, results, tokens=STEPS):
    bad = [(i, r.error) for i, r in enumerate(results) if not r.ok]
    assert not bad, f"{len(bad)} streams failed: {bad[:5]}"
    for body, r in zip(bodies, results):
        assert r.events[-1] == "[DONE]"
        assert "[DONE]" not in r.events[:-1]
        texts = [
            c["choices"][0]["text"]
            for c in map(json.loads, r.events[:-1]) if c.get("choices")
        ]
        assert len(texts) == tokens  # one chunk a token, none merged
        p = body["prompt"]
        assert "".join(texts) == "".join(
            p[k % len(p)] for k in range(tokens)
        )


def test_a_pushed_batch_crosses_the_master_on_one_thread():
    store, master, srv, engine = _cluster("event")
    try:
        # One request first: the pools' threads that a burst of arrivals
        # starts are not what this test counts.
        warm = _bodies(WORKERS)
        _check_streams(warm, run_sse_load(
            master.http_address, "/v1/completions", warm, timeout_s=60.0
        ))
        sched = master.scheduler
        before = threading.active_count()
        inline0 = _counter(sched, "inline")
        queued0 = _counter(sched, "queued")
        batches0, outputs0 = _batches(sched)
        engine.peak_threads, engine.gather = 0, STREAMS

        bodies = _bodies(STREAMS)
        results = run_sse_load(
            master.http_address, "/v1/completions", bodies, timeout_s=120.0
        )
        _check_streams(bodies, results)

        inline = _counter(sched, "inline") - inline0
        queued = _counter(sched, "queued") - queued0
        assert inline + queued == STREAMS * STEPS
        assert inline >= 0.98 * STREAMS * STEPS, (inline, queued)
        # The engine made 96 callbacks a step from one thread and they
        # reached the master as batches, not one by one.
        batches, outputs = _batches(sched)
        assert outputs - outputs0 == STREAMS * STEPS
        assert (outputs - outputs0) / (batches - batches0) >= 8
        # No thread per token, per request or per lane: no hop thread was
        # started, and the busiest step saw no more threads than the two
        # servers' worker pools may add.
        assert sched._hop.num_threads == 0
        assert not [
            t.name for t in threading.enumerate()
            if t.name.startswith("ordered-out")
        ]
        assert engine.peak_threads <= before + 2 * WORKERS + 2, (
            before, engine.peak_threads
        )
        assert wait_until(lambda: sched.num_inflight == 0)
    finally:
        _teardown(store, master, srv)


def _stalled_client(addr, tokens):
    """Sends a streaming request and never reads: a tiny receive buffer,
    so the server's writes meet a full socket after a few chunks."""
    host, _, port = addr.partition(":")
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1024)
    sock.connect((host, int(port)))
    body = json.dumps({
        "model": "fake-echo", "prompt": "stalled-" + "z" * 56,
        "max_tokens": tokens, "temperature": 0.0, "stream": True,
    }).encode()
    sock.sendall(
        f"POST /v1/completions HTTP/1.1\r\nHost: {addr}\r\n"
        f"Content-Type: application/json\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    return sock


@pytest.mark.parametrize("backend", ["event", "threaded"])
def test_a_client_that_stops_reading_holds_up_nobody(backend):
    """One client asks for 64 KiB of text a step and reads none of it. On
    the event backend its outbox overflows and it is evicted; on the
    threaded backend its socket fills and the write blocks, on a hop
    thread. Either way the other streams of the same batches finish."""
    n = 32
    store, master, srv, engine = _cluster(backend, sse_max_buffered_kb=256)
    stalled = None
    try:
        sched = master.scheduler
        stalled = _stalled_client(master.http_address, STEPS * 65536)
        assert wait_until(lambda: sched.num_inflight == 1)
        engine.gather = n  # the stalled one may be evicted before they come
        bodies = _bodies(n)
        results = run_sse_load(
            master.http_address, "/v1/completions", bodies, timeout_s=120.0
        )
        _check_streams(bodies, results)
        if backend == "event":
            # evicted as a slow client, its generation cancelled upstream
            assert wait_until(
                lambda: master.http.stats()["slow_client_closes"] == 1
            )
            assert wait_until(lambda: sched.num_inflight == 0)
            assert sched._hop.num_threads <= 1  # the upstream cancel's
        else:
            # its write is stuck in a hop thread and its request is still
            # open, with every other stream already finished
            assert sched.num_inflight == 1
            assert _counter(sched, "queued") >= n * STEPS
            assert 1 <= sched._hop.num_threads <= (
                master.config.num_ordered_output_streams
            )
            stalled.close()
            stalled = None
            assert wait_until(lambda: sched.num_inflight == 0, timeout=20.0)
    finally:
        if stalled is not None:
            stalled.close()
        _teardown(store, master, srv)
