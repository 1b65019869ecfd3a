"""Fenced master failover: epoch-guarded dispatch, takeover state
reconciliation, orphan reaping, and control-plane chaos hooks.

The reference's standby master takes over the `XLLM:SERVICE:MASTER`
lease with empty state (scheduler.cpp:132-149) and a deposed master can
keep dispatching; here the failover story is behavior under test:

  * the election transaction commits a monotonically increasing fencing
    epoch; instances persist the highest seen and 412-reject lower —
    a deposed master's dispatch is structurally rejected;
  * a takeover puts the new master into RECONCILING, scans instance
    POST /reconcile manifests, and rebuilds loads / in-flight charges /
    the KV index to match instance ground truth;
  * manifests the new master does not reclaim are reaped instance-side
    after the orphan TTL — engine work cancelled, no KV leaks;
  * a master killed mid-stream plus a client retry against the new
    master yields a completed stream, with the orphaned first attempt
    reaped;
  * control-plane fault points (election.keepalive, store.watch,
    reconcile.send, reconcile.recv) drive the above deterministically.
"""

import http.client
import json
import threading
import time

import pytest

from xllm_service_tpu.api import FakeEngine, Master
from xllm_service_tpu.api.http_utils import post_json
from xllm_service_tpu.api.instance import InstanceServer
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
from xllm_service_tpu.coordination import (
    MASTER_EPOCH_KEY,
    MASTER_KEY,
    MasterElection,
    MemoryStore,
)
from xllm_service_tpu.coordination import store as coord_store
from xllm_service_tpu.service.scheduler import (
    MASTER_ACTIVE,
    MASTER_STANDBY,
)

from tests.test_api_e2e import http_post, wait_until


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    faults.clear()
    yield
    faults.clear()


def make_master(store, **kw):
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.2, master_lease_ttl_s=1.0,
        instance_lease_min_ttl_s=0.0,
        load_balance_policy="RR", block_size=16,
        detect_disconnected_instance_interval_s=2.0,
        reconcile_orphan_ttl_s=kw.pop("reconcile_orphan_ttl_s", 10.0),
        **kw,
    )
    m = Master(cfg, store=store)
    m.start()
    return m


def make_instance(master, name, itype="DEFAULT", **engine_kw):
    ecfg = EngineConfig(
        model="fake-echo", instance_name=name, instance_type=itype,
        block_size=16,
    )
    srv = InstanceServer(
        ecfg, master_rpc_addr=master.rpc_address,
        heartbeat_interval_s=0.2, engine=FakeEngine(**engine_kw),
    )
    srv.start()
    return srv


def expire_master_lease(store, master):
    """The crash signal the sweeper raises when a real TTL lapses: the
    master's election lease expires, its key DELETEs, standbys campaign.
    Retried until the key actually flips — a still-running keepalive can
    refresh the lease between the expiry mark and the sweep."""
    lease = master.scheduler._election._lease_id
    ident = master.scheduler.election_identity
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        store.expire_lease_now(lease)
        if store.get(MASTER_KEY) != ident:
            return
        time.sleep(0.02)
    raise AssertionError("master lease never expired")


# ---------------------------------------------------------------------------
# store + election: the epoch transaction
# ---------------------------------------------------------------------------


class TestEpochTransaction:
    def test_epoch_commits_with_the_winning_txn(self):
        store = MemoryStore()
        try:
            assert store.compare_create_with_epoch(
                "E:m", "a", "E:m:EPOCH"
            ) == 1
            # the loser gets 0 and the epoch does NOT advance
            assert store.compare_create_with_epoch(
                "E:m", "b", "E:m:EPOCH"
            ) == 0
            assert store.get("E:m:EPOCH") == "1"
            store.remove("E:m")
            assert store.compare_create_with_epoch(
                "E:m", "b", "E:m:EPOCH"
            ) == 2
            assert store.get("E:m") == "b"
        finally:
            store.close()

    def test_election_epoch_monotonic_across_terms(self):
        # Frozen lease clock: every expiry is DELIBERATE
        # (expire_lease_now), never a wall-clock miss under suite-wide
        # GIL stalls (XLA compiles in sibling tests) — the repo's
        # established anti-flake pattern for lease-driven tests.
        store = MemoryStore(clock=lambda: 0.0)
        e1 = MasterElection(store, "svc1", lease_ttl_s=0.2)
        elected2 = threading.Event()
        e2 = MasterElection(
            store, "svc2", lease_ttl_s=0.2, on_elected=elected2.set
        )
        try:
            e1.start()
            assert e1.is_master and e1.epoch == 1
            assert store.get(MASTER_EPOCH_KEY) == "1"
            e2.start()
            store.expire_lease_now(e1._lease_id)
            assert elected2.wait(5.0)
            assert e2.epoch == 2
            # the deposed master's epoch stays STICKY at its old term —
            # that is exactly what instances fence on
            assert wait_until(lambda: not e1.is_master)
            assert e1.epoch == 1
        finally:
            e1.stop(); e2.stop(); store.close()

    def test_keepalive_thread_joined_on_reelect_cycle(self):
        """Satellite: a demote -> re-elect cycle must not leak a live
        keepalive thread per term (the old loop is joined before the new
        term starts one)."""
        # Frozen lease clock (see test_election_epoch_monotonic_across
        # _terms): under load a 0.2 s wall-clock lease can miss its
        # refresh window and expire SPONTANEOUSLY, inserting an extra
        # demote/re-elect cycle that overshoots the strict per-cycle
        # epoch this test pins.
        store = MemoryStore(clock=lambda: 0.0)
        # Scope the leak check to THIS election: earlier test files'
        # masters may still be winding their keepalive threads down.
        pre = {
            t for t in threading.enumerate()
            if t.name == "master-keepalive"
        }
        e1 = MasterElection(store, "svc1", lease_ttl_s=0.2)
        try:
            e1.start()
            assert e1.is_master
            for cycle in range(3):
                # drop the keepalive once: demote, then the vacancy watch
                # (or demote-time recheck) re-elects
                plan = faults.install_plan(faults.FaultPlan(seed=1))
                plan.add_rule(faults.FaultRule(
                    point="election.keepalive", match="svc1",
                    action="drop", count=1,
                ))
                store.expire_lease_now(e1._lease_id)
                want_epoch = cycle + 2
                assert wait_until(
                    lambda: e1.is_master and e1.epoch == want_epoch,
                    timeout=10.0,
                ), f"cycle {cycle}: epoch {e1.epoch}"
                faults.clear()
            def alive():
                return [
                    t for t in threading.enumerate()
                    if t.name == "master-keepalive" and t.is_alive()
                    and t not in pre
                ]

            # A leaked loop lives as long as its term; one that is only
            # late to notice its stop flag (a loaded machine) is gone
            # within a refresh period.
            assert wait_until(lambda: len(alive()) <= 1, timeout=5.0), alive()
            assert e1.epoch >= 2
        finally:
            e1.stop(); store.close()

    def test_watch_reconnect_backoff_shape(self):
        """Satellite: the etcd watch reconnect backoff grows, caps, and
        jitters (no synchronized reconnect waves); the process-wide
        counter is readable for xllm_coord_watch_reconnects_total."""
        lows = [coord_store._watch_backoff_s(a) for a in range(10)]
        for a, v in enumerate(lows):
            base = min(0.1 * (2 ** min(a, 16)), 5.0)
            assert base * 0.5 <= v <= base * 1.5
        assert min(
            coord_store._watch_backoff_s(12) for _ in range(20)
        ) >= 2.5  # capped at 5.0, jitter floor 0.5x
        before = coord_store.watch_reconnects_total()
        coord_store._count_watch_reconnect()
        assert coord_store.watch_reconnects_total() == before + 1

    def test_store_watch_fault_point_drops_one_delivery(self):
        """A dropped store.watch delivery loses exactly that batch for
        that watcher — later events still flow (the etcd-blip analog)."""
        store = MemoryStore()
        try:
            got = []
            store.add_watch("FW:", lambda evs: got.extend(evs))
            plan = faults.install_plan(faults.FaultPlan(seed=3))
            plan.add_rule(faults.FaultRule(
                point="store.watch", match="FW:", action="drop", count=1,
            ))
            store.set("FW:a", "1")  # dropped
            store.set("FW:b", "2")  # delivered
            assert wait_until(lambda: len(got) == 1, timeout=5.0)
            time.sleep(0.1)
            assert [e.key for e in got] == ["FW:b"]
        finally:
            store.close()


# ---------------------------------------------------------------------------
# takeover reconciliation
# ---------------------------------------------------------------------------


def test_takeover_rebuilds_loads_inflight_and_cache_index():
    """(a) A standby that takes over reconciles every instance: request
    charges, load metrics, and the KV-cache index match the instances'
    ground truth instead of starting empty."""
    store = MemoryStore(clock=lambda: 0.0)  # frozen: explicit expiry only
    m1 = make_master(store)
    # Hung engine: the in-flight request never delivers a token, so the
    # manifest must classify it as queued prefill work.
    srv = make_instance(m1, "r0", "DEFAULT", ttft_ms=3600_000)
    h1 = bytes(range(16))
    h2 = bytes(range(16, 32))
    srv.engine.cache_hashes = {h1, h2}
    m2 = None
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        result = {}

        def client():
            try:
                result["resp"] = http_post(
                    m1.http_address, "/v1/completions",
                    {"model": "fake-echo", "prompt": "abcdef",
                     "max_tokens": 4},
                    timeout=30.0,
                )
            except Exception as e:  # master dies under this exchange
                result["err"] = repr(e)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert wait_until(lambda: m1.scheduler.num_inflight == 1)
        assert wait_until(
            lambda: len(srv._srid_map) == 1, timeout=10.0
        )

        m2 = make_master(store)
        assert m2.scheduler.master_state == MASTER_STANDBY
        # standby registry view is already warm (store watches)
        assert wait_until(
            lambda: sum(m2.scheduler.instance_mgr.counts()) == 1
        )
        expire_master_lease(store, m1)
        assert wait_until(
            lambda: m2.scheduler.master_state == MASTER_ACTIVE,
            timeout=10.0,
        )
        assert m2.scheduler.master_epoch == 2
        assert m2.scheduler.last_takeover_ms is not None

        # ground truth: one queued prefill request of 6 prompt tokens
        rm = m2.scheduler.instance_mgr.get_request_metrics("r0")
        assert rm.prefill_request_num == 1
        assert rm.prefill_token_num == 6
        assert rm.decode_request_num == 0
        # load metrics came from the manifest, not a heartbeat race
        load = m2.scheduler.instance_mgr.get_load_metrics()["r0"]
        assert load.waiting_requests_num >= 1
        # the KV index holds the instance's committed snapshot
        for h in (h1, h2):
            assert "r0" in m2.scheduler.kvcache_mgr.lookup(h).hbm_instance_set
        # the manifest was orphaned (m2 never knew the request)
        assert m2.scheduler.total_orphaned == 1
        assert m2.scheduler.total_reconciled == 0
        assert "xllm_master_epoch 2" in m2.scheduler.metrics.render()
    finally:
        srv.stop()
        if m2 is not None:
            m2.stop()
        m1.stop()
        store.close()


def test_reconcile_survives_injected_faults():
    """reconcile.send / reconcile.recv drops must not wedge a takeover:
    the failed instance is skipped and the master still reaches ACTIVE
    (its state re-syncs through heartbeats)."""
    store = MemoryStore(clock=lambda: 0.0)
    m1 = make_master(store)
    srv = make_instance(m1, "f0", "DEFAULT")
    m2 = None
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        plan = faults.install_plan(faults.FaultPlan(seed=11))
        plan.add_rule(faults.FaultRule(
            point="reconcile.send", action="drop", count=1,
        ))
        plan.add_rule(faults.FaultRule(
            point="reconcile.recv", action="drop", count=1,
        ))
        m2 = make_master(store)
        assert wait_until(
            lambda: sum(m2.scheduler.instance_mgr.counts()) == 1
        )
        expire_master_lease(store, m1)
        assert wait_until(
            lambda: m2.scheduler.master_state == MASTER_ACTIVE,
            timeout=10.0,
        )
        faults.clear()
        # the new master still serves traffic end to end
        code, body = http_post(
            m2.http_address, "/v1/completions",
            {"model": "fake-echo", "prompt": "wxyz", "max_tokens": 4},
            timeout=30.0,
        )
        assert code == 200, body
        assert body["choices"][0]["text"] == "zyxw"
    finally:
        srv.stop()
        if m2 is not None:
            m2.stop()
        m1.stop()
        store.close()


# ---------------------------------------------------------------------------
# epoch fencing
# ---------------------------------------------------------------------------


def test_stale_epoch_dispatch_is_rejected():
    """(b) An instance that has seen epoch N rejects any RPC stamped
    with a lower epoch — 412 + fenced marker + counter — while current
    and unstamped (direct client) traffic still passes."""
    store = MemoryStore(clock=lambda: 0.0)
    m1 = make_master(store)
    srv = make_instance(m1, "s0", "DEFAULT")
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        # raise the instance's fence to 5
        code, _ = post_json(
            srv.address, "/health", {"master_epoch": 5}
        )
        assert code == 200
        # a stale-epoch forwarded dispatch is 412-fenced
        code, resp = post_json(
            srv.address, "/v1/completions",
            {"model": "fake-echo", "service_request_id": "cmpl-stale",
             "token_ids": [1, 2, 3], "master_epoch": 4},
        )
        assert code == 412, resp
        assert resp.get("fenced") is True
        assert resp["error"]["type"] == "stale_epoch"
        assert resp["epoch"] == 5
        # stale /cancel and /health probes are fenced identically
        code, resp = post_json(
            srv.address, "/cancel",
            {"service_request_id": "x", "master_epoch": 4},
        )
        assert code == 412
        code, resp = post_json(
            srv.address, "/health", {"master_epoch": 4}
        )
        assert code == 412
        fenced = srv.metrics.get("xllm_instance_fenced_rpcs_total").get()
        assert fenced == 3
        # nothing reached the engine
        assert "cmpl-stale" not in srv._srid_map
        # unstamped direct traffic is untouched by the fence
        code, body = post_json(
            srv.address, "/v1/completions",
            {"model": "fake-echo", "prompt": "ab", "max_tokens": 2},
            timeout=30.0,
        )
        assert code == 200
    finally:
        srv.stop(); m1.stop(); store.close()


def test_demoted_master_is_fenced_and_redirects():
    """A master deposed by a store partition (election.keepalive drop)
    stops dispatching and 307-redirects its front door at the current
    master; the successor's reconcile raised the instance fence, so any
    straggler RPC from the old epoch is provably rejected."""
    store = MemoryStore(clock=lambda: 0.0)
    m1 = make_master(store)
    srv = make_instance(m1, "d0", "DEFAULT")
    m2 = None
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        m2 = make_master(store)
        assert wait_until(
            lambda: sum(m2.scheduler.instance_mgr.counts()) == 1
        )
        # Partition m1 from the store: its keepalives drop, it demotes.
        plan = faults.install_plan(faults.FaultPlan(seed=7))
        plan.add_rule(faults.FaultRule(
            point="election.keepalive",
            match=m1.scheduler.election_identity, action="drop",
        ))
        expire_master_lease(store, m1)
        assert wait_until(
            lambda: not m1.scheduler.is_master
            and m2.scheduler.master_state == MASTER_ACTIVE,
            timeout=10.0,
        )
        faults.clear()
        assert m2.scheduler.master_epoch == 2
        # the reconcile carried epoch 2 to the instance
        assert srv._fence_epoch == 2

        # (1) the deposed master's front door redirects to the successor
        host, _, port = m1.http_address.partition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=10)
        conn.request(
            "POST", "/v1/completions",
            body=json.dumps({
                "model": "fake-echo", "prompt": "ab", "max_tokens": 2,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 307
        loc = resp.getheader("Location")
        assert m2.scheduler.election_identity in loc
        payload = json.loads(resp.read())
        assert payload["master"] == m2.scheduler.election_identity
        conn.close()

        # (2) a straggler dispatch stamped with the deposed epoch is
        # rejected by the instance (the wire-level proof)
        code, resp = post_json(
            srv.address, "/v1/completions",
            {"model": "fake-echo", "service_request_id": "cmpl-old",
             "token_ids": [1, 2], "master_epoch": 1},
        )
        assert code == 412 and resp.get("fenced") is True
        assert srv.metrics.get(
            "xllm_instance_fenced_rpcs_total"
        ).get() >= 1

        # (3) the successor serves normally with its higher epoch
        code, body = http_post(
            m2.http_address, "/v1/completions",
            {"model": "fake-echo", "prompt": "pq", "max_tokens": 2},
            timeout=30.0,
        )
        assert code == 200, body
        assert body["choices"][0]["text"] == "qp"
    finally:
        srv.stop()
        if m2 is not None:
            m2.stop()
        m1.stop()
        store.close()


# ---------------------------------------------------------------------------
# orphan reaping
# ---------------------------------------------------------------------------


def test_unreclaimed_manifests_are_reaped():
    """(c) In-flight requests the new master does not reclaim are reaped
    after the orphan TTL: engine work cancelled, every per-srid table
    emptied, the reap counted — zero leaked state."""
    store = MemoryStore(clock=lambda: 0.0)
    m1 = make_master(store, reconcile_orphan_ttl_s=0.5)
    # Fast first token, then a 4 s token gap: the request is mid-decode
    # through the whole kill->takeover->reap window, and the engine
    # thread wakes AFTER the reap to observe its cancellation.
    srv = make_instance(
        m1, "o0", "DEFAULT", ttft_ms=300.0, token_delay_s=4.0
    )
    m2 = None
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        result = {}

        def client():
            try:
                result["resp"] = http_post(
                    m1.http_address, "/v1/completions",
                    {"model": "fake-echo", "prompt": "abcd",
                     "max_tokens": 4},
                    timeout=30.0,
                )
            except Exception as e:
                result["err"] = repr(e)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert wait_until(lambda: len(srv._srid_map) == 1, timeout=10.0)
        assert len(srv._srid_info) == 1
        # first token delivered: the manifest classifies a decode slot
        assert wait_until(
            lambda: next(iter(srv._srid_info.values()))["delivered"] >= 1,
            timeout=10.0,
        )

        m2 = make_master(store, reconcile_orphan_ttl_s=0.5)
        assert wait_until(
            lambda: sum(m2.scheduler.instance_mgr.counts()) == 1
        )
        m1.kill()
        expire_master_lease(store, m1)
        assert wait_until(
            lambda: m2.scheduler.master_state == MASTER_ACTIVE,
            timeout=10.0,
        )
        # the orphan TTL fires instance-side: every table drains
        assert wait_until(
            lambda: not srv._srid_map and not srv._srid_info,
            timeout=10.0,
        )
        # (the reap empties the tables first and counts last: wait for it)
        assert wait_until(
            lambda: srv.metrics.get(
                "xllm_service_orphan_reaped_total"
            ).get() == 1
        )
        # the engine request was cancelled (work + blocks released)
        assert wait_until(
            lambda: srv.engine.get_load_metrics().waiting_requests_num == 0,
            timeout=10.0,
        )
        with srv._push_acked_mu:
            assert not srv._push_acked
        # the manifest was orphaned and its absorbed charge (an open
        # decode slot — one token had been delivered) unwinds on the
        # same clock master-side
        assert m2.scheduler.total_orphaned == 1
        rm = m2.scheduler.instance_mgr.get_request_metrics("o0")
        assert wait_until(
            lambda: rm.decode_request_num == 0
            and rm.prefill_request_num == 0,
            timeout=10.0,
        )
    finally:
        srv.stop()
        if m2 is not None:
            m2.stop()
        m1.stop()
        store.close()


# ---------------------------------------------------------------------------
# end to end: master kill mid-stream + client retry
# ---------------------------------------------------------------------------


def _stream_once(addr, prompt, max_tokens, timeout=30.0):
    """One streaming attempt; returns (text, saw_done). Raises on
    connection death (the master-kill signal a client sees)."""
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    conn.request(
        "POST", "/v1/completions",
        body=json.dumps({
            "model": "fake-echo", "prompt": prompt,
            "max_tokens": max_tokens, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    if resp.status != 200:
        conn.close()
        raise RuntimeError(f"HTTP {resp.status}")
    text, done = "", False
    for raw in resp:
        line = raw.decode().strip()
        if not line.startswith("data: "):
            continue
        payload = line[len("data: "):]
        if payload == "[DONE]":
            done = True
            break
        ev = json.loads(payload)
        if "error" in ev:
            break
        text += ev["choices"][0]["text"]
    conn.close()
    return text, done


def test_master_kill_midstream_client_retry_completes():
    """(d) Kill the master mid-stream; the client retries the request
    against the takeover master and receives a COMPLETE stream, while
    the instance reaps the orphaned first attempt. The heartbeat plane
    re-points at the successor, so the fleet outlives its master."""
    store = MemoryStore(clock=lambda: 0.0)
    m1 = make_master(store, reconcile_orphan_ttl_s=1.0)
    # Slow stream (0.5 s/token x 12): mid-flight through the whole
    # kill -> takeover window.
    srv = make_instance(m1, "k0", "DEFAULT", token_delay_s=0.5)
    m2 = None
    prompt, max_tokens = "abcdefghijkl", 12
    try:
        assert wait_until(
            lambda: sum(m1.scheduler.instance_mgr.counts()) == 1
        )
        m2 = make_master(store, reconcile_orphan_ttl_s=1.0)
        assert wait_until(
            lambda: sum(m2.scheduler.instance_mgr.counts()) == 1
        )
        result = {}

        def client():
            # first attempt dies with the master; retry against the
            # CURRENT master resolved from the election key
            try:
                result["first"] = _stream_once(
                    m1.http_address, prompt, max_tokens
                )
            except Exception as e:
                result["first_err"] = repr(e)
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                cur = store.get(MASTER_KEY)
                if cur and cur != m1.scheduler.election_identity:
                    try:
                        result["retry"] = _stream_once(
                            cur, prompt, max_tokens
                        )
                        return
                    except Exception:
                        pass
                time.sleep(0.2)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        # wait until tokens are flowing, then kill the master UNGRACEFULLY
        assert wait_until(
            lambda: any(
                s.request.num_generated_tokens >= 2
                for s in m1.scheduler._requests.values()
            ),
            timeout=20.0,
        )
        m1.kill()
        expire_master_lease(store, m1)
        assert wait_until(
            lambda: m2.scheduler.master_state == MASTER_ACTIVE,
            timeout=10.0,
        )
        t.join(timeout=40.0)
        assert not t.is_alive()
        # the first attempt did NOT complete; the retry did, byte-complete
        assert result.get("first", ("", False))[1] is False
        text, done = result["retry"]
        assert done and text == prompt[::-1]
        # the takeover was measured
        assert m2.scheduler.last_takeover_ms is not None
        assert m2.scheduler.takeover_first_dispatch_ms is not None
        # the reconcile classified the first attempt as an orphan, and
        # the instance tore it down (the TTL reap, or sooner: the new
        # master's cont=False on its pushes) — zero tracked requests left
        assert m2.scheduler.total_orphaned >= 1
        assert wait_until(
            lambda: not srv._srid_map and not srv._srid_info,
            timeout=15.0,
        )
        # heartbeats re-pointed: the new master keeps receiving beats
        assert srv._master._addr == m2.rpc_address
    finally:
        srv.stop()
        if m2 is not None:
            m2.stop()
        m1.stop()
        store.close()
