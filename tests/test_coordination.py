"""Coordination store + master election tests.

Covers the etcd semantics the reference relies on (SURVEY.md §3.5): prefix
scans, watch PUT/DELETE delivery, lease expiry => key deletion => watch
event, compare-create election txn, guarded batch delete, and watch-driven
master takeover/failover.
"""

import threading
import time

import pytest

from xllm_service_tpu.coordination import (
    MASTER_KEY,
    MasterElection,
    MemoryStore,
    EventType,
    connect,
    reset_memory_namespace,
)


def wait_until(pred, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


@pytest.fixture
def store():
    st = MemoryStore()
    yield st
    st.close()


class TestMemoryStore:
    def test_get_set_remove(self, store):
        assert store.get("k") is None
        assert store.set("k", "v")
        assert store.get("k") == "v"
        assert store.remove("k")
        assert store.get("k") is None
        assert not store.remove("k")

    def test_prefix_scan(self, store):
        store.set("XLLM:PREFILL:a", "1")
        store.set("XLLM:PREFILL:b", "2")
        store.set("XLLM:DECODE:c", "3")
        got = store.get_prefix("XLLM:PREFILL:")
        assert got == {"XLLM:PREFILL:a": "1", "XLLM:PREFILL:b": "2"}

    def test_json_roundtrip(self, store):
        store.set_json("j", {"a": [1, 2], "b": "x"})
        assert store.get_json("j") == {"a": [1, 2], "b": "x"}

    def test_watch_put_delete(self, store):
        events = []
        done = threading.Event()

        def cb(evs):
            events.extend(evs)
            if len(events) >= 2:
                done.set()

        store.add_watch("W:", cb)
        store.set("W:x", "1")
        store.set("other", "ignored")
        store.remove("W:x")
        assert done.wait(5.0)
        assert [(e.type, e.key) for e in events] == [
            (EventType.PUT, "W:x"),
            (EventType.DELETE, "W:x"),
        ]

    def test_remove_watch_stops_delivery(self, store):
        events = []
        wid = store.add_watch("W:", lambda evs: events.extend(evs))
        store.remove_watch(wid)
        store.set("W:x", "1")
        time.sleep(0.2)
        assert events == []

    def test_lease_expiry_deletes_and_notifies(self, store):
        deleted = threading.Event()
        store.add_watch(
            "L:",
            lambda evs: deleted.set()
            if any(e.type == EventType.DELETE for e in evs)
            else None,
        )
        lease = store.grant_lease(ttl_s=0.2)
        store.set("L:inst", "meta", lease_id=lease)
        assert store.get("L:inst") == "meta"
        assert deleted.wait(5.0)
        assert store.get("L:inst") is None

    def test_keepalive_refreshes(self, store):
        lease = store.grant_lease(ttl_s=0.3)
        store.set("K:x", "v", lease_id=lease)
        for _ in range(4):
            time.sleep(0.15)
            assert store.keepalive(lease)
        assert store.get("K:x") == "v"
        # stop refreshing -> expires
        assert wait_until(lambda: store.get("K:x") is None)
        assert not store.keepalive(lease)

    def test_revoke_lease_deletes_keys(self, store):
        lease = store.grant_lease(ttl_s=30)
        store.set("R:x", "v", lease_id=lease)
        store.revoke_lease(lease)
        assert store.get("R:x") is None

    @pytest.mark.parametrize(
        "stalled_s,injected_clock,kept",
        [(4.0, False, True), (0.2, False, False), (4.0, True, False)],
        ids=["stalled-sweep-gives-time-back", "late-within-grace", "injected-clock"],
    )
    def test_stalled_sweep_does_not_expire_leases(
        self, stalled_s, injected_clock, kept
    ):
        """A sweep that comes seconds late (the process was held up, so no
        holder could keep its lease alive either) extends the leases by the
        lost time; a punctual one, or lease time on an injected clock, does
        not. No wall clock: the lease is made overdue by hand."""
        now = [100.0]
        st = MemoryStore(clock=(lambda: now[0]) if injected_clock else None)
        try:
            lease = st.grant_lease(ttl_s=30)
            st.set("S:master", "me", lease_id=lease)
            with st._mu:
                # overdue by 1 s: what a 4 s stall leaves of a 3 s lease
                st._leases[lease].expires_at = st._clock() - 1.0
                assert st._sweep_once(stalled_s)
            assert (st.get("S:master") == "me") is kept
            assert st.keepalive(lease) is kept
        finally:
            st.close()

    def test_compare_create_single_winner(self, store):
        wins = sum(
            store.compare_create("E:master", f"id{i}") for i in range(5)
        )
        assert wins == 1
        assert store.get("E:master") == "id0"

    def test_guarded_remove(self, store):
        store.set("G:guard", "me")
        store.set("G:a", "1")
        store.set("G:b", "2")
        assert not store.guarded_remove(["G:a"], "G:guard", "not-me")
        assert store.get("G:a") == "1"
        assert store.guarded_remove(["G:a", "G:b"], "G:guard", "me")
        assert store.get("G:a") is None and store.get("G:b") is None

    def test_memory_namespace_shared(self):
        reset_memory_namespace("t1")
        a = connect("memory://t1")
        b = connect("memory://t1")
        assert a is b
        a.set("x", "1")
        assert b.get("x") == "1"
        reset_memory_namespace("t1")


class TestMasterElection:
    def test_first_wins_second_watches(self, store):
        # Generous TTL: a 0.3 s lease on the REAL clock flaked once under
        # full-suite load (keepalive beat starved past the TTL, svc2 took
        # over mid-assert). Nothing here waits on expiry, so the longer
        # lease costs nothing.
        e1 = MasterElection(store, "svc1", lease_ttl_s=3.0)
        e2 = MasterElection(store, "svc2", lease_ttl_s=3.0)
        e1.start()
        e2.start()
        assert e1.is_master and not e2.is_master
        assert store.get(MASTER_KEY) == "svc1"
        e1.stop()
        e2.stop()

    def test_failover_on_master_death(self, store):
        lost = threading.Event()
        elected2 = threading.Event()
        e1 = MasterElection(store, "svc1", lease_ttl_s=0.2, on_lost=lost.set)
        e2 = MasterElection(
            store, "svc2", lease_ttl_s=0.2, on_elected=elected2.set
        )
        e1.start()
        e2.start()
        assert e1.is_master
        # Simulate svc1 crash: stop keepalives by force-expiring its lease.
        with e1._mu:
            lease = e1._lease_id
        store.expire_lease_now(lease)
        assert elected2.wait(5.0), "svc2 should take over after lease expiry"
        assert e2.is_master
        assert store.get(MASTER_KEY) == "svc2"
        e1.stop()
        e2.stop()

    def test_clean_stop_releases_mastership(self, store):
        elected2 = threading.Event()
        e1 = MasterElection(store, "svc1", lease_ttl_s=0.3)
        e2 = MasterElection(
            store, "svc2", lease_ttl_s=0.3, on_elected=elected2.set
        )
        e1.start()
        e2.start()
        e1.stop()  # revokes lease -> DELETE -> e2 takeover
        assert elected2.wait(5.0)
        assert store.get(MASTER_KEY) == "svc2"
        e2.stop()
