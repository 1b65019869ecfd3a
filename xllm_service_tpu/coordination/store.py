"""Coordination store: discovery, replicated state, liveness, election.

TPU-native redesign of the reference's single coordination backend
(reference: xllm_service/scheduler/etcd_client/etcd_client.{h,cpp}) behind a
narrow interface so the service tier is testable without a live etcd
(SURVEY.md §4 calls out that the reference has no such seam and therefore no
automatable integration tests).

Semantics preserved from the reference:
  * typed get/set/remove + prefix scans (etcd_client.h:37-118);
  * watches on key prefixes firing PUT/DELETE events (etcd_client.cpp:156-193);
  * TTL leases whose expiry deletes the attached keys, which is the entire
    liveness mechanism (instance death => lease expiry => watch DELETE =>
    registry removal; SURVEY.md §3.5);
  * compare-create transaction used for master election
    (etcd_client.cpp:47-62);
  * guarded batch delete that re-checks the master key inside the txn
    (etcd_client.cpp:90-99).

Backends: `MemoryStore` (in-process, process-global named namespaces so a
service and fake instances in one test share a view) and `EtcdGatewayStore`
(etcd v3 HTTP/JSON gateway over stdlib urllib — no extra deps). Select via
address: "memory://[ns]" or "etcd://host:port".
"""

from __future__ import annotations

import base64
import enum
import json
import random
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from xllm_service_tpu.common import faults

# Watch-stream reconnects across every EtcdGatewayStore in the process
# (exported as xllm_coord_watch_reconnects_total by the scheduler's
# registry — the store itself has no registry to avoid an obs dependency
# in the coordination layer).
_watch_reconnects_mu = threading.Lock()
_watch_reconnects = 0


def watch_reconnects_total() -> int:
    with _watch_reconnects_mu:
        return _watch_reconnects


def _count_watch_reconnect() -> None:
    global _watch_reconnects
    with _watch_reconnects_mu:
        _watch_reconnects += 1


def _watch_backoff_s(attempt: int, base_s: float = 0.1, max_s: float = 5.0) -> float:
    """Jittered exponential backoff for watch-stream reconnects: a blind
    fixed sleep (the old 1.0 s) synchronizes every watcher in the fleet
    into reconnect waves against a recovering etcd; jitter + growth spread
    them out. `attempt` counts consecutive failures since the last healthy
    stream (0-based)."""
    return min(base_s * (2 ** min(attempt, 16)), max_s) * random.uniform(0.5, 1.5)


class EventType(enum.Enum):
    PUT = "PUT"
    DELETE = "DELETE"


@dataclass
class WatchEvent:
    type: EventType
    key: str
    value: str = ""  # empty for DELETE


# Callback receives a batch of events (one etcd watch response may carry many).
WatchCallback = Callable[[List[WatchEvent]], None]


class CoordinationStore:
    """Abstract coordination backend (reference: etcd_client.h:32-144)."""

    # -- plain KV ----------------------------------------------------------
    def get(self, key: str) -> Optional[str]:
        raise NotImplementedError

    def set(self, key: str, value: str, lease_id: int = 0) -> bool:
        raise NotImplementedError

    def remove(self, key: str) -> bool:
        raise NotImplementedError

    def get_prefix(self, prefix: str) -> Dict[str, str]:
        raise NotImplementedError

    def set_many(self, kvs: Dict[str, str], lease_id: int = 0) -> bool:
        ok = True
        for k, v in kvs.items():
            ok = self.set(k, v, lease_id) and ok
        return ok

    # -- watches -----------------------------------------------------------
    def add_watch(self, prefix: str, callback: WatchCallback) -> int:
        raise NotImplementedError

    def remove_watch(self, watch_id: int) -> None:
        raise NotImplementedError

    # -- leases ------------------------------------------------------------
    def grant_lease(self, ttl_s: float) -> int:
        raise NotImplementedError

    def keepalive(self, lease_id: int) -> bool:
        """Refresh; False if the lease already expired."""
        raise NotImplementedError

    def revoke_lease(self, lease_id: int) -> None:
        raise NotImplementedError

    # -- transactions ------------------------------------------------------
    def compare_create(self, key: str, value: str, lease_id: int = 0) -> bool:
        """Atomically create `key` iff it does not exist (election txn,
        reference: etcd_client.cpp:47-62). True iff this caller won."""
        raise NotImplementedError

    def guarded_remove(self, keys: List[str], guard_key: str, guard_value: str) -> bool:
        """Delete `keys` iff guard_key still holds guard_value
        (reference: etcd_client.cpp:90-99 re-checks mastership)."""
        raise NotImplementedError

    def compare_create_with_epoch(
        self, key: str, value: str, epoch_key: str, lease_id: int = 0
    ) -> int:
        """Election txn WITH fencing: atomically create `key` iff absent
        AND bump the monotonically increasing counter at `epoch_key`
        (unleased — it must outlive every master) in the SAME transaction.
        Returns the new epoch (>= 1) when this caller won, 0 otherwise.

        The epoch is the split-brain fence: every master->instance RPC
        carries it, instances persist the highest seen and reject lower —
        a deposed-but-unaware master's dispatches are structurally
        rejected (docs/FAULT_TOLERANCE.md, control plane).

        Default implementation composes compare_create + set (atomic
        enough for single-writer backends); MemoryStore and
        EtcdGatewayStore override with genuinely transactional versions.
        """
        if not self.compare_create(key, value, lease_id):
            return 0
        epoch = int(self.get(epoch_key) or 0) + 1
        self.set(epoch_key, str(epoch))
        return epoch

    def close(self) -> None:
        pass

    # -- typed helpers (reference: templated JSON get/set, etcd_client.h) --
    def get_json(self, key: str) -> Optional[Any]:
        raw = self.get(key)
        return None if raw is None else json.loads(raw)

    def set_json(self, key: str, value: Any, lease_id: int = 0) -> bool:
        return self.set(key, json.dumps(value), lease_id)


# ---------------------------------------------------------------------------
# In-memory backend
# ---------------------------------------------------------------------------


# MemoryStore's sweeper: its period, and how late a sweep must come before
# the process counts as having been stalled (MemoryStore._sweep_once).
_SWEEP_PERIOD_S = 0.05
_STALL_GRACE_S = 0.5


class _Lease:
    __slots__ = ("lease_id", "ttl_s", "expires_at", "keys")

    def __init__(self, lease_id: int, ttl_s: float, now: float):
        self.lease_id = lease_id
        self.ttl_s = ttl_s
        self.expires_at = now + ttl_s
        self.keys: set = set()


class MemoryStore(CoordinationStore):
    """Process-local store with full etcd semantics.

    Watch callbacks run on a dedicated notifier thread (the reference defers
    watch handling to a threadpool for the same deadlock-avoidance reason,
    instance_mgr.cpp:58-67); lease expiry runs on a sweeper thread and
    produces DELETE events exactly like an etcd lease timeout.
    """

    def __init__(self, clock=None) -> None:
        # `clock`: monotonic-seconds callable driving LEASE TIME only
        # (watch/notify stay real-threaded). Tests that don't exercise
        # liveness inject a frozen clock so leases can never expire
        # underneath them — an XLA compile hogging the GIL past a
        # wall-clock TTL was the suite's recurring flake (rounds 1-2);
        # failure-detection tests advance a manual clock instead of
        # sleeping.
        self._clock = clock or time.monotonic
        self._mu = threading.RLock()
        self._kv: Dict[str, str] = {}
        self._key_lease: Dict[str, int] = {}
        self._leases: Dict[int, _Lease] = {}
        self._watches: Dict[int, Tuple[str, WatchCallback]] = {}
        self._next_watch_id = 1
        self._next_lease_id = 1
        self._event_q: List[List[WatchEvent]] = []
        self._event_cv = threading.Condition(self._mu)
        self._closed = False
        self._notifier = threading.Thread(
            target=self._notify_loop, name="memstore-notify", daemon=True
        )
        self._sweeper = threading.Thread(
            target=self._sweep_loop, name="memstore-sweep", daemon=True
        )
        self._notifier.start()
        self._sweeper.start()

    # -- internals ---------------------------------------------------------
    def _emit(self, events: List[WatchEvent]) -> None:
        # caller holds _mu
        if events:
            self._event_q.append(events)
            self._event_cv.notify_all()

    def _notify_loop(self) -> None:
        while True:
            with self._mu:
                while not self._event_q and not self._closed:
                    self._event_cv.wait(timeout=0.5)
                if self._closed and not self._event_q:
                    return
                batch = self._event_q.pop(0)
                watches = list(self._watches.values())
            for prefix, cb in watches:
                sub = [e for e in batch if e.key.startswith(prefix)]
                if sub:
                    try:
                        # Chaos hook: a dropped delivery simulates a lost
                        # etcd watch response (one watcher misses one
                        # batch; liveness then rests on prefix re-scans /
                        # lease expiry, exactly as with a real etcd blip).
                        faults.point(
                            "store.watch", prefix=prefix, key=sub[0].key
                        )
                        cb(sub)
                    except Exception:  # watch callbacks must not kill the loop
                        pass

    def _sweep_loop(self) -> None:
        last = time.monotonic()
        while True:
            time.sleep(_SWEEP_PERIOD_S)
            woke = time.monotonic()
            stalled_s, last = woke - last - _SWEEP_PERIOD_S, woke
            if not self._sweep_once(stalled_s):
                return

    def _sweep_once(self, stalled_s: float = 0.0) -> bool:
        """Expire what is due; False once the store is closed.

        `stalled_s` is how late this sweep came on the real clock. This
        store and every holder of its leases live in ONE process, so a
        sweep that is seconds late (a C call that kept the GIL: device
        start-up, a compile, a large executable being serialized) means
        no holder could send its keepalive either. The lost time is given
        back to every lease, as etcd extends leases after a stalled
        leader, instead of fencing a master for the store's own absence.
        Lease time on an injected clock is the test's to move: untouched."""
        with self._mu:
            if self._closed:
                return False
            if stalled_s > _STALL_GRACE_S and self._clock is time.monotonic:
                for lease in self._leases.values():
                    lease.expires_at += stalled_s
            now = self._clock()
            expired = [l for l in self._leases.values() if l.expires_at <= now]
            events: List[WatchEvent] = []
            for lease in expired:
                for key in lease.keys:
                    if self._key_lease.get(key) == lease.lease_id:
                        self._kv.pop(key, None)
                        self._key_lease.pop(key, None)
                        events.append(WatchEvent(EventType.DELETE, key))
                del self._leases[lease.lease_id]
            self._emit(events)
            return True

    def _attach(self, key: str, lease_id: int) -> None:
        # caller holds _mu
        old = self._key_lease.pop(key, None)
        if old is not None and old in self._leases:
            self._leases[old].keys.discard(key)
        if lease_id:
            if lease_id not in self._leases:
                raise KeyError(f"unknown lease {lease_id}")
            self._leases[lease_id].keys.add(key)
            self._key_lease[key] = lease_id

    # -- KV ----------------------------------------------------------------
    def get(self, key: str) -> Optional[str]:
        with self._mu:
            return self._kv.get(key)

    def set(self, key: str, value: str, lease_id: int = 0) -> bool:
        with self._mu:
            if lease_id and lease_id not in self._leases:
                return False
            self._kv[key] = value
            self._attach(key, lease_id)
            self._emit([WatchEvent(EventType.PUT, key, value)])
            return True

    def remove(self, key: str) -> bool:
        with self._mu:
            if key not in self._kv:
                return False
            del self._kv[key]
            self._attach(key, 0)
            self._emit([WatchEvent(EventType.DELETE, key)])
            return True

    def get_prefix(self, prefix: str) -> Dict[str, str]:
        with self._mu:
            return {k: v for k, v in self._kv.items() if k.startswith(prefix)}

    # -- watches -----------------------------------------------------------
    def add_watch(self, prefix: str, callback: WatchCallback) -> int:
        with self._mu:
            wid = self._next_watch_id
            self._next_watch_id += 1
            self._watches[wid] = (prefix, callback)
            return wid

    def remove_watch(self, watch_id: int) -> None:
        with self._mu:
            self._watches.pop(watch_id, None)

    # -- leases ------------------------------------------------------------
    def grant_lease(self, ttl_s: float) -> int:
        with self._mu:
            lid = self._next_lease_id
            self._next_lease_id += 1
            self._leases[lid] = _Lease(lid, ttl_s, self._clock())
            return lid

    def keepalive(self, lease_id: int) -> bool:
        with self._mu:
            lease = self._leases.get(lease_id)
            if lease is None:
                return False
            lease.expires_at = self._clock() + lease.ttl_s
            return True

    def revoke_lease(self, lease_id: int) -> None:
        with self._mu:
            lease = self._leases.pop(lease_id, None)
            if lease is None:
                return
            events = []
            for key in lease.keys:
                if self._key_lease.get(key) == lease_id:
                    self._kv.pop(key, None)
                    self._key_lease.pop(key, None)
                    events.append(WatchEvent(EventType.DELETE, key))
            self._emit(events)

    # -- txns --------------------------------------------------------------
    def compare_create(self, key: str, value: str, lease_id: int = 0) -> bool:
        with self._mu:
            if key in self._kv:
                return False
            if lease_id and lease_id not in self._leases:
                return False
            self._kv[key] = value
            self._attach(key, lease_id)
            self._emit([WatchEvent(EventType.PUT, key, value)])
            return True

    def compare_create_with_epoch(
        self, key: str, value: str, epoch_key: str, lease_id: int = 0
    ) -> int:
        with self._mu:
            if key in self._kv:
                return 0
            if lease_id and lease_id not in self._leases:
                return 0
            epoch = int(self._kv.get(epoch_key, "0")) + 1
            self._kv[key] = value
            self._attach(key, lease_id)
            self._kv[epoch_key] = str(epoch)
            self._attach(epoch_key, 0)  # the fence outlives the lease
            self._emit([
                WatchEvent(EventType.PUT, key, value),
                WatchEvent(EventType.PUT, epoch_key, str(epoch)),
            ])
            return epoch

    def guarded_remove(self, keys: List[str], guard_key: str, guard_value: str) -> bool:
        with self._mu:
            if self._kv.get(guard_key) != guard_value:
                return False
            events = []
            for key in keys:
                if key in self._kv:
                    del self._kv[key]
                    self._attach(key, 0)
                    events.append(WatchEvent(EventType.DELETE, key))
            self._emit(events)
            return True

    def close(self) -> None:
        with self._mu:
            self._closed = True
            self._event_cv.notify_all()
        # Join OUTSIDE _mu (both loops need it to observe _closed), and
        # never from a watch callback running on the notifier itself.
        me = threading.current_thread()
        if self._notifier is not me:
            self._notifier.join(timeout=2)
        if self._sweeper is not me:
            self._sweeper.join(timeout=2)

    # Test hook: force-expire a lease without waiting for wall-clock TTL.
    def expire_lease_now(self, lease_id: int) -> None:
        with self._mu:
            lease = self._leases.get(lease_id)
            if lease is not None:
                lease.expires_at = 0.0


# Process-global named namespaces: "memory://ns" returns the same store for
# every component in this process, which is how tests wire a service replica
# set and fake instances together without sockets.
_MEMORY_STORES: Dict[str, MemoryStore] = {}
_MEMORY_MU = threading.Lock()


def _memory_store(namespace: str) -> MemoryStore:
    with _MEMORY_MU:
        st = _MEMORY_STORES.get(namespace)
        if st is None:
            st = MemoryStore()
            _MEMORY_STORES[namespace] = st
        return st


def reset_memory_namespace(namespace: str = "") -> None:
    """Drop a named in-process store (test isolation)."""
    with _MEMORY_MU:
        st = _MEMORY_STORES.pop(namespace, None)
    if st is not None:
        st.close()


# ---------------------------------------------------------------------------
# etcd v3 HTTP/JSON gateway backend
# ---------------------------------------------------------------------------


def _b64(s: str) -> str:
    return base64.b64encode(s.encode()).decode()


def _unb64(s: str) -> str:
    return base64.b64decode(s).decode()


def _prefix_range_end(prefix: str) -> str:
    b = bytearray(prefix.encode())
    for i in reversed(range(len(b))):
        if b[i] < 0xFF:
            b[i] += 1
            return bytes(b[: i + 1]).decode("latin-1")
    return "\0"


class EtcdGatewayStore(CoordinationStore):
    """etcd v3 over its HTTP/JSON gateway (/v3/kv/..., /v3/lease/...).

    Matches the reference's etcd-cpp-apiv3 usage (etcd_client.cpp) without a
    client library. Watches are long-poll streams on /v3/watch, one reader
    thread per watch. This backend is exercised only when an etcd endpoint is
    reachable; unit tests use MemoryStore.
    """

    def __init__(self, addr: str):
        self._base = f"http://{addr}"
        self._watches: Dict[int, Tuple[threading.Thread, Any]] = {}
        self._next_watch_id = 1
        self._mu = threading.Lock()
        # Connectivity ping, mirroring the reference ctor's PING put
        # (etcd_client.cpp:24-33) — fail fast if etcd is unreachable.
        self._post("/v3/kv/put", {"key": _b64("XLLM:SERVICE:PING"), "value": _b64("1")})

    def _post(self, path: str, body: Dict[str, Any], timeout: float = 5.0) -> Dict[str, Any]:
        req = urllib.request.Request(
            self._base + path,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())

    def get(self, key: str) -> Optional[str]:
        r = self._post("/v3/kv/range", {"key": _b64(key)})
        kvs = r.get("kvs", [])
        return _unb64(kvs[0]["value"]) if kvs else None

    def set(self, key: str, value: str, lease_id: int = 0) -> bool:
        body: Dict[str, Any] = {"key": _b64(key), "value": _b64(value)}
        if lease_id:
            body["lease"] = str(lease_id)
        self._post("/v3/kv/put", body)
        return True

    def remove(self, key: str) -> bool:
        r = self._post("/v3/kv/deleterange", {"key": _b64(key)})
        return int(r.get("deleted", 0)) > 0

    def get_prefix(self, prefix: str) -> Dict[str, str]:
        r = self._post(
            "/v3/kv/range",
            {"key": _b64(prefix), "range_end": _b64(_prefix_range_end(prefix))},
        )
        return {_unb64(kv["key"]): _unb64(kv["value"]) for kv in r.get("kvs", [])}

    def grant_lease(self, ttl_s: float) -> int:
        r = self._post("/v3/lease/grant", {"TTL": str(max(1, int(ttl_s)))})
        return int(r["ID"])

    def keepalive(self, lease_id: int) -> bool:
        r = self._post("/v3/lease/keepalive", {"ID": str(lease_id)})
        return int(r.get("result", {}).get("TTL", 0)) > 0

    def revoke_lease(self, lease_id: int) -> None:
        self._post("/v3/lease/revoke", {"ID": str(lease_id)})

    def compare_create(self, key: str, value: str, lease_id: int = 0) -> bool:
        put: Dict[str, Any] = {"key": _b64(key), "value": _b64(value)}
        if lease_id:
            put["lease"] = str(lease_id)
        r = self._post(
            "/v3/kv/txn",
            {
                # create_revision == 0  <=>  key absent (reference election txn)
                "compare": [
                    {"key": _b64(key), "target": "CREATE", "create_revision": "0"}
                ],
                "success": [{"request_put": put}],
            },
        )
        return bool(r.get("succeeded", False))

    def compare_create_with_epoch(
        self, key: str, value: str, epoch_key: str, lease_id: int = 0
    ) -> int:
        """One etcd txn: [master absent AND epoch unchanged since read]
        -> [put master (leased), put epoch+1 (unleased)]. The epoch
        compare closes the read->txn window: two candidates racing the
        same vacancy both read epoch N, but only the txn winner commits
        N+1 — the loser's compare fails and it re-reads."""
        put_master: Dict[str, Any] = {"key": _b64(key), "value": _b64(value)}
        if lease_id:
            put_master["lease"] = str(lease_id)
        for _ in range(8):
            cur = self.get(epoch_key)
            nxt = int(cur or 0) + 1
            compare: List[Dict[str, Any]] = [
                {"key": _b64(key), "target": "CREATE", "create_revision": "0"}
            ]
            if cur is None:
                compare.append(
                    {"key": _b64(epoch_key), "target": "CREATE",
                     "create_revision": "0"}
                )
            else:
                compare.append(
                    {"key": _b64(epoch_key), "target": "VALUE",
                     "value": _b64(cur)}
                )
            r = self._post(
                "/v3/kv/txn",
                {
                    "compare": compare,
                    "success": [
                        {"request_put": put_master},
                        {"request_put": {
                            "key": _b64(epoch_key), "value": _b64(str(nxt))
                        }},
                    ],
                },
            )
            if r.get("succeeded", False):
                return nxt
            if self.get(key) is not None:
                return 0  # someone else holds the master key: lost
            # epoch moved under us (a master won and died inside the
            # window) — re-read and retry the txn
        return 0

    def guarded_remove(self, keys: List[str], guard_key: str, guard_value: str) -> bool:
        r = self._post(
            "/v3/kv/txn",
            {
                "compare": [
                    {"key": _b64(guard_key), "target": "VALUE", "value": _b64(guard_value)}
                ],
                "success": [
                    {"request_delete_range": {"key": _b64(k)}} for k in keys
                ],
            },
        )
        return bool(r.get("succeeded", False))

    def add_watch(self, prefix: str, callback: WatchCallback) -> int:
        stop = threading.Event()

        def reader() -> None:
            body = json.dumps(
                {
                    "create_request": {
                        "key": _b64(prefix),
                        "range_end": _b64(_prefix_range_end(prefix)),
                    }
                }
            ).encode()
            failures = 0
            while not stop.is_set():
                try:
                    req = urllib.request.Request(
                        self._base + "/v3/watch",
                        data=body,
                        headers={"Content-Type": "application/json"},
                    )
                    with urllib.request.urlopen(req, timeout=3600) as resp:
                        for line in resp:
                            if stop.is_set():
                                return
                            # A delivered response proves the stream is
                            # healthy again: reset the backoff ladder.
                            failures = 0
                            msg = json.loads(line.decode())
                            events = []
                            for ev in msg.get("result", {}).get("events", []):
                                kv = ev.get("kv", {})
                                etype = (
                                    EventType.DELETE
                                    if ev.get("type") == "DELETE"
                                    else EventType.PUT
                                )
                                events.append(
                                    WatchEvent(
                                        etype,
                                        _unb64(kv.get("key", "")),
                                        _unb64(kv["value"]) if kv.get("value") else "",
                                    )
                                )
                            if events:
                                callback(events)
                except Exception:
                    if not stop.is_set():
                        # Jittered exponential reconnect (counted): the
                        # old blind 1.0 s sleep marched every watcher in
                        # the fleet into synchronized reconnect storms
                        # against a recovering etcd.
                        _count_watch_reconnect()
                        time.sleep(_watch_backoff_s(failures))
                        failures += 1

        t = threading.Thread(target=reader, name=f"etcd-watch-{prefix}", daemon=True)
        t.start()
        with self._mu:
            wid = self._next_watch_id
            self._next_watch_id += 1
            self._watches[wid] = (t, stop)
            return wid

    def remove_watch(self, watch_id: int) -> None:
        with self._mu:
            entry = self._watches.pop(watch_id, None)
        if entry is not None:
            entry[1].set()


def connect(addr: str) -> CoordinationStore:
    """Open a coordination backend from an address string
    (reference: --etcd_addr flag, global_gflags.cpp)."""
    if addr.startswith("memory://"):
        return _memory_store(addr[len("memory://"):])
    if addr.startswith("etcd://"):
        return EtcdGatewayStore(addr[len("etcd://"):])
    # Bare host:port means etcd, matching the reference flag format.
    return EtcdGatewayStore(addr)
