"""Service-tier orchestration core.

TPU-native redesign of the reference Scheduler
(reference: xllm_service/scheduler/scheduler.{h,cpp}): owns the tokenizer +
chat template, the coordination store + master election, the cluster
managers and routing policy, the request registry, and the per-request
output strands. `schedule()` is the request hot path (template -> tokenize
-> policy -> metrics, scheduler.cpp:73-106); `handle_generations()` the
token hot path (a pushed batch delivered in one pass, serialized per
request, :293-336); the master loop replicates cluster state every
heartbeat period (:113-121).

Additions over the reference, per SURVEY.md §5/§7:
  * hybrid online/offline admission — `offline` requests are parked under
    cluster pressure and re-dispatched when load drops (the reference only
    declares the flag, request.h:38);
  * real disconnected-instance pruning on the master loop;
  * graceful stop that drains instead of the reference's exit(1) handler.
"""

from __future__ import annotations

import logging
import math
import os
import re
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

from xllm_service_tpu.cluster.encoder_fabric import EncoderFabric
from xllm_service_tpu.cluster.global_kvcache_mgr import GlobalKVCacheMgr
from xllm_service_tpu.cluster.goodput import GoodputController
from xllm_service_tpu.cluster.instance_mgr import HealthState, InstanceMgr
from xllm_service_tpu.cluster.policies import LoadBalancePolicy, make_policy
from xllm_service_tpu.cluster.prefix_fabric import PrefixFabric
from xllm_service_tpu.common.config import ServiceConfig
from xllm_service_tpu.common.types import (
    FinishReason,
    KvCacheEvent,
    LatencyMetrics,
    LoadMetrics,
    RequestAction,
    RequestOutput,
    Routing,
    SequenceOutput,
    Status,
    StatusCode,
    Usage,
)
from xllm_service_tpu.common import faults
from xllm_service_tpu.coordination import store as coord_store
from xllm_service_tpu.coordination.election import (
    MASTER_RPC_KEY,
    MasterElection,
)
from xllm_service_tpu.coordination.store import CoordinationStore, connect
from xllm_service_tpu.obs import (
    LATENCY_BUCKETS_MS,
    FlightRecorder,
    MetricsRegistry,
    SpanRing,
)
from xllm_service_tpu.service.admission import AdmissionController
from xllm_service_tpu.service.ordered_streams import HopThreads, Strand
from xllm_service_tpu.service.request import (
    RequestTracer,
    ServiceRequest,
    StopStringMonitor,
)
from xllm_service_tpu.service.response_handler import (
    ClientStream,
    ResponseHandler,
    accumulate_sequences,
)
from xllm_service_tpu.tokenizer import ChatTemplate, Tokenizer, create_tokenizer

logger = logging.getLogger(__name__)

# Park offline work when every prefill candidate has this many waiters.
OFFLINE_PRESSURE_WAITING = 4

# Control-plane mastership states (docs/FAULT_TOLERANCE.md):
#   STANDBY     — not holding the lease; the front door redirects to the
#                 current master and this replica never dispatches;
#   RECONCILING — lease just won; new work is PARKED (not 500'd) while
#                 the takeover scan rebuilds per-instance load, inflight
#                 charges, and the KV index from instance /reconcile
#                 manifests;
#   ACTIVE      — reconciled; dispatch flows.
MASTER_STANDBY = "standby"
MASTER_RECONCILING = "reconciling"
MASTER_ACTIVE = "active"

# How long a dispatch parks behind an in-flight reconcile before giving
# up (reconciles are one bounded RPC per instance — seconds, not minutes).
RECONCILE_PARK_TIMEOUT_S = 15.0


class NotMasterError(RuntimeError):
    """Raised by the dispatch wrapper when this replica is not the ACTIVE
    master: a demoted master must stop dispatching IMMEDIATELY (epoch
    fencing makes the instance reject it anyway; this stops the attempt
    at the source)."""


@dataclass
class _RequestState:
    request: ServiceRequest
    stream: ClientStream
    # Serializes this request's deliveries, failure and fences
    # (service/ordered_streams.py).
    strand: Strand = field(default_factory=Strand)
    # api-tier hook to propagate cancellation to the engine instance
    cancel_callback: Optional[Callable[[], None]] = None
    # api-tier hook to (re-)forward the request to its routed prefill
    # instance; enables automatic re-dispatch after instance death
    dispatch: Optional[Callable[[], None]] = None
    redispatch_count: int = 0
    first_chunk_sent: bool = False
    prefill_finished: bool = False
    # Dispatch-attempt epoch: bumped on every replay; outputs arriving
    # under an older wire id (request.wire_srid) are late pushes from a
    # dead attempt and must never reach the client stream.
    attempt: int = 0
    # Thread id of an in-flight replay (0 = none): two failure signals —
    # e.g. the master's dispatch-exception handler and the removal
    # listener — must not replay the same request concurrently (double
    # dispatch); same-thread re-entry stays allowed for nested recovery.
    replaying: int = 0
    # Monotonic stamp of an in-flight resume (cleared by the first fresh
    # delivery; feeds the resume-latency histogram).
    resume_mono: float = 0.0
    # Observability timestamps (one monotonic clock): registration,
    # first dispatch, first token, and the latest token delivery.
    sched_mono: float = 0.0
    dispatch_mono: float = 0.0
    first_token_mono: float = 0.0
    last_token_mono: float = 0.0
    # Error-finish marker (fail_request): finish_request reports the
    # outcome as "error" instead of "cancelled".
    failed: bool = False
    # Per-sequence stop-string matchers (OpenAI `stop`), lazily created.
    stop_monitors: Dict[int, "StopStringMonitor"] = field(default_factory=dict)
    # Generated tokens dropped by stop truncation (subtracted from usage).
    stop_dropped: int = 0
    # accumulated per-sequence state for non-stream responses
    acc: Dict[int, SequenceOutput] = field(default_factory=dict)
    usage: Optional[Usage] = None
    done: bool = False


class Scheduler:
    def __init__(
        self,
        config: ServiceConfig,
        store: Optional[CoordinationStore] = None,
        tokenizer: Optional[Tokenizer] = None,
        identity: str = "",
        clock: Optional[Callable[[], float]] = None,
    ) -> None:
        self._config = config
        # Injectable monotonic clock for the CONTROL-plane components
        # whose expiry/EWMA decisions must be testable and simulatable
        # (instance health, goodput freshness, admission buckets). The
        # request-path latency histograms stay on time.monotonic — they
        # time real work. None = wall monotonic.
        self._ctrl_clock: Callable[[], float] = clock or time.monotonic
        self._store = store if store is not None else connect(config.etcd_addr)
        self._tokenizer = tokenizer or create_tokenizer(config.tokenizer_path)
        self._chat_template = ChatTemplate(self._tokenizer)
        # Always-on flight-recorder ring (obs/flight.py): every lifecycle
        # span the tracer emits is mirrored here regardless of
        # --enable_request_trace, so the master always has a recent-span
        # window to dump on anomalies and to serve GET /trace from.
        self.span_ring = SpanRing(
            "master",
            int(
                os.environ.get("XLLM_TRACE_RING", "")
                or getattr(config, "trace_ring_capacity", 2048)
            ),
        )
        self._tracer = RequestTracer(
            config.trace_dir, config.enable_request_trace,
            keep=getattr(config, "trace_keep", 1), ring=self.span_ring,
        )
        # Which instances participated in each request's trace (prefill /
        # decode / encode names recorded at every dispatch attempt),
        # bounded so finished requests stay collectable for a while.
        self._trace_parts: "OrderedDict[str, List[str]]" = OrderedDict()
        # Installed by the Master: transport for role-flip notifications
        # ((instance_name, new_role) -> POST instance /flip).
        self.on_role_flip = None
        # Installed by the Master: takeover-reconciliation transport
        # ((meta, body) -> instance POST /reconcile response dict).
        self.on_reconcile = None
        # Installed by the Master: this replica's instance-plane address,
        # advertised under the election lease so deposed masters can
        # re-point heartbeating instances at the successor.
        self.advertised_rpc = ""

        # Mastership state machine (docs/FAULT_TOLERANCE.md): dispatch is
        # gated on ACTIVE; RECONCILING parks it, STANDBY rejects it.
        self._master_state = MASTER_STANDBY
        self._dispatch_gate = threading.Event()
        self._reconcile_thread: Optional[threading.Thread] = None
        self._takeover_elected_mono = 0.0
        # Bench/report surfaces (plain attrs; the histograms below carry
        # the same numbers into /metrics).
        self.last_takeover_ms: Optional[float] = None
        self.takeover_first_dispatch_ms: Optional[float] = None
        self.total_reconciled = 0
        self.total_orphaned = 0

        # Service-tier metrics registry (obs.metrics): the master's
        # /metrics renders this alongside the HTTP-plane registries and
        # the scraped per-instance expositions.
        self.metrics = MetricsRegistry()
        # Anomaly flight recorder: dumps the span ring to
        # <trace_dir>/flight on SLO breach / breaker ejection triggers
        # (instances run their own; docs/OBSERVABILITY.md).
        self.flight = FlightRecorder(
            self.span_ring,
            os.path.join(config.trace_dir, "flight"),
            registry=self.metrics,
        )
        self._m_requests = self.metrics.counter(
            "xllm_service_requests_total",
            "Requests accepted by schedule()", labelnames=("kind",),
        )
        self._m_finished = self.metrics.counter(
            "xllm_service_finished_total",
            "Requests finished by outcome", labelnames=("outcome",),
        )
        self.metrics.counter(
            "xllm_service_redispatches_total",
            "Requests transparently replayed after instance death",
        ).set_function(lambda: self.total_redispatches)
        self.metrics.counter(
            "xllm_service_redispatch_attempts_total",
            "Replay attempts (redispatch + resume), successful or not",
        ).set_function(lambda: self.total_redispatch_attempts)
        self.metrics.counter(
            "xllm_service_resumes_total",
            "Mid-stream token-replay resumes completed after instance "
            "death",
        ).set_function(lambda: self.total_resumes)
        self._m_batch_size = self.metrics.histogram(
            "xllm_service_generations_batch_size",
            "Engine outputs per pushed /rpc/generations batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
        )
        deliveries = self.metrics.counter(
            "xllm_service_deliveries_total",
            "Outputs delivered to their client streams, by the thread "
            "that ran the delivery: inline = the one that pushed it, "
            "queued = another (the request's strand was busy, or its "
            "stream's writes can block and took a thread hop)",
            labelnames=("ran",),
        )
        self._m_delivered_inline = deliveries.labels(ran="inline")
        self._m_delivered_queued = deliveries.labels(ran="queued")
        self.m_cancel_errors = self.metrics.counter(
            "xllm_service_cancel_errors_total",
            "Instance /cancel calls that failed (previously swallowed "
            "silently)",
        )
        self._m_resume_latency = self.metrics.histogram(
            "xllm_service_resume_latency_ms",
            "Resume initiation -> first post-resume token delivery",
            buckets=LATENCY_BUCKETS_MS,
        )
        self._m_ttft = self.metrics.histogram(
            "xllm_service_ttft_ms",
            "Client-perceived time to first token (schedule -> first "
            "delivery)", buckets=LATENCY_BUCKETS_MS,
        )
        self._m_tpot = self.metrics.histogram(
            "xllm_service_tpot_ms",
            "Inter-delivery gap after the first token",
            buckets=LATENCY_BUCKETS_MS,
        )
        self._m_queue_delay = self.metrics.histogram(
            "xllm_service_queue_delay_ms",
            "Schedule -> first dispatch to an instance (offline parking "
            "included)", buckets=LATENCY_BUCKETS_MS,
        )
        self._m_e2e = self.metrics.histogram(
            "xllm_service_e2e_ms",
            "Schedule -> terminal bookkeeping", buckets=LATENCY_BUCKETS_MS,
        )
        self.metrics.gauge(
            "xllm_service_inflight_requests", "Registered, unfinished "
            "requests",
        ).set_function(lambda: self.num_inflight)
        self.metrics.gauge(
            "xllm_service_is_master", "1 when this replica holds the "
            "master lease",
        ).set_function(lambda: int(self._election.is_master))
        self.metrics.gauge(
            "xllm_service_offline_parked_requests", "Offline requests "
            "parked under cluster pressure",
        ).set_function(lambda: len(self._offline_parked))
        self.metrics.counter(
            "xllm_service_trace_dropped_total", "Trace records lost to "
            "disk-write failures",
        ).set_function(lambda: self._tracer.dropped)
        self.metrics.gauge(
            "xllm_master_epoch", "Fencing epoch of this replica's most "
            "recent won master term (0 = never elected)",
        ).set_function(lambda: self._election.epoch)
        self._m_takeover = self.metrics.histogram(
            "xllm_master_takeover_ms",
            "Master takeover: lease won -> reconciliation complete "
            "(dispatch unparked)", buckets=LATENCY_BUCKETS_MS,
        )
        self.metrics.counter(
            "xllm_service_reconciled_requests_total",
            "In-flight instance manifests reclaimed by a takeover "
            "reconciliation (orphans are reaped instance-side and counted "
            "in xllm_service_orphan_reaped_total there)",
        ).set_function(lambda: self.total_reconciled)
        self.metrics.counter(
            "xllm_coord_watch_reconnects_total",
            "Coordination-store watch streams reconnected after a "
            "failure (jittered exponential backoff)",
        ).set_function(coord_store.watch_reconnects_total)

        self._election = MasterElection(
            self._store,
            identity=identity or f"{config.host}:{config.http_port}",
            lease_ttl_s=config.master_lease_ttl_s,
            on_elected=self._on_elected,
            on_lost=self._on_lost,
        )
        self._instance_mgr = InstanceMgr(
            self._store,
            is_master=lambda: self._election.is_master,
            detect_disconnected_interval_s=(
                config.detect_disconnected_instance_interval_s
            ),
            suspect_failures=getattr(config, "breaker_suspect_failures", 2),
            eject_failures=getattr(config, "breaker_eject_failures", 4),
            clock=self._ctrl_clock,
        )
        self._kvcache_mgr = GlobalKVCacheMgr(
            self._store,
            is_master=lambda: self._election.is_master,
            block_size=config.block_size,
            murmur_hash3_seed=config.murmur_hash3_seed,
        )
        # Fleet-wide prefix KV fabric (cluster/prefix_fabric.py): fetch
        # hints at dispatch, fetch-cost-adjusted CAR scoring, and the
        # coordinated-eviction decisions behind /rpc/fabric/evict_offer.
        self.prefix_fabric = PrefixFabric(
            config, self._instance_mgr, self._kvcache_mgr,
            metrics=self.metrics, span_hook=self.span_ring.emit,
        )
        # Encoder fabric (cluster/encoder_fabric.py, docs/EPD.md): the
        # fleet media-embedding index behind hit-aware encoder routing.
        # Fed by ENCODE-role heartbeat cache deltas; pruned/resynced with
        # the same breaker hardening as the KV index.
        self.encoder_fabric = EncoderFabric(
            config, self._instance_mgr, metrics=self.metrics,
            span_hook=self.span_ring.emit,
        )
        # Goodput controller plane (cluster/goodput.py): per-request
        # colocate-vs-disaggregate placement consulted in schedule(),
        # plus the periodic role-reshaping tick on the master loop.
        self.goodput = GoodputController(
            config, self._instance_mgr, metrics=self.metrics,
            clock=self._ctrl_clock,
        )
        # Front-door admission (service/admission.py): per-tenant rate +
        # inflight caps with fair-share queuing; consulted at the very
        # top of schedule(), released at terminal request bookkeeping.
        self.admission = AdmissionController(
            config, metrics=self.metrics, clock=self._ctrl_clock,
        )
        self._policy: LoadBalancePolicy = make_policy(
            config.load_balance_policy,
            self._instance_mgr,
            self._kvcache_mgr,
            target_ttft_ms=config.target_ttft_ms,
            target_tpot_ms=config.target_tpot_ms,
            fabric=self.prefix_fabric,
        )
        self._response_handler = ResponseHandler()
        # Threads for what must not run on a pusher's thread: a strand
        # whose stream's writes can block, an upstream cancel. None is
        # started until one is needed (the event backend's streams never
        # block, so a batch of tokens is delivered by its receiver).
        self._hop = HopThreads(config.num_ordered_output_streams)
        # Re-dispatch interrupted requests when their instance dies (the
        # reference only promises this — README.md:46; its failure surface
        # is an error-finish, SURVEY.md §3.5 note).
        self._instance_mgr.add_removal_listener(self._on_instance_removed)
        self._instance_mgr.add_removal_listener(
            self._kvcache_mgr.remove_instance
        )
        self._instance_mgr.add_removal_listener(
            self.encoder_fabric.remove_instance
        )
        # Stale-location pruning: an EJECTED instance's KV-index locations
        # would otherwise linger until lease expiry, letting cache-aware
        # routing (and the fabric's fetch planner) score phantom hits on
        # an unroutable peer. Deregistration/prune is covered by the
        # removal listener above; this covers the breaker path. Pruned
        # instances are flagged for a full cache resync on their next
        # heartbeat (deltas cannot rebuild dropped locations).
        self._cache_resync_needed: set = set()
        self._instance_mgr.add_health_listener(self._on_instance_health)
        self.max_redispatch = getattr(config, "max_redispatch", 2)
        # Cluster-lifetime fault accounting (aggregated /metrics +
        # bench_serving's fault-injection report).
        self.total_redispatches = 0
        self.total_redispatch_attempts = 0
        self.total_resumes = 0

        self._mu = threading.Lock()
        self._requests: Dict[str, _RequestState] = {}
        # parked offline work: (request, dispatch_callback)
        self._offline_parked: Deque = deque()

        self._stop = threading.Event()
        self._master_thread = threading.Thread(
            target=self._master_loop, name="scheduler-master", daemon=True
        )
        self._master_thread.start()
        # Campaign LAST: a synchronous win fires _on_elected, whose
        # reconcile thread touches every manager constructed above.
        self._election.start()

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    @property
    def is_master(self) -> bool:
        return self._election.is_master

    @property
    def master_state(self) -> str:
        return self._master_state

    @property
    def master_epoch(self) -> int:
        """Fencing epoch stamped on every master->instance RPC."""
        return self._election.epoch

    @property
    def election_identity(self) -> str:
        return self._election.identity

    def current_master_identity(self) -> str:
        """The identity (host:http_port) holding the master lease NOW —
        the redirect target for a standby's front door."""
        try:
            return self._election.current_master() or ""
        except Exception:
            return ""

    # ------------------------------------------------------------------ #
    # fenced master failover (docs/FAULT_TOLERANCE.md, control plane)
    # ------------------------------------------------------------------ #

    def _on_elected(self) -> None:
        """Lease won (epoch committed in the same store txn). Enter
        RECONCILING — new work parks, nothing dispatches — and rebuild
        cluster state from instance manifests on a dedicated thread (this
        callback may run on the store's watch-notifier thread, which must
        never block on instance RPCs)."""
        epoch = self._election.epoch
        with self._mu:
            self._master_state = MASTER_RECONCILING
            self._takeover_elected_mono = time.monotonic()
            self.takeover_first_dispatch_ms = None
        logger.info(
            "elected master (epoch %d): reconciling cluster state", epoch
        )
        t = threading.Thread(
            target=self._reconcile_run, args=(epoch,),
            name="master-reconcile", daemon=True,
        )
        # published once started: stop() joins what it finds here, and a
        # join before start() has returned raises
        t.start()
        self._reconcile_thread = t

    def _on_lost(self) -> None:
        """Demoted (lease lost / store partition): stop dispatching NOW.
        In-flight exchanges are error-finished so their clients retry
        against the current master instead of hanging on a replica whose
        RPCs the fleet now rejects; the front door (api tier) redirects
        from here on."""
        with self._mu:
            self._master_state = MASTER_STANDBY
            self._dispatch_gate.clear()
            inflight = [
                s.request.service_request_id
                for s in self._requests.values()
                if not s.done
            ]
        cur = self.current_master_identity()
        logger.warning(
            "demoted from master (epoch %d was fenced); failing %d "
            "in-flight requests toward current master %s",
            self._election.epoch, len(inflight), cur or "<none>",
        )
        for srid in inflight:
            self.fail_request(
                srid,
                StatusCode.UNAVAILABLE,
                "master demoted mid-request; retry against current "
                f"master {cur or 'unknown'}",
            )

    def _reconcile_run(self, epoch: int) -> None:
        """Takeover reconciliation: for every registered instance, pull
        its in-flight manifest over POST /reconcile and rebuild the
        per-instance request charges, load metrics, and the global KV
        index. Manifest entries this master does not claim (`known`) are
        reaped instance-side after the advertised TTL — no KV leaks from
        a dead master's requests. Any instance failure is skipped: a dead
        instance must not block the takeover (its state re-syncs through
        heartbeats or pruning)."""
        t0 = time.monotonic()
        takeover = epoch > 1  # epoch 1 = cluster birth, nothing to reclaim
        try:
            instances = self._instance_mgr.list_instances()
            if instances:
                # The transport is installed by the api tier right after
                # construction; tolerate that boot-order window.
                deadline = t0 + 2.0
                while (
                    self.on_reconcile is None
                    and time.monotonic() < deadline
                    and not self._stop.is_set()
                ):
                    time.sleep(0.02)
            with self._mu:
                known_by_instance: Dict[str, set] = {}
                for s in self._requests.values():
                    if s.done:
                        continue
                    wire = (
                        s.request.wire_srid
                        or s.request.service_request_id
                    )
                    for name in {
                        s.request.routing.prefill_name,
                        s.request.routing.decode_name,
                    }:
                        if name:
                            known_by_instance.setdefault(name, set()).add(
                                wire
                            )
            if self.on_reconcile is not None:
                for meta in instances:
                    if self._stop.is_set():
                        return
                    # Epoch-keyed abandonment: a demote -> re-elect cycle
                    # starts a NEW reconcile thread for the new term;
                    # this one must stop even though the state reads
                    # RECONCILING again (it belongs to the new epoch).
                    if (
                        self._master_state != MASTER_RECONCILING
                        or self._election.epoch != epoch
                    ):
                        return
                    self._reconcile_instance(
                        meta, epoch,
                        sorted(known_by_instance.get(meta.name, ())),
                    )
        finally:
            # Only the thread whose term is STILL current completes the
            # takeover: an abandoned term must neither unpark dispatch
            # against a half-rebuilt view nor record a takeover sample.
            flipped = False
            with self._mu:
                if (
                    self._master_state == MASTER_RECONCILING
                    and self._election.epoch == epoch
                ):
                    self._master_state = MASTER_ACTIVE
                    self._dispatch_gate.set()
                    flipped = True
            if flipped:
                self.advertise_master_rpc()
                ms = (time.monotonic() - t0) * 1000.0
                if takeover:
                    self._m_takeover.observe(ms)
                    self.last_takeover_ms = ms
                logger.info(
                    "reconciliation complete in %.1f ms (reclaimed=%d "
                    "orphaned=%d)", ms, self.total_reconciled,
                    self.total_orphaned,
                )

    def _reconcile_instance(self, meta, epoch: int, known: List[str]) -> None:
        body = {
            "master_epoch": epoch,
            "master": self._election.identity,
            "known": known,
            "orphan_ttl_s": getattr(
                self._config, "reconcile_orphan_ttl_s", 10.0
            ),
        }
        try:
            # Chaos hook: a dropped/errored reconcile exercises the
            # skip-and-continue path (heartbeats re-sync the instance).
            faults.point("reconcile.send", instance=meta.name, epoch=epoch)
            resp = self.on_reconcile(meta, body)
        except Exception as e:
            logger.warning("reconcile of %s failed: %s", meta.name, e)
            return
        if self._election.epoch != epoch:
            # Term changed while the RPC was in flight: the new term's
            # thread owns absorption (a stale absorb would double-count
            # and schedule a duplicate orphan unwind).
            return
        if not isinstance(resp, dict) or not resp.get("ok"):
            logger.warning("reconcile of %s rejected: %s", meta.name, resp)
            return
        manifest = resp.get("manifest") or []
        load = resp.get("load_metrics")
        self._instance_mgr.absorb_reconcile(
            meta.name,
            LoadMetrics.from_json(load) if load else None,
            manifest,
        )
        try:
            hashes = [
                bytes.fromhex(x) for x in resp.get("cache_hashes") or []
            ]
        except ValueError:
            hashes = []
        if hashes:
            self._kvcache_mgr.absorb_instance_snapshot(meta.name, hashes)
        known_set = set(known)
        reclaimed = sum(
            1 for ent in manifest
            if ent.get("service_request_id") in known_set
        )
        orphans = [
            ent for ent in manifest
            if ent.get("service_request_id") not in known_set
        ]
        with self._mu:
            self.total_reconciled += reclaimed
            self.total_orphaned += len(orphans)
        if orphans:
            # The instance reaps unclaimed manifests at the orphan TTL
            # (engine work cancelled, blocks freed); unwind the charges
            # absorbed above on the same clock so the load accounting
            # doesn't carry dead requests forever.
            t = threading.Timer(
                float(body["orphan_ttl_s"]) + 1.0,
                self._unwind_orphan_charges, args=(meta.name, orphans),
            )
            t.daemon = True
            t.start()

    def _unwind_orphan_charges(self, name: str, entries: List[Dict]) -> None:
        routing = Routing(prefill_name=name, decode_name=name)
        for ent in entries:
            try:
                delivered = int(ent.get("delivered_tokens", 0))
                prompt_toks = int(ent.get("prompt_tokens", 0))
            except (TypeError, ValueError):
                continue
            self._instance_mgr.update_request_metrics(
                routing,
                RequestAction.FINISH_DECODE
                if delivered > 0
                else RequestAction.CANCEL,
                prompt_toks,
            )

    def advertise_master_rpc(self) -> None:
        """Publish this master's instance-plane address under its
        election lease: the key dies with the master, and a deposed
        replica hands its current value to heartbeating instances — the
        re-point path that covers instances a /reconcile never reached."""
        if not self.advertised_rpc or not self._election.is_master:
            return
        try:
            self._store.set(
                MASTER_RPC_KEY, self.advertised_rpc,
                lease_id=self._election._lease_id,
            )
        except Exception:
            logger.debug("master rpc advertisement failed", exc_info=True)

    def current_master_rpc(self) -> str:
        """The ACTIVE master's advertised instance-plane address ('' when
        none) — what a deposed master hints to heartbeating instances."""
        try:
            return self._store.get(MASTER_RPC_KEY) or ""
        except Exception:
            return ""

    def _dispatch_allowed(self) -> bool:
        """Gate every master->instance forward on mastership: ACTIVE
        dispatches, RECONCILING parks (bounded wait — reconciles are one
        RPC per instance), STANDBY refuses."""
        if self._dispatch_gate.is_set():
            return True
        if self._master_state == MASTER_RECONCILING:
            self._dispatch_gate.wait(RECONCILE_PARK_TIMEOUT_S)
        return self._dispatch_gate.is_set()

    @property
    def instance_mgr(self) -> InstanceMgr:
        return self._instance_mgr

    @property
    def kvcache_mgr(self) -> GlobalKVCacheMgr:
        return self._kvcache_mgr

    @property
    def tokenizer(self) -> Tokenizer:
        return self._tokenizer

    @property
    def tracer(self) -> RequestTracer:
        return self._tracer

    def record_trace_participants(self, srid: str, names) -> None:
        """Remember which instances took part in one request's trace
        (every dispatch attempt's prefill/decode/encode trio) so the
        GET /trace collector knows whose rings to pull — bounded LRU, so
        recently finished requests stay collectable."""
        with self._mu:
            cur = self._trace_parts.setdefault(srid, [])
            for n in names:
                if n and n not in cur:
                    cur.append(n)
            self._trace_parts.move_to_end(srid)
            while len(self._trace_parts) > 512:
                self._trace_parts.popitem(last=False)

    def trace_participants(self, srid: str) -> List[str]:
        with self._mu:
            return list(self._trace_parts.get(srid, ()))

    @property
    def num_inflight(self) -> int:
        with self._mu:
            return len(self._requests)

    def stop(self, drain_timeout_s: float = 10.0) -> None:
        """Graceful drain (the reference's SIGINT handler calls exit(1),
        master.cpp:143-147 — its stop path is dead code)."""
        deadline = time.monotonic() + drain_timeout_s
        while self.num_inflight and time.monotonic() < deadline:
            time.sleep(0.05)
        self._stop.set()
        # Unblock any dispatch parked behind an in-flight reconcile.
        self._dispatch_gate.set()
        t = self._reconcile_thread
        if t is not None:
            t.join(timeout=2.0)
        self._master_thread.join(timeout=2.0)
        self._hop.shutdown()
        self._instance_mgr.close()
        self._kvcache_mgr.close()
        self._election.stop()
        self._tracer.close()

    def _master_loop(self) -> None:
        """Heartbeat-period state replication + liveness backstop
        (reference: update_master_service_heartbeat, scheduler.cpp:113-121)."""
        period = self._config.heartbeat_interval_s
        while not self._stop.wait(period):
            self.run_master_upkeep()

    def run_master_upkeep(self) -> None:
        """One master-loop iteration, callable out-of-band: the fleet
        simulator (cluster/fleet_sim) drives this at SIMULATED heartbeat
        cadence while the real loop idles on a huge interval."""
        self._pump_offline()
        self._notify_flips()
        # Master-only upkeep runs only once RECONCILED: pruning with a
        # half-rebuilt heartbeat view would mass-evict live instances
        # on the first post-takeover tick.
        if self._master_state != MASTER_ACTIVE:
            return
        try:
            self._kvcache_mgr.upload_kvcache()
            self._instance_mgr.upload_load_metrics()
            # Goodput reshaping: at most one hysteresis-damped,
            # drain-aware role flip per tick (no-op when the
            # controller is off or the fleet census already fits).
            self.goodput.tick()
            # Autoscaling signals (wanted role counts + encoder
            # headroom gauges) ride the same cadence — reshaping
            # re-slices the fleet we have, the signals say how big
            # it should be.
            self.goodput.autoscale_signals()
            # Health breaker upkeep: silent instances turn suspect
            # before the prune backstop removes them, and ejected ones
            # get an active /health probe toward probation.
            self._instance_mgr.mark_stale_suspects()
            self._instance_mgr.probe_unhealthy()
            # pruning fires the removal listeners (re-dispatch + cache
            # index cleanup)
            self._instance_mgr.prune_disconnected()
        except Exception:
            logger.exception("master loop iteration failed")

    def _notify_flips(self) -> None:
        """Tell flipped instances their new role (round-1 weak item 8:
        the registry mutated but the engine never learned it flipped).
        The transport callback is installed by the Master (HTTP POST to
        the instance's /flip); flips are rare, so one daemon thread per
        event keeps the loop unblocked."""
        if self.on_role_flip is None:
            return
        for name, attempt in self._instance_mgr.take_flip_events():
            threading.Thread(
                target=self.on_role_flip,
                args=(name, attempt),
                name=f"flip-notify-{name}",
                daemon=True,
            ).start()

    # ------------------------------------------------------------------ #
    # request hot path
    # ------------------------------------------------------------------ #

    def route_only(self, token_ids=()):
        """Pick an instance pair without registering a generation request —
        one-shot synchronous calls (/v1/embeddings) that still want the
        policy's load/affinity view. None when no instances exist."""
        routing = self._policy.select_instances_pair(list(token_ids))
        if not routing.prefill_name and not routing.decode_name:
            return None
        if not routing.prefill_name:
            routing.prefill_name = routing.decode_name
        return routing

    def schedule(self, request: ServiceRequest) -> Status:
        """Admission gate -> template -> tokenize -> route. Admission
        runs FIRST (a shed must not pay the tokenizer), and any non-OK
        outcome below returns the admitted slot immediately — only an
        OK schedule holds it until finish_request."""
        shed = self.admission.acquire(request)
        if shed is not None:
            self._tracer.stage(
                request.service_request_id, "shed",
                tenant=request.tenant,
                retry_after_s=request.retry_after_s,
            )
            return shed
        status = self._schedule_admitted(request)
        if not status.ok():
            self.admission.release(request)
        return status

    def _schedule_admitted(self, request: ServiceRequest) -> Status:
        """Template -> tokenize -> route (reference: scheduler.cpp:73-106).
        Fills request.token_ids, request.routing, request.estimated_ttft_ms."""
        self._tracer.stage(
            request.service_request_id, "receive",
            kind="chat" if request.is_chat else "completion",
            stream=request.stream, offline=request.offline,
        )
        if request.is_chat and not request.prompt:
            try:
                request.prompt = self._chat_template.apply(
                    request.messages, request.tools
                )
            except Exception as e:
                return Status(StatusCode.INVALID_ARGUMENT, f"chat template: {e}")
        media_status = self._expand_media(request)
        if media_status is not None:
            return media_status
        if not request.token_ids:
            if not request.prompt:
                return Status(StatusCode.INVALID_ARGUMENT, "empty prompt")
            request.token_ids = self._tokenizer.encode(request.prompt)
        if not request.token_ids:
            return Status(StatusCode.INVALID_ARGUMENT, "prompt tokenized to nothing")
        self._tracer.stage(
            request.service_request_id, "tokenize",
            prompt_tokens=len(request.token_ids),
        )

        # ONE index match per request, shared by the routing policy and
        # the fabric's fetch planner/gauge below — the chained hashing +
        # locked index walk must not run twice on the hot path, and must
        # not run AT ALL when nobody consumes it (RR/SLO routing with the
        # fabric disabled: those fleets never hashed prompts before, and
        # the hit-rate gauge is meaningless with both consumers off).
        # Media prompts bypass the cache (embedding-dependent KV).
        from xllm_service_tpu.cluster.policies import CacheAwareRouting

        want_scores = not request.media_parts and (
            isinstance(self._policy, CacheAwareRouting)
            or self.prefix_fabric.enabled()
        )
        scores = (
            self._kvcache_mgr.match(request.token_ids)
            if want_scores else None
        )
        request.routing = self._policy.select_instances_pair(
            request.token_ids, scores=scores
        )
        if not request.routing.prefill_name and not request.routing.decode_name:
            return Status(StatusCode.UNAVAILABLE, "no instances registered")
        if not request.media_parts:
            # Goodput placement (cluster/goodput.py): colocate the decode
            # onto the routed prefill instance's mixed hot loop when the
            # model says the handoff isn't worth it. Gated decisions
            # (controller off, cold EWMA, non-MIX target, ...) come back
            # "static" and leave the policy's pair untouched.
            try:
                covered = 0
                if scores is not None:
                    covered = int(
                        self.prefix_fabric.effective_matched(
                            request.routing.prefill_name, scores
                        ) * self._config.block_size
                    )
                decision = self.goodput.decide_placement(
                    len(request.token_ids), request.model, request.routing,
                    covered_tokens=covered,
                )
                if decision.mode == "colocate":
                    request.routing.decode_name = request.routing.prefill_name
            except Exception:
                logger.exception("goodput placement decision failed")
        if request.media_parts:
            # Three-stage EPD routing: the encoder runs before prefill.
            # Route by MODALITY — encoders host one tower each — and,
            # with the encoder fabric on, by live queue depth + embedding
            # cache hits instead of blind round-robin (docs/EPD.md). The
            # index match always runs (the fleet hit-rate gauge must not
            # flatline during an A/B hatch flip); only the routing
            # consumer is hatch-gated.
            required = {
                {2: "audio", 4: "video"}.get(len(p["shape"]), "image")
                for p in request.media_parts
            }
            hit_scores = None
            try:
                media_hashes = EncoderFabric.hashes_of(request.media_parts)
                matched = (
                    self.encoder_fabric.match(
                        media_hashes, srid=request.service_request_id
                    )
                    if media_hashes else {}
                )
                if self.encoder_fabric.enabled():
                    hit_scores = matched
            except Exception:
                logger.exception("encoder-fabric match failed")
            request.routing.encode_name = (
                self._instance_mgr.next_encode_instance(
                    required, hit_scores=hit_scores
                )
            )
            if not request.routing.encode_name:
                return Status(
                    StatusCode.UNAVAILABLE,
                    f"media request needs an ENCODE instance serving "
                    f"{sorted(required)}; none registered covers it",
                )
        if scores is not None:
            # Prefix-fabric fetch hint (docs/KV_CACHE.md): when the fleet
            # best match beats the routed instance's, name the holder so
            # the instance can pull the gap instead of recomputing it.
            # On cache-aware fleets plan_fetch also runs fabric-OFF: it
            # feeds the fleet-hit-rate gauge either way (no hint when
            # disabled), so flipping the hatch for an A/B never
            # flatlines the gauge.
            try:
                request.kv_fabric = (
                    self.prefix_fabric.plan_fetch(
                        request.token_ids, request.routing.prefill_name,
                        scores=scores, srid=request.service_request_id,
                    )
                    or {}
                )
            except Exception:
                logger.exception("fabric fetch planning failed")
                request.kv_fabric = {}
        pred = self._instance_mgr.get_time_predictor(request.routing.prefill_name)
        if pred is not None and pred.has_ttft_model:
            request.estimated_ttft_ms = pred.predict_ttft(len(request.token_ids))
        self._instance_mgr.update_request_metrics(
            request.routing, RequestAction.SCHEDULE, len(request.token_ids)
        )
        self._tracer.stage(
            request.service_request_id, "route",
            prefill=request.routing.prefill_name,
            decode=request.routing.decode_name,
        )
        self._m_requests.labels(
            kind="chat" if request.is_chat else "completion"
        ).inc()
        return Status(StatusCode.OK)

    _MM_MARKERS = ("<|image|>", "<|video|>", "<|audio|>")
    _MM_DATA_RE = re.compile(
        r"data:application/x-raw-f32;shape=(\d+)x(\d+)x(\d+);base64,(.*)",
        re.S,
    )
    # Video tensor backdoor: T x H x W x C frames (T even — the qwen2vl
    # temporal_patch_size pairs frames).
    _MM_DATA4_RE = re.compile(
        r"data:application/x-raw-f32;shape=(\d+)x(\d+)x(\d+)x(\d+);"
        r"base64,(.*)",
        re.S,
    )
    # Audio tensor backdoor: num_mel_bins x mel_frames log-mel features.
    _MM_DATA2_RE = re.compile(
        r"data:application/x-raw-f32;shape=(\d+)x(\d+);base64,(.*)",
        re.S,
    )

    def _decode_media_part(self, p):
        """One MMContentPart -> ({type, shape, data}, None) or (None,
        error Status). Real images (data:image/...;base64) decode via PIL
        and preprocess with the configured family's HF pixel math
        (service/image_processor.py); the raw-f32 tensor URI remains as
        the pre-encoded backdoor (tests, non-image media)."""
        import base64 as _b64

        from xllm_service_tpu.service import image_processor as _ip

        url = p.url or ""
        if p.type in ("image", "image_url"):
            try:
                img = _ip.decode_image_url(url)
            except ValueError as e:
                return None, Status(StatusCode.INVALID_ARGUMENT, str(e))
            if img is not None:
                proc = self._config.mm_image_processor
                size = self._config.mm_image_size
                if not proc or not size:
                    return None, Status(
                        StatusCode.INVALID_ARGUMENT,
                        "real-image ingestion is not enabled on this "
                        "deployment (set mm_image_processor and "
                        "mm_image_size to match the ENCODE tower)",
                    )
                if proc == "siglip":
                    arr = _ip.preprocess_siglip(img, size)
                elif proc == "qwen2vl":
                    arr = _ip.preprocess_qwen2vl(img, pinned_size=size)
                else:
                    return None, Status(
                        StatusCode.INVALID_ARGUMENT,
                        f"unknown mm_image_processor {proc!r}",
                    )
                return {
                    "type": p.type,
                    "shape": list(arr.shape),
                    "data": _b64.b64encode(
                        np.ascontiguousarray(arr).tobytes()
                    ).decode(),
                }, None
        if p.type in ("video", "video_url"):
            tps_cfg = max(self._config.mm_temporal_patch_size, 1)
            is_real_video = _ip.is_video_data_url(url)
            proc = self._config.mm_image_processor
            size = self._config.mm_image_size
            if is_real_video and (proc != "qwen2vl" or not size):
                # Config check BEFORE the cv2 decode — a misconfigured
                # deployment must reject for free, not after buffering a
                # whole clip (review finding, r5).
                return None, Status(
                    StatusCode.INVALID_ARGUMENT,
                    "real-video ingestion needs mm_image_processor="
                    "'qwen2vl' and mm_image_size (the video-capable "
                    "tower family)",
                )
            try:
                frames = _ip.decode_video_url(
                    url, max_frames=self._config.mm_video_max_frames,
                    temporal_patch=tps_cfg,
                )
            except ValueError as e:
                return None, Status(StatusCode.INVALID_ARGUMENT, str(e))
            if frames is not None:
                # Real compressed video: per-frame HF pixel math (the
                # qwen2vl family's CLIP normalize, pinned to the tower's
                # square) -> the 4D f32 tensor the encode stage carries.
                arr = np.stack([
                    _ip.preprocess_qwen2vl(f, pinned_size=size)
                    for f in frames
                ])
                return {
                    "type": p.type,
                    "shape": list(arr.shape),
                    "data": _b64.b64encode(
                        np.ascontiguousarray(arr).tobytes()
                    ).decode(),
                }, None
            m4 = self._MM_DATA4_RE.match(url)
            if m4:
                T = int(m4.group(1))
                tps = max(self._config.mm_temporal_patch_size, 1)
                if T < tps or T % tps:
                    return None, Status(
                        StatusCode.INVALID_ARGUMENT,
                        f"video needs a frame count that is a positive "
                        f"multiple of temporal_patch_size {tps}, got {T}",
                    )
                return {
                    "type": p.type,
                    "shape": [T] + [int(m4.group(i)) for i in (2, 3, 4)],
                    "data": m4.group(5),
                }, None
        if p.type in ("audio", "audio_url"):
            from xllm_service_tpu.service import audio_processor as _ap

            frames_cfg = self._config.mm_audio_mel_frames
            if _ap.is_audio_data_url(url):
                if not frames_cfg:
                    return None, Status(
                        StatusCode.INVALID_ARGUMENT,
                        "real-audio ingestion is not enabled (set "
                        "mm_audio_mel_frames/mm_audio_mel_bins to the "
                        "ENCODE audio tower's geometry)",
                    )
                try:
                    wav = _ap.decode_audio_url(url)
                except ValueError as e:
                    return None, Status(
                        StatusCode.INVALID_ARGUMENT, str(e)
                    )
                mel = _ap.log_mel(
                    wav, self._config.mm_audio_mel_bins, frames_cfg
                )
                return {
                    "type": p.type,
                    "shape": list(mel.shape),
                    "data": _b64.b64encode(
                        np.ascontiguousarray(mel).tobytes()
                    ).decode(),
                }, None
            m2 = self._MM_DATA2_RE.match(url)
            if m2:
                return {
                    "type": p.type,
                    "shape": [int(m2.group(1)), int(m2.group(2))],
                    "data": m2.group(3),
                }, None
            # NO fallthrough to the image/video tensor regexes: an
            # audio-typed part with a 3D tensor would otherwise be
            # silently ingested as an image, binding wrong embeddings to
            # the audio marker (review finding, r5).
            return None, Status(
                StatusCode.INVALID_ARGUMENT,
                f"unsupported media URL for {p.type}: expected "
                "data:audio/wav;base64 or a "
                "data:application/x-raw-f32;shape=MxT;base64 log-mel "
                "tensor",
            )
        m = self._MM_DATA_RE.match(url)
        if not m:
            return None, Status(
                StatusCode.INVALID_ARGUMENT,
                f"unsupported media URL for {p.type}: expected a "
                "data:image/...;base64 image, data:audio/wav;base64, a "
                "data:application/x-raw-f32;shape=HxWxC;base64 tensor, "
                "(video) ...shape=TxHxWxC, or (audio) ...shape=MxT",
            )
        return {
            "type": p.type,
            "shape": [int(m.group(1)), int(m.group(2)), int(m.group(3))],
            "data": m.group(4),
        }, None

    def _expand_media(self, request: ServiceRequest) -> Optional[Status]:
        """EPD stage-E preparation (SURVEY.md §7 stage 7): media parts in
        chat messages become runs of placeholder tokens in token_ids; the
        raw payloads + placeholder positions ride the request so the master
        can dispatch the encoder before prefill. Returns a Status only on
        error; None means proceed (with or without media)."""
        parts = [
            p
            for m in request.messages
            if isinstance(m.content, list)
            for p in m.content
            if p.type != "text"
        ]
        if not parts:
            return None
        from xllm_service_tpu.service.image_processor import (
            media_content_hash,
        )

        media_parts = []
        for p in parts:
            part, err = self._decode_media_part(p)
            if err is not None:
                return err
            # Content key for the encoder-fabric embedding cache + the
            # master's fleet index (docs/EPD.md): keyed on what the
            # encode stage will actually see, so a re-sent item in a
            # multi-turn chat hits regardless of which encoder served it.
            part["hash"] = media_content_hash(
                {2: "audio", 4: "video"}.get(len(part["shape"]), "img"),
                part["shape"], part["data"],
            )
            media_parts.append(part)
        k = self._config.mm_tokens_per_media
        marker_re = re.compile(
            "(" + "|".join(re.escape(s) for s in self._MM_MARKERS) + ")"
        )
        segments = marker_re.split(request.prompt)
        n_markers = sum(1 for s in segments if s in self._MM_MARKERS)
        if n_markers != len(media_parts):
            # STRICT equality: a literal marker string typed inside a text
            # part would otherwise steal a real image's placeholder slot
            # and bind its embeddings to an attacker-chosen position.
            return Status(
                StatusCode.INVALID_ARGUMENT,
                f"{len(media_parts)} media parts but {n_markers} media "
                "markers in the templated prompt (literal marker text in a "
                "message is not allowed)",
            )
        # Per-part placeholder counts: an image part takes k tokens (the
        # encoder's tokens-per-slice); a video of T frames spans
        # T // tps temporal slices of k tokens each (tps = the tower's
        # temporal_patch_size, config mm_temporal_patch_size). mm_grids
        # carries each part's merged (t, gh, gw) grid for the engine's
        # M-RoPE streams — only when k is a perfect square (the
        # square-tower geometry); otherwise the engine's span inference
        # applies.
        tps = max(self._config.mm_temporal_patch_size, 1)
        s = math.isqrt(k)
        emit_grids = s * s == k
        counts, grids = [], []
        for part in media_parts:
            shape = part["shape"]
            if len(shape) == 2:
                # Audio: tokens are the Whisper conv+pool geometry of
                # the mel length (models/audio.audio_out_tokens); the
                # M-RoPE grid is sequential (t=1, h=1, w=n).
                from xllm_service_tpu.models.audio import audio_out_tokens

                n = audio_out_tokens(shape[1])
                counts.append(n)
                grids.append([1, 1, n])
                continue
            slices = shape[0] // tps if len(shape) == 4 else 1
            counts.append(k * slices)
            grids.append([slices, s, s])
        token_ids: List[int] = []
        positions: List[int] = []
        pi = 0
        for seg in segments:
            if seg in self._MM_MARKERS:
                n = counts[pi]
                pi += 1
                positions.extend(range(len(token_ids), len(token_ids) + n))
                token_ids.extend([0] * n)  # placeholder (pad) tokens
            elif seg:
                token_ids.extend(self._tokenizer.encode(seg))
        request.token_ids = token_ids
        request.mm_positions = positions
        request.media_parts = media_parts
        request.mm_grids = grids if emit_grids else []
        return None

    def should_defer_offline(self, request: ServiceRequest) -> bool:
        """Hybrid scheduling: park offline work while online traffic keeps
        every prefill candidate busy."""
        if not request.offline:
            return False
        load = self._instance_mgr.get_load_metrics()
        candidates = self._instance_mgr.prefill_instances() or list(load)
        if not candidates:
            return False
        return all(
            load.get(n, LoadMetrics()).waiting_requests_num
            >= OFFLINE_PRESSURE_WAITING
            for n in candidates
        )

    def park_offline(
        self, request: ServiceRequest, dispatch: Callable[[], None]
    ) -> None:
        with self._mu:
            self._offline_parked.append((request, dispatch))

    def _pump_offline(self) -> None:
        while True:
            with self._mu:
                if not self._offline_parked:
                    return
                request, dispatch = self._offline_parked[0]
            if self.should_defer_offline(request):
                return
            with self._mu:
                self._offline_parked.popleft()
            try:
                dispatch()
            except NotMasterError as e:
                # Parked work outlived this replica's mastership: error
                # it toward the current master instead of losing it.
                self.fail_request(
                    request.service_request_id,
                    StatusCode.UNAVAILABLE, str(e),
                )
            except Exception:
                logger.exception("offline dispatch failed")

    def record_new_request(
        self,
        request: ServiceRequest,
        stream: ClientStream,
        cancel_callback: Optional[Callable[[], None]] = None,
        dispatch: Optional[Callable[[], None]] = None,
    ) -> Optional[Callable[[], None]]:
        """Register the response route for a scheduled request
        (reference: scheduler.cpp:171-266). Returns the dispatch callable
        the caller should invoke: it wraps the one passed in with span +
        queue-delay instrumentation, and re-dispatch reuses the same
        wrapper so every forward attempt is timed."""
        if self._tracer.enabled:
            request.trace_callback = self._tracer.bind(request.service_request_id)
            request.trace(
                "in",
                {
                    "model": request.model,
                    "stream": request.stream,
                    "prompt_tokens": len(request.token_ids),
                    "routing": request.routing.to_json(),
                },
            )
        state = _RequestState(
            request=request,
            stream=stream,
            cancel_callback=cancel_callback,
            sched_mono=time.monotonic(),
        )
        request.wire_srid = request.service_request_id

        if dispatch is not None:
            def dispatch_instrumented() -> None:
                # Mastership gate (docs/FAULT_TOLERANCE.md): a demoted
                # replica must never forward — the fleet would reject its
                # stale epoch anyway; refusing here keeps the failure on
                # this side of the wire. A reconciling master PARKS the
                # dispatch instead (bounded), so takeover never 500s work
                # that arrived mid-transition.
                if not self._dispatch_allowed():
                    raise NotMasterError(
                        "not the active master (state="
                        f"{self._master_state}); current master is "
                        f"{self.current_master_identity() or 'unknown'}"
                    )
                now = time.monotonic()
                first = state.dispatch_mono == 0.0
                if first:
                    state.dispatch_mono = now
                    self._m_queue_delay.observe(
                        (now - state.sched_mono) * 1000.0
                    )
                    if (
                        self.takeover_first_dispatch_ms is None
                        and self._takeover_elected_mono
                        and self._election.epoch > 1
                    ):
                        # Takeover-to-first-dispatch: the acceptance
                        # number the chaos bench reports.
                        self.takeover_first_dispatch_ms = (
                            (now - self._takeover_elected_mono) * 1000.0
                        )
                self._tracer.stage(
                    request.service_request_id, "dispatch",
                    prefill=request.routing.prefill_name,
                    attempt=state.redispatch_count + 1,
                )
                # Trace-collector participant set: every attempt's routed
                # trio, so GET /trace knows which rings to pull.
                self.record_trace_participants(
                    request.service_request_id,
                    (
                        request.routing.prefill_name,
                        request.routing.decode_name,
                        request.routing.encode_name,
                    ),
                )
                dispatch()

            state.dispatch = dispatch_instrumented
        with self._mu:
            self._requests[request.service_request_id] = state
        return state.dispatch

    # ------------------------------------------------------------------ #
    # token hot path
    # ------------------------------------------------------------------ #

    def handle_generations(
        self, outputs: List[RequestOutput]
    ) -> Dict[str, bool]:
        """One pushed batch of engine steps (reference:
        scheduler.cpp:293-336, once per output there). Returns the
        continue map by wire id: False when the request is unknown
        (finished/cancelled) OR the output carries a stale attempt's wire
        id — both tell the pusher to stop the upstream stream. Outputs
        arrive keyed by the attempt-versioned wire id (`<srid>` or
        `<srid>#rN`, service/request.py); a replaced attempt's late
        pushes must not interleave with the live one.

        The admitted outputs are delivered in batch order on the
        caller's thread, each through its request's strand: a request
        that another thread is delivering to, and a stream whose writes
        can block, take theirs on that other thread instead."""
        self._m_batch_size.observe(len(outputs))
        bases = [o.service_request_id.partition("#r")[0] for o in outputs]
        with self._mu:
            states = [self._requests.get(b) for b in bases]
        cont: Dict[str, bool] = {}
        inline = queued = 0
        for output, state in zip(outputs, states):
            wire = output.service_request_id
            if (
                state is None
                or state.done
                # late push from a replaced dispatch attempt
                or wire != (
                    state.request.wire_srid
                    or state.request.service_request_id
                )
            ):
                cont[wire] = False
                continue
            cont[wire] = True
            if self._submit(
                state, lambda s=state, o=output: self._deliver(s, o)
            ):
                inline += 1
            else:
                queued += 1
        if inline:
            self._m_delivered_inline.inc(inline)
        if queued:
            self._m_delivered_queued.inc(queued)
        return cont

    def handle_generation(self, output: RequestOutput) -> bool:
        """A batch of one (the fleet simulator and tests push this way)."""
        return self.handle_generations([output])[output.service_request_id]

    def _submit(self, state: _RequestState, fn: Callable[[], None]) -> bool:
        """Run `fn` in the request's strand. True when it has run on this
        thread; False when another thread runs it: the strand was busy,
        or the stream's writes can block and must not hold up whatever
        else this thread has to deliver."""
        return state.strand.submit(
            fn,
            self._hop
            if getattr(state.stream, "writes_can_block", False)
            else None,
        )

    def _deliver(self, state: _RequestState, output: RequestOutput) -> None:
        if state.done:
            # finish_request/fail_request won the race while this step sat
            # queued in the strand — never write after the exchange ended.
            return
        request = state.request
        if output.service_request_id != (
            request.wire_srid or request.service_request_id
        ):
            # A resume raced this already-queued delivery: the attempt it
            # belongs to was replaced after handle_generation admitted it.
            return
        if request.resume_base and output.usage is not None:
            # Normalize the resumed attempt's local view back to the
            # client's: the replayed tokens ride as prompt on the wire
            # (prompt + acc), but the client sees them as generated.
            output.usage.num_prompt_tokens = max(
                0, output.usage.num_prompt_tokens - request.resume_base
            )
            output.usage.num_generated_tokens += request.resume_base
        if request.stop:
            self._apply_stop_strings(state, output)
            if output.usage is not None and state.stop_dropped:
                # The engine's cumulative usage counts tokens the stop
                # truncation dropped — report what the client received.
                output.usage.num_generated_tokens = max(
                    0, output.usage.num_generated_tokens - state.stop_dropped
                )
        new_tokens = sum(len(seq.token_ids) for seq in output.outputs)
        if new_tokens:
            now = time.monotonic()
            if state.first_token_mono == 0.0:
                state.first_token_mono = now
                ttft_ms = (now - state.sched_mono) * 1000.0
                self._m_ttft.observe(ttft_ms)
                self._tracer.stage(
                    request.service_request_id, "first_token",
                    ttft_ms=round(ttft_ms, 3),
                )
                # Anomaly trigger: TTFT past the configured SLO dumps the
                # flight ring (hatch XLLM_TRACE_SLO_TTFT_MS; 0 = off).
                # Once per request, never per token.
                slo = float(
                    os.environ.get("XLLM_TRACE_SLO_TTFT_MS", "")
                    or getattr(self._config, "trace_slo_ttft_ms", 0.0)
                    or 0.0
                )
                if slo and ttft_ms > slo:
                    self.flight.trigger(
                        "slo_ttft", request.service_request_id,
                        ttft_ms=round(ttft_ms, 3), slo_ms=slo,
                    )
            else:
                # Per-TOKEN time: a delivery may carry several tokens
                # (speculative decode, RPC-batched chunks) — observing the
                # raw gap would read k x the client-perceived TPOT.
                self._m_tpot.observe(
                    (now - state.last_token_mono) * 1000.0 / new_tokens
                )
                if self._tracer.enabled:
                    self._tracer.stage(
                        request.service_request_id, "decode",
                        n_tokens=new_tokens,
                    )
            state.last_token_mono = now
            if state.resume_mono:
                self._m_resume_latency.observe(
                    (now - state.resume_mono) * 1000.0
                )
                state.resume_mono = 0.0
            request.num_generated_tokens += new_tokens
            if not state.prefill_finished:
                state.prefill_finished = True
                self._instance_mgr.update_request_metrics(
                    request.routing,
                    RequestAction.FINISH_PREFILL,
                    # Must mirror the SCHEDULE charge exactly: a resumed
                    # attempt was charged for prompt + replayed tokens.
                    len(request.resume_token_ids or request.token_ids),
                )
            self._instance_mgr.update_request_metrics(
                request.routing, RequestAction.GENERATE, new_tokens
            )

        if request.stream:
            if not output.status.ok() and not output.status.code == StatusCode.CANCELLED:
                # Engine-side failure mid-stream (or at admission): surface
                # it instead of closing as a clean empty stream.
                state.stream.finish_with_error(
                    output.status.code, output.status.message
                )
                self.finish_request(request.service_request_id, cancelled=True)
                return
            if request.resumable:
                # Streams keep the same delivered-token accumulator the
                # non-stream path fills: it is the replay source a
                # mid-stream resume rebuilds the request from.
                self._accumulate(state, output)
            ok = self._response_handler.send_delta_to_client(
                state.stream, request, output, state.first_chunk_sent
            )
            state.first_chunk_sent = True
            if not ok and not output.finished:
                self._cancel(state)
                return
        else:
            self._accumulate(state, output)
            if output.finished or not output.status.ok():
                final = RequestOutput(
                    request_id=output.request_id,
                    service_request_id=output.service_request_id,
                    status=output.status,
                    outputs=sorted(state.acc.values(), key=lambda s: s.index),
                    usage=state.usage,
                    finished=True,
                )
                self._response_handler.send_result_to_client(
                    state.stream, request, final
                )
        if output.finished or not output.status.ok():
            self.finish_request(
                request.service_request_id,
                cancelled=not output.status.ok()
                and output.status.code == StatusCode.CANCELLED,
            )

    def _apply_stop_strings(
        self, state: _RequestState, output: RequestOutput
    ) -> None:
        """OpenAI `stop` sequences, enforced on the service tier where the
        detokenized text stream lives (stops can span token boundaries —
        each sequence's matcher holds back partial matches). When every
        sequence has stopped, the output is force-finished and the engine
        side is cancelled (it would otherwise keep generating discarded
        tokens)."""
        request = state.request
        for seq in output.outputs:
            mon = state.stop_monitors.get(seq.index)
            if mon is None:
                mon = state.stop_monitors[seq.index] = StopStringMonitor(
                    request.stop
                )
            if mon.stopped:
                # Post-stop tail from the engine: drop entirely, and keep
                # asserting the STOP reason — the engine's later natural
                # finish (length/eos) must not overwrite it in accumulation
                # or emit a contradictory finish_reason delta (n>1: the
                # engine keeps generating this child until all stop).
                state.stop_dropped += len(seq.token_ids)
                seq.text = ""
                seq.token_ids = []
                seq.logprobs = []
                seq.finish_reason = FinishReason.STOP
                continue
            pushed = seq.text or ""
            emit, hit = mon.push(pushed)
            if hit:
                seq.finish_reason = FinishReason.STOP
                # Align token-level fields with the truncated text: exact
                # per-token boundaries aren't visible at this tier (the
                # instance detokenized), so keep a character-proportional
                # share of this chunk's tokens — post-stop tokens must not
                # leak into logprobs/usage/GENERATE metrics.
                if pushed and seq.token_ids:
                    import math as _math

                    keep = min(
                        len(seq.token_ids),
                        _math.ceil(
                            len(emit) / len(pushed) * len(seq.token_ids)
                        ),
                    )
                    state.stop_dropped += len(seq.token_ids) - keep
                    seq.token_ids = seq.token_ids[:keep]
                    seq.logprobs = seq.logprobs[:keep]
            elif output.finished or seq.finish_reason != FinishReason.NONE:
                # THIS sequence ended naturally (n>1: a child can finish
                # before the request-level finished flag) — release any
                # held-back stop-prefix text.
                emit += mon.flush()
            seq.text = emit
        n = max(request.n, 1)
        if (
            not output.finished
            and len(state.stop_monitors) >= n
            and all(m.stopped for m in state.stop_monitors.values())
        ):
            output.finished = True
            # Stop the engine's generation; the finish below is CLEAN
            # (finish_reason stop), not a client cancel.
            self._cancel_upstream(state)

    def _accumulate(self, state: _RequestState, output: RequestOutput) -> None:
        accumulate_sequences(state.acc, output)
        if output.usage is not None:
            state.usage = output.usage

    def _cancel(self, state: _RequestState) -> None:
        """Client went away mid-stream: unwind metrics + tell the engine
        (reference cancels via the OutputCallback returning false)."""
        self._cancel_upstream(state)
        self.finish_request(state.request.service_request_id, cancelled=True)

    def _cancel_upstream(self, state: _RequestState) -> None:
        """Tell the routed instances to stop generating. That is an RPC
        (api/master.py `_cancel_on_instance`, seconds when a peer is
        dead), so it takes a hop thread: the delivery that asked for it
        is one of a batch, and the instance that pushed the batch waits
        for its answer."""
        if state.cancel_callback is not None:
            self._hop.submit(state.cancel_callback)

    def finish_request(self, service_request_id: str, cancelled: bool = False) -> None:
        """Terminal bookkeeping (reference: scheduler.cpp:268-291).

        A request cancelled BEFORE its first token unwinds the queued
        prefill work (CANCEL); once FINISH_PREFILL has fired, the prefill
        counters were already decremented and only the decode slot is open,
        so any termination — clean or cancelled — closes it with
        FINISH_DECODE (a CANCEL here would double-decrement prefill and
        corrupt other requests' counts)."""
        with self._mu:
            state = self._requests.pop(service_request_id, None)
        if state is None or state.done:
            return
        state.done = True
        request = state.request
        # Return the admission slot the moment the stream is terminal —
        # a parked fair-queue waiter gets it before this method even
        # finishes its metric bookkeeping. Idempotent (release no-ops on
        # an already-released request).
        self.admission.release(request)
        action = (
            RequestAction.CANCEL
            if cancelled and not state.prefill_finished
            else RequestAction.FINISH_DECODE
        )
        self._instance_mgr.update_request_metrics(
            request.routing, action,
            # Mirror the live attempt's SCHEDULE charge (a resumed
            # attempt was charged for prompt + replayed tokens).
            len(request.resume_token_ids or request.token_ids),
        )
        now = time.monotonic()
        if state.sched_mono:
            self._m_e2e.observe((now - state.sched_mono) * 1000.0)
        outcome = (
            "error" if state.failed
            else "cancelled" if cancelled
            else "ok"
        )
        self._m_finished.labels(outcome=outcome).inc()
        if outcome == "ok":
            # Clean completions feed the goodput controller's per-tenant
            # decode-length EWMA (cancelled/errored lengths would bias
            # the predictor low).
            self.goodput.observe_completion(
                request.model, request.num_generated_tokens
            )
        terminal = {"ok": "finish", "error": "error"}.get(outcome, "cancel")
        self._tracer.stage(
            service_request_id, terminal,
            outcome=outcome,
            generated_tokens=request.num_generated_tokens,
        )

    def fail_request(self, service_request_id: str, code: StatusCode, msg: str) -> None:
        """Error-finish from the API tier (e.g. prefill POST failed —
        reference: handle_first_response cntl->Failed, service.cpp:101-106)."""
        with self._mu:
            state = self._requests.get(service_request_id)
        if state is None:
            return
        self._tracer.stage(
            service_request_id, "error", code=int(code), message=msg
        )
        state.failed = True  # finish_request reports outcome="error"
        self._submit(
            state,
            lambda: (
                state.stream.finish_with_error(code, msg),
                self.finish_request(service_request_id, cancelled=True),
            ),
        )

    # ------------------------------------------------------------------ #
    # fault handling: interrupted-request re-dispatch
    # ------------------------------------------------------------------ #

    def _on_instance_health(self, name: str, state: str) -> None:
        """Breaker transition: ejection retracts the instance's KV-index
        locations so routing/fetch planning stop scoring phantom hits.
        Heartbeats carry DELTAS, so the prune also flags the instance for
        a full cache resync — the next heartbeat response asks it to fold
        its committed-block snapshot into a stored delta, rebuilding the
        index once the instance is reachable again."""
        if state == HealthState.EJECTED:
            # Anomaly trigger: a breaker ejection is exactly the moment
            # the recent-span window explains what went wrong.
            self.flight.trigger("breaker_ejection", instance=name)
            self._kvcache_mgr.remove_instance(name)
            # Encoder fabric parity: an ejected encoder's embedding-index
            # locations are phantom hits for hit-aware routing too; the
            # same armed resync rebuilds them from its LRU snapshot.
            self.encoder_fabric.remove_instance(name)
            with self._mu:
                self._cache_resync_needed.add(name)

    def take_cache_resync(self, name: str) -> bool:
        """Pop the pending cache-resync flag for one instance (called by
        the master's heartbeat handler; the flag rides the response).
        The flag stays armed WHILE the instance remains ejected — a
        partitioned instance whose beats still arrive must not re-index
        blocks nobody can fetch (evict_decisions would count them as live
        replicas and let the real last copy die). Best-effort thereafter:
        a lost response re-flags only on the next ejection, which is also
        the only path that loses index state."""
        with self._mu:
            if name not in self._cache_resync_needed:
                return False
        if self._instance_mgr.health_state(name) == HealthState.EJECTED:
            return False  # keep armed until the breaker re-admits it
        with self._mu:
            self._cache_resync_needed.discard(name)
        return True

    def _on_instance_removed(self, name: str) -> None:
        """An instance left the registry (lease expiry / prune). Requests
        routed to it that have produced NO tokens yet are re-routed and
        re-forwarded transparently; requests already mid-stream resume by
        token replay (prompt + every delivered token re-dispatched to a
        survivor); only when neither works does the request error-finish."""
        with self._mu:
            affected = [
                s
                for s in self._requests.values()
                if not s.done
                and name
                in (s.request.routing.prefill_name, s.request.routing.decode_name)
            ]
        for state in affected:
            srid = state.request.service_request_id
            if not (
                self.redispatch_request(srid, exclude=name)
                or self.resume_request(srid, exclude=name)
            ):
                self.fail_request(
                    srid,
                    StatusCode.UNAVAILABLE,
                    f"instance {name} died mid-generation",
                )

    def _route_excluding(self, token_ids: List[int], exclude: str):
        """Policy pair choice that never lands on `exclude` (the registry
        may still list the failed instance — fast-fail beats lease
        expiry). Returns None when no viable pair exists."""
        routing = self._policy.select_instances_pair(token_ids)
        if exclude and routing.prefill_name == exclude:
            candidates = [
                n
                for n in (
                    self._instance_mgr.routable_prefill_instances()
                    + self._instance_mgr.routable_decode_instances()
                )
                if n != exclude
            ]
            if not candidates:
                return None
            routing.prefill_name = self._instance_mgr.least_loaded(candidates)
        if exclude and routing.decode_name == exclude:
            routing.decode_name = routing.prefill_name
        if not routing.prefill_name and not routing.decode_name:
            return None
        return routing

    def _bump_attempt(self, state: _RequestState) -> None:
        """Advance the dispatch-attempt epoch: outputs pushed under the
        previous wire id are rejected from here on (handle_generation and
        the queued-delivery check in _deliver)."""
        with self._mu:
            state.attempt += 1
            state.request.wire_srid = (
                f"{state.request.service_request_id}#r{state.attempt}"
            )

    def _drain_strand(self, state: _RequestState) -> None:
        """Barrier on the request's strand: any delivery admitted BEFORE
        the attempt bump finishes writing (client + acc) before we
        snapshot the delivered tokens. An idle strand runs the fence at
        once. Never called from inside the strand."""
        fence = threading.Event()
        self._submit(state, fence.set)
        fence.wait(timeout=5.0)

    def redispatch_request(
        self, service_request_id: str, exclude: str = ""
    ) -> bool:
        """Re-route + re-forward a request whose instance failed. Only safe
        before any token reached the client (mid-stream requests go through
        resume_request's token replay); bounded by max_redispatch.
        Returns False when the request cannot be replayed (caller decides
        how to fail it)."""
        me = threading.get_ident()
        with self._mu:
            state = self._requests.get(service_request_id)
            if state is None or state.done:
                return False
            request = state.request
            if (
                request.num_generated_tokens > 0
                or state.dispatch is None
                or state.redispatch_count >= self.max_redispatch
                # another thread is already replaying this request (the
                # dispatch-failure handler racing the removal listener):
                # a second concurrent replay would double-dispatch it
                or state.replaying not in (0, me)
            ):
                return False
            outermost = state.replaying == 0
            state.replaying = me
            state.redispatch_count += 1
            self.total_redispatch_attempts += 1
        try:
            return self._redispatch_locked_out(
                service_request_id, state, request, exclude
            )
        finally:
            if outermost:
                state.replaying = 0

    def _redispatch_locked_out(
        self, service_request_id, state, request, exclude
    ) -> bool:
        routing = self._route_excluding(request.token_ids, exclude)
        if routing is None:
            return False
        # Unwind the failed attempt's queued-prefill bookkeeping (a no-op
        # when the instance already left the registry) before charging the
        # new target.
        self._instance_mgr.update_request_metrics(
            request.routing, RequestAction.CANCEL, len(request.token_ids)
        )
        request.routing = routing
        self._bump_attempt(state)
        self._instance_mgr.update_request_metrics(
            routing, RequestAction.SCHEDULE, len(request.token_ids)
        )
        logger.info(
            "re-dispatching %s (excluding %s) -> %s",
            service_request_id, exclude or "-", routing.to_json(),
        )
        try:
            state.dispatch()
        except Exception:
            # The SCHEDULE above must not leak when the forward itself
            # failed — load accounting would drift on every failed replay
            # (mirror of the "prefill instance vanished" unwind in
            # api/master.py). Clearing the routing keeps the later
            # finish_request/fail_request from unwinding a second time.
            self._instance_mgr.update_request_metrics(
                routing, RequestAction.CANCEL, len(request.token_ids)
            )
            request.routing = Routing()
            return False
        # Count only SUCCESSFUL replays (the /metrics counter claims
        # "transparently replayed", not "attempted"); under self._mu —
        # the removal watch and the prune loop race here.
        with self._mu:
            self.total_redispatches += 1
        self._tracer.stage(
            service_request_id, "redispatch",
            excluded=exclude, prefill=routing.prefill_name,
        )
        return True

    def resume_request(
        self, service_request_id: str, exclude: str = ""
    ) -> bool:
        """Mid-stream token-replay resume (docs/FAULT_TOLERANCE.md): the
        request's instance died AFTER tokens reached the client. The
        forwarded request is rebuilt as prompt + every delivered token
        (state.acc), re-dispatched to a survivor with a `resume_from`
        marker, and the continuation splices onto the client stream with
        no duplicated or missing tokens (the attempt-versioned wire id
        fences off the dead attempt's late pushes). Eligibility:
        n=1/best_of=1, non-guided, no media (request.resumable); bounded
        by max_redispatch together with pre-token redispatches."""
        me = threading.get_ident()
        with self._mu:
            state = self._requests.get(service_request_id)
            if state is None or state.done:
                return False
            request = state.request
            if (
                request.num_generated_tokens <= 0
                or not request.resumable
                or state.dispatch is None
                or state.redispatch_count >= self.max_redispatch
                # see redispatch_request: one replay at a time
                or state.replaying not in (0, me)
            ):
                return False
            outermost = state.replaying == 0
            state.replaying = me
            state.redispatch_count += 1
            self.total_redispatch_attempts += 1
        try:
            return self._resume_locked_out(
                service_request_id, state, request, exclude
            )
        finally:
            if outermost:
                state.replaying = 0

    def _resume_locked_out(
        self, service_request_id, state, request, exclude
    ) -> bool:
        # Fence the dead attempt FIRST, then drain the strand: deliveries
        # already admitted finish writing into acc, later ones are
        # rejected — the snapshot below is exactly what the client has.
        self._bump_attempt(state)
        self._drain_strand(state)
        with self._mu:
            seq = state.acc.get(0)
            emitted = list(seq.token_ids) if seq is not None else []
        resume_ids = list(request.token_ids) + emitted
        routing = self._route_excluding(resume_ids, exclude)
        if routing is None:
            return False
        # Resumed requests serve colocated on the instance (no second PD
        # handoff on a recovery path) — keep the load accounting aligned
        # with where the work actually runs.
        routing.decode_name = routing.prefill_name
        # Close out the dead attempt's load accounting: its decode slot
        # (or queued prefill, if the kill beat the first FINISH_PREFILL
        # bookkeeping) — no-ops when the instance already left the
        # registry. The unwind mirrors that attempt's own SCHEDULE charge
        # (a second resume's predecessor was charged prompt + replay).
        self._instance_mgr.update_request_metrics(
            request.routing,
            RequestAction.FINISH_DECODE
            if state.prefill_finished
            else RequestAction.CANCEL,
            len(request.resume_token_ids or request.token_ids),
        )
        state.prefill_finished = False
        request.routing = routing
        request.resume_token_ids = resume_ids
        request.resume_base = len(emitted)
        # Stop bookkeeping restarts per attempt: drops already applied to
        # acc are excluded from the replay, so carrying the old counter
        # would double-subtract from the resumed attempt's usage.
        state.stop_dropped = 0
        state.resume_mono = time.monotonic()
        self._instance_mgr.update_request_metrics(
            routing, RequestAction.SCHEDULE, len(resume_ids)
        )
        logger.info(
            "resuming %s mid-stream at token %d (excluding %s) -> %s",
            service_request_id, len(emitted), exclude or "-",
            routing.to_json(),
        )
        try:
            state.dispatch()
        except Exception:
            # Same unwind rule as redispatch: a failed forward must not
            # leave the SCHEDULE charge on the new target, and the cleared
            # routing keeps the terminal bookkeeping from re-unwinding it.
            self._instance_mgr.update_request_metrics(
                routing, RequestAction.CANCEL, len(resume_ids)
            )
            request.routing = Routing()
            return False
        with self._mu:
            self.total_resumes += 1
        self._tracer.stage(
            service_request_id, "resume",
            excluded=exclude, prefill=routing.prefill_name,
            replayed_tokens=len(emitted),
        )
        return True

    # ------------------------------------------------------------------ #
    # instance-facing plane
    # ------------------------------------------------------------------ #

    def handle_instance_heartbeat(
        self,
        name: str,
        load_metrics: Optional[LoadMetrics] = None,
        latency_metrics: Optional[LatencyMetrics] = None,
        cache_event: Optional[KvCacheEvent] = None,
    ) -> None:
        """(reference: scheduler.cpp:123-130)"""
        if cache_event is not None and not cache_event.empty():
            # Breaker gate: an EJECTED instance's beats may still arrive
            # (asymmetric partition), but its cache deltas must not
            # re-index blocks nobody can fetch — evict_decisions would
            # count them as live replicas and let the real last copy die.
            # Its locations were pruned at ejection; the armed cache
            # resync rebuilds them (all tiers) once the breaker re-admits
            # it, so dropping deltas here loses nothing.
            if (
                self._instance_mgr.health_state(name)
                != HealthState.EJECTED
            ):
                meta = self._instance_mgr.get_instance(name)
                if meta is not None and meta.current_type.name == "ENCODE":
                    # ENCODE-role deltas are embedding-LRU transitions
                    # keyed by media content hashes, not KV block hashes:
                    # they feed the fleet embedding index, never the KV
                    # index (a media hash colliding into prefix scoring
                    # would score phantom KV hits).
                    self.encoder_fabric.record_event(name, cache_event)
                else:
                    self._kvcache_mgr.record_updated_kvcaches(
                        name, cache_event
                    )
        if load_metrics is not None:
            self._instance_mgr.record_load_metrics_update(name, load_metrics)
        if latency_metrics is not None:
            self._instance_mgr.update_latency_metrics(name, latency_metrics)
