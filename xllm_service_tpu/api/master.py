"""Master process: OpenAI-compatible HTTP front end + instance-facing RPC.

Composes one Scheduler with two HTTP servers on separate ports (the
evserve event loop by default, config.http_backend="threaded" for the
stdlib thread-per-connection backend) — the same process shape as the
reference master (reference: master.cpp:26-34
wires Scheduler->RPC->HTTP; :60-102 HTTP server; :104-139 RPC server; two
server threads at :38-58). The client plane parses OpenAI JSON, schedules,
injects service fields, and forwards to the prefill instance
(http_service/service.cpp:286-424, :147-191); the instance plane carries
registration, heartbeats, and the decode->service token stream
(rpc_service/service.cpp:107-206).

Divergences by design: registration is a real RPC that writes a leased
store key (the reference declares RegisterInstance but never overrides it —
instances write etcd directly; both paths work here), and /metrics serves
aggregated cluster metrics instead of a bare passthrough
(service.cpp:452-457), with ?instance= for the passthrough behavior.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from xllm_service_tpu.api.http_utils import (
    HttpJsonApi,
    RetryBudget,
    SseWriter,
    get_json,
    get_raw,
    make_http_server,
    post_json,
    post_json_retrying,
)
from xllm_service_tpu.api.protocol import (
    augment_forwarded_request,
    output_from_json,
    parse_prompt_field,
)
from xllm_service_tpu.cluster.instance_mgr import (
    HEALTH_STATE_VALUES,
    instance_key,
)
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.config import ServiceConfig
from xllm_service_tpu.common.types import (
    InstanceMetaInfo,
    KvCacheEvent,
    LatencyMetrics,
    LoadMetrics,
    RequestAction,
    StatusCode,
    TraceContext,
)
from xllm_service_tpu.coordination.store import CoordinationStore
from xllm_service_tpu.obs import (
    ClockSync,
    MetricsRegistry,
    absorb_exposition,
    assemble_trace,
    blame_stages,
    render_families,
    trace_to_chrome,
)
from xllm_service_tpu.service import (
    ClientStream,
    Scheduler,
    ServiceRequest,
    make_service_request_id,
)
from xllm_service_tpu.service.scheduler import NotMasterError
from xllm_service_tpu.tokenizer import parse_messages

logger = logging.getLogger(__name__)

_HTTP_STATUS = {
    StatusCode.OK: 200,
    StatusCode.INVALID_ARGUMENT: 400,
    StatusCode.DEADLINE_EXCEEDED: 504,
    StatusCode.RESOURCE_EXHAUSTED: 429,
    StatusCode.UNAVAILABLE: 503,
    StatusCode.CANCELLED: 499,
}


class HttpClientStream(ClientStream):
    """Bridges the scheduler's deliveries to one live HTTP exchange; the
    handler thread blocks on `done` (threaded backend) or parks the
    exchange (event backend) while the delivering threads write
    (reference: StreamCallData + the early done->Run SSE trick,
    call_data.h:83-92)."""

    def __init__(
        self, handler: HttpJsonApi, streaming: bool, x_request_id: str = ""
    ):
        self._handler = handler
        self._streaming = streaming
        # The backend's answer (a socket write on the threaded one, an
        # outbox append on the event one): the scheduler keeps a write
        # that can block off the thread that delivers a batch.
        self.writes_can_block = getattr(handler, "writes_can_block", True)
        # Echoed on every response — success AND error (reference
        # CallData captures the same header pair; here it round-trips to
        # the client and lands in the request trace for correlation).
        self._extra_headers = (
            {"x-request-id": x_request_id} if x_request_id else None
        )
        self._sse: Optional[SseWriter] = None
        self.done = threading.Event()
        # Set when the handler thread gives up on the exchange (timeout):
        # any later write must be dropped, never land on the socket —
        # the connection may be serving another request by then.
        self._abandoned = threading.Event()

    def abandon(self) -> None:
        self._abandoned.set()
        self.done.set()

    def _ensure_sse(self) -> SseWriter:
        if self._sse is None:
            self._sse = SseWriter(self._handler, self._extra_headers)
        return self._sse

    def write(self, payload: Dict[str, Any]) -> bool:
        if self._abandoned.is_set():
            return False
        if not self._streaming:
            return True  # non-stream accumulates in the scheduler
        return self._ensure_sse().send(payload)

    def write_done(self) -> bool:
        ok = True
        if self._streaming and not self._abandoned.is_set():
            ok = self._ensure_sse().send_done()
        self.done.set()
        return ok

    def finish(self, payload: Dict[str, Any]) -> bool:
        if self._abandoned.is_set():
            return False
        try:
            self._handler.send_json(
                payload, extra_headers=self._extra_headers
            )
            return True
        except (BrokenPipeError, ConnectionResetError, OSError):
            return False
        finally:
            self.done.set()

    def finish_with_error(self, code: StatusCode, message: str) -> bool:
        if self._abandoned.is_set():
            return False
        try:
            if self._streaming and self._sse is not None:
                ok = self._sse.send(
                    {"error": {"message": message, "code": int(code)}}
                )
                self._sse.close()
                return ok
            self._handler.send_error_json(
                _HTTP_STATUS.get(code, 500), message, "service_error",
                extra_headers=self._extra_headers,
            )
            return True
        except (BrokenPipeError, ConnectionResetError, OSError):
            return False
        finally:
            self.done.set()


class Master:
    def __init__(
        self,
        config: ServiceConfig,
        store: Optional[CoordinationStore] = None,
        tokenizer=None,
    ):
        self.config = config
        # instance name -> lease id held on its registration key
        self._leases: Dict[str, int] = {}
        self._leases_mu = threading.Lock()
        self._request_timeout_s = 600.0
        self._killed = False

        # Both control-plane servers ride the configured backend ("event"
        # = evserve selectors loop, "threaded" = stdlib thread-per-conn).
        # They bind BEFORE the scheduler exists so the election identity
        # is this replica's REAL client-plane address (ephemeral :0 ports
        # resolve at bind) — the master key in the store then doubles as
        # the redirect target a standby's front door hands to clients.
        # Handlers only dereference self.scheduler at request time, after
        # start().
        server_opts = dict(
            workers=config.http_workers,
            max_connections=config.http_max_connections,
            idle_timeout_s=config.http_idle_timeout_s,
            max_stream_buffer=config.sse_max_buffered_kb * 1024,
            drain_timeout_s=config.http_drain_timeout_s,
            max_body_bytes=config.http_max_body_mb * 1024 * 1024,
        )
        self.http = make_http_server(
            config.http_backend, config.host, config.http_port,
            do_get=self.handle_client_get, do_post=self.handle_client_post,
            name="master-http", **server_opts,
        )
        self.rpc = make_http_server(
            config.http_backend, config.host, config.rpc_port,
            do_get=self.handle_rpc_get, do_post=self.handle_rpc_post,
            name="master-rpc", **server_opts,
        )
        self.scheduler = Scheduler(
            config, store=store, tokenizer=tokenizer,
            identity=f"{self.http.host}:{self.http.port}",
        )
        self._store = self.scheduler._store
        self.scheduler.advertised_rpc = self.rpc_address

        # Cluster-level registry: fleet shape + fault accounting the
        # aggregated /metrics adds on top of the scheduler's own series.
        mgr = self.scheduler.instance_mgr
        self.cluster_metrics = MetricsRegistry()
        inst_gauge = self.cluster_metrics.gauge(
            "xllm_cluster_instances",
            "Registered instances by current serving role",
            labelnames=("role",),
        )
        for i, role in enumerate(("prefill", "decode", "encode")):
            inst_gauge.labels(role=role).set_function(
                lambda i=i: mgr.counts()[i]
            )
        self.cluster_metrics.counter(
            "xllm_cluster_pd_flips_total",
            "Dynamic PREFILL<->DECODE role flips applied by the master",
        ).set_function(lambda: mgr.total_flips)
        # Reshaping observability (ISSUE 16 satellite): the same flip
        # counter under the service namespace plus a census gauge that —
        # unlike xllm_cluster_instances — includes the MIX serving role.
        self.cluster_metrics.counter(
            "xllm_service_role_flips_total",
            "Role flips applied by the master (all transitions, "
            "including MIX)",
        ).set_function(lambda: mgr.total_flips)
        census_gauge = self.cluster_metrics.gauge(
            "xllm_service_role_census",
            "Instances by current serving role, including MIX",
            labelnames=("role",),
        )
        for role in ("prefill", "decode", "encode", "mix"):
            census_gauge.labels(role=role).set_function(
                lambda r=role: float(mgr.role_census()[r])
            )
        self.cluster_metrics.counter(
            "xllm_cluster_breaker_ejections_total",
            "Instances ejected by the health circuit breaker",
        ).set_function(lambda: mgr.total_ejections)
        self.cluster_metrics.counter(
            "xllm_cluster_breaker_probe_recoveries_total",
            "Ejected instances re-admitted to probation by a /health probe",
        ).set_function(lambda: mgr.total_probe_recoveries)
        # Global retry budget over control-plane POSTs (dispatch/cancel/
        # encoder push): bounds fleet-wide retry amplification so one
        # flapping instance can't start a retry storm.
        self._retry_budget = RetryBudget(
            ratio=getattr(config, "retry_budget_ratio", 0.2),
            min_tokens=getattr(config, "retry_budget_min", 10.0),
        )
        self._retry_attempts = getattr(config, "dispatch_retry_attempts", 3)
        self.cluster_metrics.counter(
            "xllm_service_retry_budget_exhausted_total",
            "Control-plane retries refused by the exhausted retry budget",
        ).set_function(lambda: self._retry_budget.exhausted_total)

        def health_probe(meta) -> bool:
            # Breaker probe, deliberately POST-shaped: it exercises the
            # SAME plane dispatch failures implicated (post_json), so a
            # partition that kills dispatch also fails the probe instead
            # of falsely healing the instance. Identity is cross-checked —
            # a recycled port must not heal a dead instance's breaker.
            # The probe carries the fencing epoch like every other
            # master->instance RPC: a deposed master's probe gets a 412
            # and must not keep healing breakers it no longer owns.
            body: Dict[str, Any] = {}
            ep = self.scheduler.master_epoch
            if ep:
                body["master_epoch"] = ep
            code, resp = post_json(
                meta.http_address, "/health", body, timeout=2.0
            )
            return (
                code == 200
                and isinstance(resp, dict)
                and bool(resp.get("ok"))
                and resp.get("name") == meta.name
            )

        mgr.health_prober = health_probe

        def reconcile_transport(meta, body: Dict[str, Any]) -> Dict[str, Any]:
            # Takeover reconciliation RPC (docs/FAULT_TOLERANCE.md): the
            # scheduler builds the claim set; this adds the rpc-plane
            # address instances should re-point heartbeats/pushes to, and
            # carries it over the wire. Idempotent — a retried reconcile
            # returns the same manifest.
            body = dict(body, master_rpc=self.rpc_address)
            code, resp = post_json_retrying(
                meta.http_address, "/reconcile", body, timeout=5.0,
                attempts=2, budget=self._retry_budget, idempotent=True,
            )
            if code != 200:
                raise RuntimeError(f"reconcile HTTP {code}: {resp}")
            return resp

        self.scheduler.on_reconcile = reconcile_transport
        self._m_scrape_failures = self.cluster_metrics.counter(
            "xllm_cluster_scrape_failures_total",
            "Instance /metrics scrapes that failed during aggregation",
        )
        # Scrape COST, not just failures: one slow engine inflating the
        # fleet /metrics path shows up here before it times out.
        self._m_scrape_ms = self.cluster_metrics.histogram(
            "xllm_cluster_scrape_ms",
            "Per-instance /metrics scrape latency during aggregation",
            labelnames=("instance",),
        )
        self._m_scrape_conflicts = self.cluster_metrics.counter(
            "xllm_cluster_scrape_type_conflicts_total",
            "Instance metric families skipped during aggregation because "
            "their # TYPE disagreed with the first-seen kind",
        )
        # Per-instance monotonic-clock offset estimators, fed by the
        # heartbeat piggyback samples (docs/OBSERVABILITY.md, Distributed
        # tracing): GET /trace shifts instance spans into the master
        # clock domain with these.
        self._clocks: Dict[str, ClockSync] = {}
        self._clocks_mu = threading.Lock()
        # Long-lived scrape pool: its threads keep get_raw's thread-local
        # keep-alive connections warm across scrape intervals (a per-call
        # pool would pay thread start-up + a fresh TCP connect to every
        # instance on every scrape).
        from concurrent.futures import ThreadPoolExecutor

        self._scrape_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="metrics-scrape"
        )

        def notify_flip(name: str, attempt: int) -> None:
            # Role resolved at SEND time from the registry (not frozen at
            # event time): a delayed delivery racing a flip-back would
            # otherwise park the engine on a stale role.
            meta = self.scheduler.instance_mgr.get_instance(name)
            if meta is None:
                return  # deregistered since the flip: nothing to notify
            role = meta.current_type.name
            err = ""
            flip_body: Dict[str, Any] = {"role": role}
            if self.scheduler.master_epoch:
                flip_body["master_epoch"] = self.scheduler.master_epoch
            try:
                code, resp = post_json(
                    meta.http_address, "/flip", flip_body, timeout=5.0
                )
                if code != 200:
                    err = f"HTTP {code}: {resp}"
            except Exception as e:  # instance may be mid-restart
                err = str(e)
            if err:
                logger.warning(
                    "flip notify %s -> %s failed (attempt %d): %s",
                    name, role, attempt, err,
                )
                # Bounded retry on the next master-loop tick; a dead
                # instance leaves the registry and stops the retries
                # naturally, the bound stops a live-but-broken one.
                if attempt < 5:
                    self.scheduler.instance_mgr.requeue_flip(name, attempt + 1)

        self.scheduler.on_role_flip = notify_flip

    # ------------------------------------------------------------------ #
    def start(self) -> None:
        self.http.start()
        self.rpc.start()
        # The initial election may have completed inside the scheduler's
        # constructor, before advertised_rpc was installed — publish now.
        self.scheduler.advertise_master_rpc()
        logger.info(
            "master serving http=:%d rpc=:%d", self.http.port, self.rpc.port
        )

    def stop(self) -> None:
        if not self._killed:
            self.http.stop()
            self.rpc.stop()
        self.scheduler.stop(drain_timeout_s=0.0 if self._killed else 10.0)
        self._scrape_pool.shutdown(wait=False)

    def kill(self) -> None:
        """UNGRACEFUL master death for chaos tests/benches: both HTTP
        planes drop (in-flight exchanges included), the election
        keepalive stops WITHOUT revoking the lease — the master key
        lingers until TTL expiry, exactly like a crashed master process —
        and the scheduler's loops halt. Standbys take over only once the
        store's liveness mechanism fires; a later stop() still runs the
        remaining teardown."""
        self._killed = True
        self.scheduler._stop.set()
        self.scheduler._dispatch_gate.clear()
        self.scheduler._election.kill()
        for srv in (self.http, self.rpc):
            try:
                # ZERO drain: a crash does not finish in-flight streams.
                srv.stop(drain_s=0.0)
            except TypeError:  # threaded backend has no drain knob
                srv.stop()
        self._scrape_pool.shutdown(wait=False)

    @property
    def http_address(self) -> str:
        return f"{self.http.host}:{self.http.port}"

    @property
    def rpc_address(self) -> str:
        return f"{self.rpc.host}:{self.rpc.port}"

    # ------------------------------------------------------------------ #
    # client plane
    # ------------------------------------------------------------------ #

    def handle_client_get(self, h: HttpJsonApi) -> None:
        route = h.route
        if route == "/hello":
            h.send_json({"message": "hello from xllm-service-tpu master"})
        elif route == "/v1/models":
            names = set()
            for m in self.scheduler.instance_mgr.list_instances():
                if m.model_name:
                    names.add(m.model_name)
                names.update(m.lora_adapters)
            models = sorted(names)
            h.send_json(
                {
                    "object": "list",
                    "data": [
                        {"id": m, "object": "model", "owned_by": "xllm-service-tpu"}
                        for m in models
                    ],
                }
            )
        elif route == "/metrics":
            self._handle_metrics(h)
        elif route.startswith("/trace/"):
            self._handle_trace(h, route[len("/trace/"):])
        else:
            h.send_error_json(404, f"no route {route}")

    def _handle_trace(self, h: HttpJsonApi, srid: str) -> None:
        """Distributed-trace collector (docs/OBSERVABILITY.md): pull every
        participant's ring spans for one service_request_id, shift them
        into the master clock domain with the heartbeat-derived offsets,
        and return ONE assembled timeline + per-stage blame + a Perfetto
        trace_event export with one track per process."""
        if not srid:
            h.send_error_json(400, "service_request_id required")
            return
        sched = self.scheduler
        master_spans = sched.span_ring.for_request(srid)
        names = sched.trace_participants(srid)
        if not names:
            # Unknown to the participant index (evicted or pre-dispatch):
            # fall back to asking the whole (small) fleet.
            names = [
                m.name for m in sched.instance_mgr.list_instances()
            ]
        participants = []
        offsets: Dict[str, Any] = {}
        for name in names:
            meta = sched.instance_mgr.get_instance(name)
            if meta is None:
                continue
            try:
                code, resp = get_json(
                    meta.http_address, f"/trace?srid={srid}", timeout=5.0
                )
            except Exception:
                continue
            if code != 200 or not isinstance(resp, dict):
                continue
            spans = resp.get("spans") or []
            off = self.clock_offset_ms(name)
            offsets[name] = round(off, 3)
            if spans:
                participants.append((name, spans, off))
        if not master_spans and not participants:
            h.send_error_json(404, f"no spans recorded for {srid}")
            return
        merged = assemble_trace("master", master_spans, participants)
        h.send_json(
            {
                "service_request_id": srid,
                "processes": ["master"] + [p[0] for p in participants],
                "offsets_ms": offsets,
                "blame_ms": blame_stages(merged),
                "spans": merged,
                "chrome": trace_to_chrome(merged),
            }
        )

    def _handle_metrics(self, h: HttpJsonApi) -> None:
        inst = h.query().get("instance")
        if inst:
            # Passthrough to one instance (reference behavior,
            # service.cpp:452-457): forward body + content type verbatim so
            # the Prometheus exposition format survives.
            meta = self.scheduler.instance_mgr.get_instance(inst)
            if meta is None:
                h.send_error_json(404, f"unknown instance {inst}")
                return
            try:
                status, body, ctype = get_raw(meta.http_address, "/metrics")
                h.send_response(status)
                h.send_header("Content-Type", ctype)
                h.send_header("Content-Length", str(len(body)))
                h.end_headers()
                h.wfile.write(body)
            except Exception as e:
                h.send_error_json(502, f"instance unreachable: {e}")
            return
        body = self._aggregate_metrics().encode()
        h.send_response(200)
        h.send_header("Content-Type", "text/plain; version=0.0.4")
        h.send_header("Content-Length", str(len(body)))
        h.end_headers()
        h.wfile.write(body)

    def _aggregate_metrics(self) -> str:
        """Cluster-wide exposition: master-local registries (scheduler +
        cluster), per-plane HTTP front-end stats, per-instance load
        gauges, and every registered instance's own /metrics scraped and
        re-labelled under instance="...". One TYPE line per family with
        every origin's samples grouped beneath it — the Prometheus text
        parser rejects duplicate TYPE lines / ungrouped series, which
        would fail the whole scrape."""
        mgr = self.scheduler.instance_mgr
        fams: "OrderedDict[str, Any]" = OrderedDict()
        # Local registries go straight in as families — no render->parse
        # round trip for data already in memory in the target shape.
        # (cluster_metrics is snapshotted AFTER the scrape loop below so
        # the scrape-latency histogram includes THIS exposure's scrapes.)
        fams.update(self.scheduler.metrics.families())
        # Front-end planes: both backends report stats() now (the event
        # loop's full set; the threaded backend's request/accept
        # counters) — emit whichever keys each plane has.
        plane_stats = [
            (plane, srv.stats())
            for plane, srv in (("http", self.http), ("rpc", self.rpc))
        ]
        for key, kind, metric in (
            ("open_connections", "gauge", "xllm_http_open_connections"),
            ("active_streams", "gauge", "xllm_http_active_streams"),
            ("buffered_bytes", "gauge", "xllm_http_buffered_bytes"),
            ("accepted_total", "counter", "xllm_http_accepted_total"),
            ("requests_total", "counter", "xllm_http_requests_total"),
            # stats() keys predate the naming convention; the exported
            # counter names carry the mandatory _total suffix.
            ("slow_client_closes", "counter",
             "xllm_http_slow_client_closes_total"),
            ("rejected_connections", "counter",
             "xllm_http_rejected_connections_total"),
        ):
            samples = [
                (f'{{plane="{plane}"}}', str(st[key]))
                for plane, st in plane_stats
                if key in st
            ]
            if samples:
                fams[metric] = (kind, "", samples)
        # Event-loop registries (loop-lag histogram), one per plane.
        for plane, srv in (("http", self.http), ("rpc", self.rpc)):
            reg = getattr(srv, "metrics", None)
            if reg is not None:
                absorb_exposition(
                    fams, reg.render(), extra_labels={"plane": plane}
                )
        load = mgr.get_load_metrics()
        fams["xllm_instance_waiting_requests"] = ("gauge", "", [
            (f'{{instance="{name}"}}', str(m.waiting_requests_num))
            for name, m in sorted(load.items())
        ])
        fams["xllm_instance_kv_cache_usage"] = ("gauge", "", [
            (f'{{instance="{name}"}}', f"{m.gpu_cache_usage_perc:.4f}")
            for name, m in sorted(load.items())
        ])
        fams["xllm_instance_health_state"] = ("gauge", "", [
            (
                f'{{instance="{name}",state="{state}"}}',
                str(HEALTH_STATE_VALUES.get(state, 0)),
            )
            for name, state in sorted(mgr.health_states().items())
        ])
        # Scrape each instance's registry-rendered /metrics and merge its
        # engine series under an instance label. Scrapes run CONCURRENTLY
        # (a dead instance costs one 2 s timeout, not a serial stall that
        # blows the scraper's own deadline on a large fleet); failures
        # skip the instance (counted) — one dead engine must not fail the
        # fleet scrape. The merge itself stays on this thread, in name
        # order, so the exposition is deterministic.
        instances = sorted(mgr.list_instances(), key=lambda m: m.name)

        def scrape(meta):
            # Timed INSIDE the pool thread so the histogram measures the
            # instance's own /metrics latency, not queueing behind other
            # scrapes in the pool.
            t0 = time.monotonic()
            try:
                status, raw, _ = get_raw(
                    meta.http_address, "/metrics", timeout=2.0
                )
            finally:
                self._m_scrape_ms.labels(instance=meta.name).observe(
                    (time.monotonic() - t0) * 1000.0
                )
            if status != 200:
                raise RuntimeError(f"HTTP {status}")
            return raw.decode("utf-8", "replace")

        futures = [self._scrape_pool.submit(scrape, m) for m in instances]
        for meta, fut in zip(instances, futures):
            try:
                conflicts = absorb_exposition(
                    fams, fut.result(timeout=10.0),
                    extra_labels={"instance": meta.name},
                )
                if conflicts:
                    # Deterministic skip (first-seen kind wins); count the
                    # dropped families instead of losing them silently.
                    self._m_scrape_conflicts.inc(len(conflicts))
                    logger.warning(
                        "metrics aggregation skipped %d kind-conflicting "
                        "families from %s: %s",
                        len(conflicts), meta.name, ", ".join(conflicts),
                    )
            except Exception:
                self._m_scrape_failures.inc()
        # Cluster-level registry last: scrape_ms observations from the
        # loop above are already in it, so the histogram is never a
        # TYPE-only family on the first exposure. absorb via the families
        # dict, not update(): an instance-absorbed family of the same
        # name must not be clobbered.
        for name, fam in self.cluster_metrics.families().items():
            if name in fams:
                kind, _help, samples = fams[name]
                if kind == fam[0]:
                    fams[name] = (kind, fam[1] or _help, fam[2] + samples)
            else:
                fams[name] = fam
        return render_families(fams)

    def _redirect_if_standby(
        self, h: HttpJsonApi, xh: Optional[Dict[str, str]] = None
    ) -> bool:
        """Fenced front door (docs/FAULT_TOLERANCE.md): a replica that
        does not hold the master lease never accepts generation work — it
        307-redirects to the current master (Location + a JSON body
        naming it) or 503s when no master exists yet. A RECONCILING
        master still holds the lease and accepts (the dispatch gate parks
        the work until the takeover scan completes). Returns True when
        the exchange was handled here."""
        sched = self.scheduler
        if sched.is_master:
            return False
        cur = sched.current_master_identity()
        if cur and cur != sched.election_identity:
            h.send_json(
                {
                    "error": {
                        "message": (
                            "this replica is not the master; retry "
                            f"against {cur}"
                        ),
                        "type": "not_master",
                    },
                    "master": cur,
                },
                status=307,
                extra_headers={
                    **(xh or {}), "Location": f"http://{cur}{h.path}",
                },
            )
        else:
            h.send_error_json(
                503, "no master elected yet; retry shortly",
                etype="not_master", extra_headers=xh,
            )
        return True

    def handle_client_post(self, h: HttpJsonApi) -> None:
        route = h.route
        if route == "/v1/completions":
            self._serve_generation(h, chat=False)
        elif route == "/v1/chat/completions":
            self._serve_generation(h, chat=True)
        elif route == "/v1/embeddings":
            # The reference rejects embeddings outright (service.cpp:441-442);
            # serving them here EXCEEDS parity: the service tokenizes (same
            # injection contract as generation), an instance pools hidden
            # states.
            self._serve_embeddings(h)
        else:
            h.send_error_json(404, f"no route {route}")

    def _serve_embeddings(self, h: HttpJsonApi) -> None:
        if self._redirect_if_standby(h):
            return
        body = h.read_json()
        if body is None:
            h.send_error_json(400, "invalid JSON body")
            return
        raw = body.get("input")
        if isinstance(raw, str):
            raw = [raw]
        if isinstance(raw, list) and raw and all(
            isinstance(x, int) for x in raw
        ):
            raw = [raw]  # single pre-tokenized input
        if not isinstance(raw, list) or not raw:
            h.send_error_json(400, "input (string or array) is required")
            return
        token_lists: List[List[int]] = []
        for x in raw:
            if isinstance(x, str):
                ids = self.scheduler.tokenizer.encode(x)
            elif isinstance(x, list) and all(isinstance(i, int) for i in x):
                ids = list(x)
            else:
                h.send_error_json(400, "input items must be strings or id lists")
                return
            if not ids:
                h.send_error_json(400, "input item tokenized to nothing")
                return
            token_lists.append(ids)
        # Route like a prefill: the policy's pair choice keeps load skew
        # visible to it; embeddings are synchronous one-shot calls.
        routing = self.scheduler.route_only(token_lists[0])
        if routing is None:
            h.send_error_json(503, "no instances registered")
            return
        meta = self.scheduler.instance_mgr.get_instance(routing.prefill_name)
        if meta is None:
            h.send_error_json(503, "routed instance vanished")
            return
        try:
            code, resp = post_json(
                meta.http_address,
                "/v1/embeddings",
                {"model": body.get("model") or "", "token_ids": token_lists},
                timeout=120.0,
            )
        except Exception as e:
            h.send_error_json(502, f"instance unreachable: {e}")
            return
        if code != 200:
            h.send_error_json(502, f"instance rejected embeddings: {resp}")
            return
        h.send_json(resp)

    def _parse_request(
        self, body: Dict[str, Any], chat: bool
    ) -> ServiceRequest:
        req = ServiceRequest(
            service_request_id=make_service_request_id(
                "chatcmpl" if chat else "cmpl"
            ),
            model=body.get("model", ""),
            stream=bool(body.get("stream", False)),
            include_usage=bool(
                (body.get("stream_options") or {}).get("include_usage", False)
            ),
            echo=bool(body.get("echo", False)),
            offline=bool(body.get("offline", False)),
            n=int(body.get("n", 1)),
            max_tokens=int(
                body.get("max_tokens")
                or body.get("max_completion_tokens")
                or 0
            ),
            temperature=float(body.get("temperature", 1.0)),
            top_p=float(body.get("top_p", 1.0)),
            # Admission fair-share key: the OpenAI `user` field when the
            # client sends one, else the model name (service/admission.py).
            tenant=str(body.get("user") or body.get("model") or ""),
        )
        raw_stop = body.get("stop")
        if raw_stop is not None:
            if isinstance(raw_stop, str):
                raw_stop = [raw_stop]
            if not isinstance(raw_stop, list) or not all(
                isinstance(s, str) for s in raw_stop
            ):
                raise ValueError("stop must be a string or array of strings")
            if len(raw_stop) > 4:
                raise ValueError("stop supports at most 4 sequences")
            req.stop = [s for s in raw_stop if s]
        if chat:
            req.messages = parse_messages(body.get("messages", []))
            req.tools = body.get("tools")
            req.top_logprobs = int(body.get("top_logprobs", 0) or 0)
            if body.get("logprobs"):
                req.logprobs = max(1, req.top_logprobs)
        else:
            text, token_ids, err = parse_prompt_field(body.get("prompt", ""))
            if err:
                raise ValueError(err)
            req.prompt = text
            req.token_ids = token_ids
            lp = body.get("logprobs")
            req.logprobs = int(lp) if lp is not None else None
        return req

    def _serve_generation(self, h: HttpJsonApi, chat: bool) -> None:
        xrid = h.x_request_id()
        xh = {"x-request-id": xrid} if xrid else None
        if self._redirect_if_standby(h, xh):
            return
        body = h.read_json()
        if body is None:
            h.send_error_json(400, "invalid JSON body", extra_headers=xh)
            return
        if chat and not body.get("messages"):
            h.send_error_json(400, "messages is required", extra_headers=xh)
            return
        if not chat and not body.get("prompt"):
            h.send_error_json(400, "prompt is required", extra_headers=xh)
            return
        try:
            req = self._parse_request(body, chat)
        except (ValueError, TypeError) as e:
            h.send_error_json(400, str(e), extra_headers=xh)
            return
        status = self.scheduler.schedule(req)
        if not status.ok():
            eh = dict(xh) if xh else {}
            if status.code == StatusCode.RESOURCE_EXHAUSTED and req.retry_after_s:
                # Admission shed: tell well-behaved clients exactly when
                # to come back instead of letting them hammer the door.
                eh["Retry-After"] = str(int(req.retry_after_s))
            h.send_error_json(
                _HTTP_STATUS.get(status.code, 500), status.message,
                extra_headers=eh or None,
            )
            return

        if self.scheduler.instance_mgr.get_instance(req.routing.prefill_name) is None:
            # Unwind the SCHEDULE bookkeeping recorded by schedule() — the
            # request never dispatches. The admission slot goes back too.
            self.scheduler.admission.release(req)
            self.scheduler.instance_mgr.update_request_metrics(
                req.routing, RequestAction.CANCEL, len(req.token_ids)
            )
            h.send_error_json(
                503, "prefill instance vanished", extra_headers=xh
            )
            return
        if xrid and self.scheduler.tracer.enabled:
            self.scheduler.tracer.record(
                req.service_request_id, "x_request_id", xrid
            )
        # Mid-stream resume eligibility (docs/FAULT_TOLERANCE.md): token
        # replay reconstructs exactly one sequence, guided FSM state does
        # not survive a re-prefill of emitted tokens, and media embeddings
        # would need a fresh encode pass — all of those fall back to the
        # pre-token-only replay (then error-finish).
        req.resumable = (
            req.n <= 1
            and int(body.get("best_of") or 1) <= 1
            and not body.get("response_format")
            and not req.media_parts
        )
        stream = HttpClientStream(h, req.stream, x_request_id=xrid)

        path = "/v1/chat/completions" if chat else "/v1/completions"
        mgr = self.scheduler.instance_mgr

        def dispatch() -> None:
            # Forward to the CURRENT routed prefill instance (re-resolved
            # per call: re-dispatch after instance death changes routing;
            # reference: service.cpp:147-191, ack-mode — tokens return via
            # /rpc/generations). The wire id is attempt-versioned so a
            # replaced attempt's late pushes can't reach the client.
            meta = mgr.get_instance(req.routing.prefill_name)
            if meta is None:
                self.scheduler.fail_request(
                    req.service_request_id,
                    StatusCode.UNAVAILABLE,
                    "prefill instance vanished",
                )
                return
            wire = req.wire_srid or req.service_request_id
            epoch = self.scheduler.master_epoch
            # Distributed-tracing context: trace_id is the BASE service
            # id (stable across replay attempts), the parent span names
            # the attempt-versioned dispatch that spawned the downstream
            # work, origin_epoch fences stale traces.
            trace_ctx = TraceContext(
                trace_id=req.service_request_id,
                parent_span=f"dispatch:{wire}",
                origin_epoch=epoch,
            ).to_json()
            stream_mm = False
            if req.media_parts:
                from xllm_service_tpu.cluster.encoder_fabric import (
                    encoder_fabric_enabled,
                )

                # Encoder fabric (docs/EPD.md): dispatch the encoder
                # CONCURRENTLY with the text forward — the prefill peer
                # admits the text with an open stream handle and prefills
                # text chunks while the encoder's per-item session lands
                # embeddings (re-route retry across the encode tier on
                # failure).
                stream_mm = encoder_fabric_enabled(self.config)
            if req.media_parts and not stream_mm:
                # Legacy synchronous EPD (and the hatch-off path): the
                # encoder computes media embeddings and pushes them to
                # the prefill peer's /mm/import BEFORE the text request
                # arrives there. Re-pushing embeddings is idempotent, so
                # the retry wrapper may redeliver.
                enc = mgr.get_instance(req.routing.encode_name)
                if enc is None:
                    self.scheduler.fail_request(
                        req.service_request_id,
                        StatusCode.UNAVAILABLE,
                        "encode instance vanished",
                    )
                    return
                try:
                    code, resp = post_json_retrying(
                        enc.http_address,
                        "/encode",
                        {
                            "service_request_id": wire,
                            "parts": req.media_parts,
                            "positions": req.mm_positions,
                            "target": meta.http_address,
                            "master_epoch": epoch,
                            "trace": trace_ctx,
                        },
                        # Generous: the encoder's FIRST request pays its
                        # XLA compile inside this call.
                        timeout=180.0,
                        attempts=self._retry_attempts,
                        budget=self._retry_budget,
                        idempotent=True,
                    )
                except Exception as e:
                    code, resp = 0, str(e)
                if code != 200:
                    # Breaker signal only for transport failures and
                    # instance-side (5xx) errors: a client's bad media
                    # (4xx) must never eject a healthy encoder.
                    if code == 0 or code >= 500:
                        mgr.record_dispatch_failure(enc.name)
                    else:
                        mgr.record_dispatch_success(enc.name)
                    self.scheduler.fail_request(
                        req.service_request_id,
                        StatusCode.UNAVAILABLE,
                        f"encoder failed: {resp}",
                    )
                    return
                mgr.record_dispatch_success(enc.name)
            fwd = augment_forwarded_request(
                body, wire, req.resume_token_ids or req.token_ids,
                req.routing,
                decode_response_to_service=(
                    self.config.enable_decode_response_to_service
                ),
                master_epoch=epoch,
                # Skip the fetch hint when a replay re-routed onto the
                # holder itself (the instance also self-checks).
                kv_fabric=(
                    req.kv_fabric
                    if req.kv_fabric
                    and req.kv_fabric.get("holder")
                    != req.routing.prefill_name
                    else None
                ),
                trace=trace_ctx,
            )
            if req.resume_base:
                # Token-replay resume: the last resume_base token_ids are
                # replayed output, not prompt — the instance fences its
                # generation budget and (FakeEngine) its echo script on it.
                fwd["resume_from"] = req.resume_base
            if req.mm_positions:
                fwd["mm_positions"] = list(req.mm_positions)
                if req.mm_grids:
                    fwd["mm_grids"] = [list(g) for g in req.mm_grids]
            if stream_mm:
                # Encoder dispatch CONCURRENT with the text forward
                # (docs/EPD.md): stage E overlaps the forward round-trip,
                # prefill admission, and the text chunks. Concurrency —
                # not strict forward-first — also keeps a legacy prefill
                # (hatch off, blocking /mm/import wait inside its serve
                # handler) from deadlocking against this thread.
                threading.Thread(
                    target=self._encode_fabric_async,
                    args=(req, wire, meta, epoch),
                    name=f"encode-dispatch-{wire}",
                    daemon=True,
                ).start()
            try:
                # Dispatch is NOT idempotent: the wrapper only retries
                # failures proven send-time (request never written); an
                # indeterminate failure falls through to replay on another
                # instance under a fresh wire id.
                code, resp = post_json_retrying(
                    meta.http_address, path, fwd, timeout=30.0,
                    attempts=self._retry_attempts,
                    budget=self._retry_budget,
                )
                # Breaker signal: a 5xx is an instance-side failure (a
                # wedged engine behind a live HTTP plane must still trip
                # the breaker); a 4xx is the CLIENT's error and proves the
                # instance healthy.
                if code >= 500:
                    mgr.record_dispatch_failure(meta.name)
                else:
                    mgr.record_dispatch_success(meta.name)
                if code != 200:
                    # A 4xx from the instance is the CLIENT's error
                    # (e.g. invalid logit_bias) — relay it as such
                    # instead of masking it as a service failure.
                    msg = resp
                    fenced = isinstance(resp, dict) and resp.get("fenced")
                    if isinstance(resp, dict):
                        msg = (resp.get("error") or {}).get(
                            "message", resp
                        )
                    if fenced:
                        # 412 stale-epoch: the FLEET is telling this
                        # replica it was deposed — not a client error,
                        # not an instance failure. The client retries
                        # against the current master.
                        self.scheduler.fail_request(
                            req.service_request_id,
                            StatusCode.UNAVAILABLE,
                            "dispatch fenced (this master was deposed); "
                            "retry against "
                            + (
                                self.scheduler.current_master_identity()
                                or "the current master"
                            ),
                        )
                        return
                    self.scheduler.fail_request(
                        req.service_request_id,
                        StatusCode.INVALID_ARGUMENT
                        if 400 <= code < 500
                        else StatusCode.UNAVAILABLE,
                        f"prefill rejected: {msg}",
                    )
            except Exception as e:
                # Fast failure (connection refused / timeout): feed the
                # breaker, then try another instance before giving up —
                # lease expiry would take seconds to notice. Pre-token
                # requests replay whole; mid-stream ones resume by token
                # replay.
                mgr.record_dispatch_failure(meta.name)
                if not (
                    self.scheduler.redispatch_request(
                        req.service_request_id, exclude=meta.name
                    )
                    or self.scheduler.resume_request(
                        req.service_request_id, exclude=meta.name
                    )
                ):
                    self.scheduler.fail_request(
                        req.service_request_id,
                        StatusCode.UNAVAILABLE,
                        f"prefill unreachable: {e}",
                    )

        # The scheduler wraps dispatch with span/queue-delay
        # instrumentation; use its wrapper so re-dispatch and the first
        # forward are timed identically.
        dispatch = self.scheduler.record_new_request(
            req, stream,
            cancel_callback=lambda: self._cancel_on_instance(req),
            dispatch=dispatch,
        )

        if self.scheduler.should_defer_offline(req):
            self.scheduler.park_offline(req, dispatch)
        else:
            try:
                dispatch()
            except NotMasterError as e:
                # Demoted between the redirect check and the forward (or
                # the reconcile park timed out): error the exchange toward
                # the current master instead of leaving it to the deadline.
                self.scheduler.fail_request(
                    req.service_request_id, StatusCode.UNAVAILABLE, str(e)
                )

        # Hold the exchange open until the scheduler finishes it. The
        # threaded backend blocks this handler thread; the event backend
        # parks the exchange on the connection and returns, enforcing the
        # deadline with a loop timer — a stream holds a socket, not a
        # thread.
        def fail_deadline() -> None:
            self.scheduler.fail_request(
                req.service_request_id, StatusCode.DEADLINE_EXCEEDED, "timeout"
            )

        h.hold(stream, self._request_timeout_s, fail_deadline)

    def _encode_fabric_async(self, req, wire, prefill_meta, epoch) -> None:
        """Background encode dispatch for one media request (encoder
        fabric): runs concurrently with the text forward. When every
        encode candidate fails, the request error-finishes AND the
        prefill peer's parked work is cancelled so the stream-deadline
        reject never has to fire."""
        try:
            ok, emsg = self._dispatch_encode_fabric(
                req, wire, prefill_meta, epoch
            )
        except Exception as e:  # noqa: BLE001 — daemon thread must report
            ok, emsg = False, str(e)
        if ok:
            return
        try:
            post_json(
                prefill_meta.http_address, "/cancel",
                {"service_request_id": wire, "master_epoch": epoch},
                timeout=5.0,
            )
        except Exception:
            pass
        self.scheduler.fail_request(
            req.service_request_id,
            StatusCode.UNAVAILABLE,
            f"encoder failed: {emsg}",
        )

    def _dispatch_encode_fabric(self, req, wire, prefill_meta, epoch):
        """Encode-tier dispatch with re-route retry (encoder fabric,
        docs/EPD.md): try the scheduler-routed encoder first, then — on
        transport/5xx failure, which also feeds the breaker exactly like
        the LM tiers — re-resolve a DIFFERENT modality-covering encoder
        and try again, up to 3 candidates. Returns (ok, error_message).
        A 4xx is the client's bad media: no re-route, fail once."""
        mgr = self.scheduler.instance_mgr
        required = {
            {2: "audio", 4: "video"}.get(len(p["shape"]), "image")
            for p in req.media_parts
        }
        tried = set()
        enc_name = req.routing.encode_name
        last_err = "no ENCODE instance available"
        for _attempt in range(3):
            if not enc_name or enc_name in tried:
                enc_name = mgr.next_encode_instance(
                    required, exclude=tried
                )
            if not enc_name:
                break
            tried.add(enc_name)
            enc = mgr.get_instance(enc_name)
            if enc is None:
                enc_name = ""
                continue
            try:
                faults.point(
                    "encode.dispatch", instance=enc_name, srid=wire
                )
                code, resp = post_json_retrying(
                    enc.http_address,
                    "/encode",
                    {
                        "service_request_id": wire,
                        "parts": req.media_parts,
                        "positions": req.mm_positions,
                        "target": prefill_meta.http_address,
                        "master_epoch": epoch,
                        "trace": TraceContext(
                            trace_id=req.service_request_id,
                            parent_span=f"dispatch:{wire}",
                            origin_epoch=epoch,
                        ).to_json(),
                    },
                    # Generous: the encoder's FIRST request pays its XLA
                    # compile inside this call.
                    timeout=180.0,
                    attempts=self._retry_attempts,
                    budget=self._retry_budget,
                    idempotent=True,
                )
            except Exception as e:
                code, resp = 0, str(e)
            if code == 200:
                mgr.record_dispatch_success(enc_name)
                req.routing.encode_name = enc_name
                return True, ""
            last_err = str(resp)
            if code == 0 or code >= 500:
                # Instance-side failure: feed the breaker and re-route
                # to another encoder (third-role failover parity).
                mgr.record_dispatch_failure(enc_name)
                enc_name = ""
                continue
            # 4xx: the client's bad media — the encoder is healthy and a
            # re-route would just fail identically.
            mgr.record_dispatch_success(enc_name)
            return False, last_err
        return False, last_err

    def _cancel_on_instance(self, req: ServiceRequest) -> None:
        """Propagate a client cancel to the routed instance(s). /cancel is
        idempotent, so the retry wrapper may redeliver; failures feed the
        breaker and the xllm_service_cancel_errors_total counter instead
        of vanishing silently (a dead cancel path leaks engine work)."""
        for name in {req.routing.prefill_name, req.routing.decode_name}:
            meta = self.scheduler.instance_mgr.get_instance(name)
            if meta is None:
                continue
            try:
                post_json_retrying(
                    meta.http_address,
                    "/cancel",
                    {
                        "service_request_id": (
                            req.wire_srid or req.service_request_id
                        ),
                        "master_epoch": self.scheduler.master_epoch,
                    },
                    timeout=5.0,
                    attempts=self._retry_attempts,
                    budget=self._retry_budget,
                    idempotent=True,
                )
                self.scheduler.instance_mgr.record_dispatch_success(name)
            except Exception as e:
                self.scheduler.m_cancel_errors.inc()
                self.scheduler.instance_mgr.record_dispatch_failure(name)
                logger.debug(
                    "cancel of %s on %s failed: %s",
                    req.service_request_id, name, e,
                )

    # ------------------------------------------------------------------ #
    # instance plane
    # ------------------------------------------------------------------ #

    def handle_rpc_get(self, h: HttpJsonApi) -> None:
        route = h.route
        mgr = self.scheduler.instance_mgr
        if route == "/rpc/instance_info":
            name = h.query().get("name", "")
            meta = mgr.get_instance(name)
            if meta is None:
                h.send_error_json(404, f"unknown instance {name}")
            else:
                h.send_json(meta.to_json())
        elif route == "/rpc/static_prefill_list":
            h.send_json({"instances": mgr.prefill_instances()})
        elif route == "/rpc/static_decode_list":
            h.send_json({"instances": mgr.decode_instances()})
        else:
            h.send_error_json(404, f"no route {route}")

    def handle_rpc_post(self, h: HttpJsonApi) -> None:
        route = h.route
        body = h.read_json()
        if body is None:
            h.send_error_json(400, "invalid JSON body")
            return
        if route == "/rpc/hello":
            h.send_json({"ok": True, "name": body.get("name", "")})
        elif route == "/rpc/register":
            self._handle_register(h, body)
        elif route == "/rpc/heartbeat":
            self._handle_heartbeat(h, body)
        elif route == "/rpc/deregister":
            self._handle_deregister(h, body)
        elif route == "/rpc/generations":
            self._handle_generations(h, body)
        elif route == "/rpc/fabric/evict_offer":
            self._handle_evict_offer(h, body)
        else:
            h.send_error_json(404, f"no route {route}")

    def _handle_evict_offer(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        """Coordinated multi-tier eviction (docs/KV_CACHE.md): an instance
        about to drop blocks from its coldest tier asks where they should
        live. Per-hash verdicts come from the scheduler's PrefixFabric;
        a non-master replica refuses (its index view may be stale)."""
        if not self.scheduler.is_master:
            h.send_error_json(503, "not the master", etype="not_master")
            return
        try:
            hashes = [
                bytes.fromhex(x) for x in body.get("block_hashes") or []
            ]
        except ValueError:
            h.send_error_json(400, "malformed block hashes")
            return
        decisions = self.scheduler.prefix_fabric.evict_decisions(
            str(body.get("name") or ""), hashes
        )
        h.send_json({"ok": True, "decisions": decisions})

    def _handle_register(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        try:
            meta = InstanceMetaInfo.from_json(body.get("meta", body))
        except Exception as e:
            h.send_error_json(400, f"bad meta: {e}")
            return
        if not meta.name:
            h.send_error_json(400, "meta.name required")
            return
        ttl = max(
            3.0 * self.config.heartbeat_interval_s,
            self.config.instance_lease_min_ttl_s,
        )
        lease = self._store.grant_lease(ttl)
        self._store.set(instance_key(meta), meta.serialize(), lease_id=lease)
        with self._leases_mu:
            # A stale prior lease is left to expire on its own; revoking it
            # here would delete the key the new lease now owns.
            self._leases[meta.name] = lease
        h.send_json(
            {
                "ok": True,
                "lease_ttl_s": ttl,
                "heartbeat_interval_s": self.config.heartbeat_interval_s,
            }
        )

    def _handle_deregister(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        """Graceful shutdown: revoke the instance's registration lease NOW
        (DELETE event -> registry drop -> routing stops immediately),
        instead of leaving a dead endpoint routable until the TTL lapses.
        Ungraceful death keeps the lease-expiry path (sweeper)."""
        name = body.get("name", "")
        if not name:
            h.send_error_json(400, "name required")
            return
        with self._leases_mu:
            lease = self._leases.pop(name, None)
        if lease is not None:
            self._store.revoke_lease(lease)
        h.send_json({"ok": True, "removed": lease is not None})

    def _record_clock_sample(self, name: str, clk: Any) -> None:
        """One heartbeat's monotonic-offset bounds for `name` (clock
        alignment, docs/OBSERVABILITY.md): the request's send stamp gives
        an UPPER bound on (master_mono - instance_mono); the echoed reply
        stamp from the PREVIOUS response gives a LOWER bound."""
        if not isinstance(clk, dict):
            return
        now_ms = time.monotonic() * 1000.0
        with self._clocks_mu:
            sync = self._clocks.setdefault(name, ClockSync())
        try:
            if clk.get("send_mono_ms") is not None:
                sync.sample_upper(now_ms - float(clk["send_mono_ms"]))
            if (
                clk.get("echo_master_mono_ms") is not None
                and clk.get("echo_recv_mono_ms") is not None
            ):
                sync.sample_lower(
                    float(clk["echo_master_mono_ms"])
                    - float(clk["echo_recv_mono_ms"])
                )
        except (TypeError, ValueError):
            pass

    def clock_offset_ms(self, name: str) -> float:
        with self._clocks_mu:
            sync = self._clocks.get(name)
        return sync.offset_ms() if sync is not None else 0.0

    def _handle_heartbeat(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        name = body.get("name", "")
        if not self.scheduler.is_master:
            # Deposed (or never-elected) replica: do NOT keepalive the
            # instance's lease — this replica doesn't own the fleet — and
            # hand back the ACTIVE master's advertised rpc address so the
            # instance re-points even if a /reconcile never reached it.
            h.send_json(
                {
                    "ok": False,
                    "master_rpc": self.scheduler.current_master_rpc(),
                }
            )
            return
        with self._leases_mu:
            lease = self._leases.get(name)
        alive = lease is not None and self._store.keepalive(lease)
        if not alive or self.scheduler.instance_mgr.get_instance(name) is None:
            # Lease lost (or this replica never saw the registration):
            # tell the engine to re-register (the etcd-expiry analog).
            h.send_json({"ok": False, "reregister": True})
            return
        self._record_clock_sample(name, body.get("clock"))
        load = body.get("load_metrics")
        lat = body.get("latency_metrics")
        cache = body.get("cache_event")
        self.scheduler.handle_instance_heartbeat(
            name,
            load_metrics=LoadMetrics.from_json(load) if load else None,
            latency_metrics=LatencyMetrics.from_json(lat) if lat else None,
            cache_event=KvCacheEvent.from_json(cache) if cache else None,
        )
        # Role reconciliation (flip notifications are best-effort + bounded
        # retry; a restart or a dropped event would otherwise desync the
        # engine's serving role from the registry forever): on mismatch,
        # queue a fresh notification.
        reported = body.get("serving_role", "")
        meta = self.scheduler.instance_mgr.get_instance(name)
        if (
            reported
            and meta is not None
            and reported != meta.current_type.name
            # Only PD/MIX roles are flip-notifiable; an ENCODE instance
            # can never accept /flip, so a mismatch there must not loop.
            and meta.current_type.name in ("PREFILL", "DECODE", "MIX")
        ):
            self.scheduler.instance_mgr.requeue_flip(name, 1)
        resp: Dict[str, Any] = {"ok": True}
        if isinstance(body.get("clock"), dict):
            # Reply stamp: the instance echoes it (with its own receive
            # stamp) on the NEXT beat, closing the offset's lower bound.
            resp["clock"] = {
                "master_mono_ms": round(time.monotonic() * 1000.0, 3)
            }
        if self.scheduler.take_cache_resync(name):
            # Breaker ejection pruned this instance's KV-index locations;
            # deltas can't rebuild them — ask for the full committed-block
            # snapshot on the next beat (docs/KV_CACHE.md).
            resp["resync_cache"] = True
        h.send_json(resp)

    def _handle_generations(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        try:
            pushed_epoch = int(body.get("master_epoch") or 0)
        except (TypeError, ValueError):
            pushed_epoch = 0
        if not self.scheduler.is_master or (
            pushed_epoch and pushed_epoch > self.scheduler.master_epoch
        ):
            # A deposed master must not answer the token stream: its
            # `cont` map would authoritatively cancel work the CURRENT
            # master dispatched. That covers both the replica that KNOWS
            # it was demoted and the split-brain window where the fleet's
            # fence epoch (stamped on the push) has already moved past
            # this replica's term but its keepalive hasn't failed yet.
            # 503 makes the instance's push loop retry; by the next
            # attempt its heartbeat has re-pointed.
            h.send_error_json(
                503,
                "not the master; retry against "
                + (self.scheduler.current_master_rpc() or "current master"),
                etype="not_master",
            )
            return
        outs: List[RequestOutput] = []
        for j in body.get("gens", []):
            try:
                outs.append(output_from_json(j))
            except Exception:
                continue
        # The batch is delivered here, on this worker: the client plane's
        # loop is woken once for all the connections it wrote to.
        with self.http.deferred_wakes():
            cont = self.scheduler.handle_generations(outs)
        h.send_json({"cont": cont})


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO)
    config = ServiceConfig.from_args(argv)
    master = Master(config)
    master.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        master.stop()


if __name__ == "__main__":
    main()
