"""Engine instance server: the TPU engine behind the cluster protocol.

The reference's engine tier is the absent xLLM submodule; this is its
TPU-native replacement's front door (SURVEY.md §2.3 lists the service-side
touchpoints that constrain it): per-instance OpenAI HTTP endpoints (the
service forwards raw JSON to `instance/v1/...`, service.cpp:163-190),
registration + heartbeats with load/latency/cache events, and the
decode->service `Generations` push. Detokenization happens here — the
engine speaks token ids only.

Serves two modes on the same endpoints:
  * forwarded service traffic (body carries service_request_id+token_ids):
    ack immediately, stream tokens back via /rpc/generations;
  * direct client traffic: run locally, return/stream OpenAI JSON itself.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time

from typing import Any, Callable, Dict, List, Optional

from xllm_service_tpu.api.client import HeartbeatLoop, MasterClient
from xllm_service_tpu.api.http_utils import HttpJsonApi, make_http_server
from xllm_service_tpu.api.protocol import sampling_from_body  # noqa: F401 — re-export
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.common.types import (
    InstanceMetaInfo,
    InstanceType,
    RequestOutput,
)
from xllm_service_tpu.runtime import compile_cache
from xllm_service_tpu.obs import (
    BATCH_BUCKETS,
    FlightRecorder,
    MetricsRegistry,
    SpanRing,
    absorb_exposition,
    render_families,
    startup_phase,
)
from xllm_service_tpu.service.response_handler import ResponseHandler
from xllm_service_tpu.tokenizer import ChatTemplate, create_tokenizer
from xllm_service_tpu.tokenizer.tokenizer import IncrementalDetokenizer

logger = logging.getLogger(__name__)


# Process-local instance registry (api/instance_registry.py): colocated PD
# peers hand KV off through direct calls; re-exported here for tests.
from xllm_service_tpu.api.instance_registry import (  # noqa: E402
    _LOCAL_INSTANCES,
    _LOCAL_MU,
)
from xllm_service_tpu.api.instance_fabric import FabricMixin  # noqa: E402
from xllm_service_tpu.api.instance_kv import KVHandoffMixin  # noqa: E402
from xllm_service_tpu.api.instance_mm import MultimodalMixin  # noqa: E402
from xllm_service_tpu.api.instance_serving import ServingMixin  # noqa: E402


class InstanceServer(
    KVHandoffMixin, FabricMixin, MultimodalMixin, ServingMixin
):
    @startup_phase("instance")
    def __init__(
        self,
        engine_cfg: EngineConfig,
        master_rpc_addr: str = "",
        host: str = "127.0.0.1",
        port: int = 0,
        tokenizer_path: str = "",
        heartbeat_interval_s: float = 3.0,
        engine=None,
        lora_adapters=None,  # {name: peft-dir path OR adapter dict}
    ):
        # Deferred imports keep jax out of service-only processes.
        if engine is None:
            if engine_cfg.instance_type == "ENCODE":
                # EPD stage E: this instance hosts the vision encoder
                # instead of an LM engine (engine_cfg.model names a
                # VisionConfig, e.g. vit-tiny).
                from xllm_service_tpu.runtime.vision_executor import (
                    EncoderEngine,
                )

                engine = EncoderEngine(
                    model=engine_cfg.model,
                    checkpoint_path=engine_cfg.checkpoint_path,
                    dtype=engine_cfg.dtype,
                    cfg=engine_cfg,
                )
            else:
                from xllm_service_tpu.runtime.engine import InferenceEngine
                from xllm_service_tpu.runtime.executor import ModelExecutor

                engine = InferenceEngine(
                    engine_cfg, executor=ModelExecutor(engine_cfg)
                )
        self.engine = engine
        self.cfg = engine_cfg
        # Multi-LoRA registry: adapter name -> row in the executor's
        # stacks; OpenAI `model` fields naming an adapter route to it.
        self.lora_names: Dict[str, int] = {}
        if lora_adapters:
            if not hasattr(engine, "set_lora_adapters"):
                raise ValueError(
                    "lora_adapters requires a real inference engine"
                )
            loaded = {}
            for name, spec in lora_adapters.items():
                if isinstance(spec, str):
                    from xllm_service_tpu.runtime.weights import (
                        load_lora_checkpoint,
                    )

                    spec = load_lora_checkpoint(
                        spec, self.engine.executor.cfg
                    )
                loaded[name] = spec
            self.lora_names = self.engine.set_lora_adapters(loaded)
        self.tokenizer = create_tokenizer(tokenizer_path)
        self.chat_template = ChatTemplate(self.tokenizer)
        self._responses = ResponseHandler()

        # Front door on the configured backend (EngineConfig.http_backend;
        # "threaded" default — see the config comment there).
        self.http = make_http_server(
            getattr(engine_cfg, "http_backend", "threaded"), host, port,
            do_get=self.handle_get, do_post=self.handle_post,
            name=f"inst-{engine_cfg.instance_name or port}",
        )
        self.name = engine_cfg.instance_name or f"{host}:{self.http.port}"
        # Tag the engine so its fault-injection points (FakeEngine's step
        # loop) can be matched per instance in a chaos spec.
        setattr(self.engine, "instance_name", self.name)
        self.meta = InstanceMetaInfo(
            name=self.name,
            rpc_address=f"{host}:{self.http.port}",
            http_address=f"{host}:{self.http.port}",
            model_name=engine_cfg.model,
            type=InstanceType.parse(engine_cfg.instance_type),
            dp_size=engine_cfg.dp_size,
            tp_size=engine_cfg.tp_size,
            lora_adapters=sorted(self.lora_names),
        )
        # Fixed-role instances SERVE their declared role from beat one —
        # the field otherwise defaults to PREFILL and an ENCODE instance
        # would heartbeat a role mismatch the master can never reconcile
        # (/flip only swaps PREFILL<->DECODE), looping flip notifications
        # forever. MIX keeps the default: the master assigns its first
        # serving role and the reconciliation beat self-heals.
        if self.meta.type in (
            InstanceType.PREFILL, InstanceType.DECODE, InstanceType.ENCODE
        ):
            self.meta.current_type = self.meta.type
        if self.meta.type == InstanceType.ENCODE:
            # Advertise the hosted modality: encoders serve ONE tower
            # (vision_executor.EncoderEngine), and the scheduler must
            # route each media request to an encoder covering every
            # requested modality (review finding, r5).
            mods = []
            vis = getattr(self.engine, "executor", None)
            if vis is not None:
                mods.append("image")
                if getattr(getattr(vis, "cfg", None), "arch", "") in (
                    "qwen2vl", "qwen25vl"
                ):
                    mods.append("video")
            if getattr(self.engine, "audio_executor", None) is not None:
                mods.append("audio")
            self.meta.modalities = mods
        ttft, tpot = self.engine.profiling_data()
        self.meta.ttft_profiling_data = ttft
        self.meta.tpot_profiling_data = tpot

        # Instance-front-door registry: heartbeat-visible load/latency as
        # pull gauges (any engine, FakeEngine included) plus the
        # speculative-decoding counters when the engine runs a verifier.
        # /metrics renders this merged with the engine's OWN registry
        # (runtime/engine.py step/preemption/prefix-cache series).
        self.metrics = MetricsRegistry()
        self.metrics.gauge(
            "xllm_engine_waiting_requests", "Engine admission queue depth",
        ).set_function(
            lambda: self.engine.get_load_metrics().waiting_requests_num
        )
        self.metrics.gauge(
            "xllm_engine_kv_cache_usage", "Fraction of KV blocks in use",
        ).set_function(
            lambda: self.engine.get_load_metrics().gpu_cache_usage_perc
        )
        self.metrics.gauge(
            "xllm_engine_recent_max_ttft_ms",
            "Max TTFT over the engine's recent window",
        ).set_function(
            lambda: self.engine.get_latency_metrics().recent_max_ttft
        )
        self.metrics.gauge(
            "xllm_engine_recent_max_tbt_ms",
            "Max time-between-tokens over the engine's recent window",
        ).set_function(
            lambda: self.engine.get_latency_metrics().recent_max_tbt
        )
        # Spec series only when this instance actually runs a verifier —
        # a spec-off engine exporting a 0x "realized speedup" gauge would
        # skew fleet dashboards (and FakeEngine has no spec at all).
        if getattr(
            getattr(self.engine, "cfg", None), "speculative_tokens", 0
        ) > 0:
            self.metrics.counter(
                "xllm_engine_spec_verify_steps_total",
                "Speculative verify steps run",
            ).set_function(lambda: self.engine.spec_steps)
            self.metrics.counter(
                "xllm_engine_spec_tokens_emitted_total",
                "Tokens emitted by speculative verify steps",
            ).set_function(lambda: self.engine.spec_tokens_emitted)
            self.metrics.gauge(
                "xllm_engine_spec_tokens_per_slot_step",
                "Realized speculative speedup over plain decode",
            ).set_function(
                lambda: self.engine.spec_tokens_emitted
                / max(self.engine.spec_slot_steps, 1)
            )

        # Distributed tracing + anomaly flight recorder (obs/flight.py,
        # docs/OBSERVABILITY.md). The ring is always-on (the recorder
        # dumps it on fenced RPCs / KV stalls); span EMISSION is gated by
        # the XLLM_TRACE hatch — with it off the engine's span hook stays
        # None and the token path does no per-step tracing work at all.
        self.trace_enabled = os.environ.get(
            "XLLM_TRACE", "1"
        ).lower() not in ("0", "false", "off")
        self.span_ring = SpanRing(
            self.name,
            int(os.environ.get("XLLM_TRACE_RING", "") or 2048),
        )
        self.flight = FlightRecorder(
            self.span_ring,
            os.path.join(
                os.environ.get("XLLM_TRACE_DIR", "trace"),
                f"flight-{self.name}",
            ),
            registry=self.metrics,
        )
        if self.trace_enabled:
            # Engine-side emission (prefill chunks, step batches): the
            # engine loop calls hook(srid, stage, **fields) per step /
            # chunk — never per token — only while a hook is installed.
            setattr(self.engine, "span_hook", self.span_ring.emit)

        # Pipelined PD handoff state + metrics (instance_kv mixin):
        # streaming-session tables and the handoff stall/overlap series.
        self._init_kv_handoff()

        self._master: Optional[MasterClient] = (
            MasterClient(master_rpc_addr) if master_rpc_addr else None
        )
        # Prefix-fabric state + metrics (instance_fabric mixin): peer
        # fetch dedup tables, the evict-offer worker, and the
        # xllm_fabric_* series. After self._master — the evictor side
        # needs it to ask /rpc/fabric/evict_offer.
        self._init_fabric()
        self._heartbeat: Optional[HeartbeatLoop] = (
            HeartbeatLoop(
                self._master,
                self.meta,
                interval_s=heartbeat_interval_s,
                collect_load=self._collect_load,
                collect_latency=self.engine.get_latency_metrics,
                collect_cache_event=self.engine.take_cache_event,
                collect_cache_snapshot=getattr(
                    self.engine, "cache_snapshot_event", None
                ),
            )
            if self._master
            else None
        )
        # decode->service push pipeline: every item is a LIST of outputs
        # (a step's, where the engine marks the end of a step's booking;
        # one output otherwise), None stops the loop
        self._push_q: "queue.Queue[Optional[List[RequestOutput]]]" = (
            queue.Queue()
        )
        # One hand-over a step (instance_serving._hand_over): the push
        # callbacks of an engine that has step listeners only collect
        # here, [(output, the request's detokenizers)] in booking order;
        # the listener empties it where the booking ends. Engine thread
        # only, and only inside a step (`engine.step_open()`): no lock.
        self._pending_outs: list = []
        self._step_open = None
        self._m_handover = self.metrics.histogram(
            "xllm_engine_handover_outputs",
            "Outputs the engine thread hands to the push queue at one "
            "step boundary (one put; a step's booked rows)",
            buckets=BATCH_BUCKETS,
        )
        add_listener = getattr(self.engine, "add_step_listener", None)
        if add_listener is not None:
            add_listener(self._hand_over)
            self._step_open = self.engine.step_open
        self._push_thread = threading.Thread(
            target=self._push_loop, name=f"gen-push-{self.name}", daemon=True
        )
        # service_request_id -> engine request_ids (n>1 fans out to one
        # engine request per sequence; /cancel and dropped-stream feedback
        # cancel them all)
        self._srid_map: Dict[str, List[str]] = {}
        self._srid_mu = threading.Lock()
        # Per-srid reconcile manifest state (same lock): owning master
        # epoch, prompt-token count, and delivered-token count — what a
        # freshly elected master needs to rebuild its load charges from
        # POST /reconcile (docs/FAULT_TOLERANCE.md, control plane).
        self._srid_info: Dict[str, Dict[str, int]] = {}
        # Epoch fence: highest master epoch this instance has seen on any
        # control RPC. RPCs stamped with a LOWER epoch come from a
        # deposed master and are rejected with 412 — split-brain dispatch
        # is structurally impossible, not just unlikely.
        self._fence_mu = threading.Lock()
        self._fence_epoch = 0
        self._m_fenced = self.metrics.counter(
            "xllm_instance_fenced_rpcs_total",
            "Master RPCs rejected for carrying a stale fencing epoch "
            "(split-brain dispatch attempts)",
        )
        self._m_orphans = self.metrics.counter(
            "xllm_service_orphan_reaped_total",
            "In-flight requests reaped after a master takeover did not "
            "reclaim them within the orphan TTL (engine work cancelled, "
            "KV blocks freed)",
        )
        # decode-peer address cache (PD disagg handoff target)
        self._peer_addrs: Dict[str, str] = {}
        # Alternate PD response topology (service.h:61-71 analog): srid ->
        # prefill-instance address to relay generations through instead of
        # pushing to the master directly.
        self._relay_addrs: Dict[str, str] = {}
        # EPD multimodal state + instruments (instance_mm mixin): the
        # monolithic /mm/import landing table, the streamed-handoff
        # session handles, and the reap/wait/overlap series.
        self._init_mm()
        # srid -> set once a generations push carrying it was acked by the
        # master; the handoff sender waits on this so the decode peer's
        # tokens can never reach the master before the first token
        self._push_acked: Dict[str, threading.Event] = {}
        self._push_acked_mu = threading.Lock()
        # PD handoff transfer pipeline: the engine thread only enqueues
        # (the KV payload is already a host copy and the slot/blocks are
        # released before send); the master-ack wait + KV POST run here so
        # a slow master or decode peer never stalls admission/decode. A
        # small worker POOL bounds head-of-line blocking: one stuck peer
        # (60s ack wait + HTTP timeout) delays only its own lane. The queue
        # is BOUNDED so a stuck master/peer backpressures the engine thread
        # (blocking put) instead of accumulating unbounded host KV copies.
        self._transfer_q: "queue.Queue[Optional[Callable[[], None]]]" = (
            queue.Queue(maxsize=8)
        )
        self._transfer_threads = [
            threading.Thread(
                target=self._transfer_loop,
                name=f"kv-xfer-{self.name}-{i}",
                daemon=True,
            )
            for i in range(4)
        ]
        # Pipelined-handoff chunk lane (docs/PD_DISAGGREGATION.md): chunk
        # jobs get their OWN bounded queue + workers so one streaming
        # session to a stuck decode peer can only saturate this lane —
        # chunk sends then fail fast (put_nowait -> session abort ->
        # monolithic fallback) and the monolithic plane's engine-thread
        # backpressure never engages on a chunk's behalf.
        self._stream_q: "queue.Queue[Optional[Callable[[], None]]]" = (
            queue.Queue(maxsize=8)
        )
        self._stream_threads = [
            threading.Thread(
                target=self._transfer_loop,
                args=(self._stream_q,),
                name=f"kv-stream-{self.name}-{i}",
                daemon=True,
            )
            for i in range(2)
        ]
        # Cross-process device-to-device KV plane (runtime/transfer.py):
        # offers ride this process's TransferServer; the /kv/import control
        # message carries only {addr, uuid, shape, dtype} and the decode
        # peer pulls straight into its device memory. ENCODE instances and
        # disabled configs keep the bytes-in-body plane.
        self._kv_transfer = None
        # Peers that rejected a kv_pull header (no transfer server): the
        # bytes plane is used for them without another failing round trip.
        self._peer_no_pull: set = set()
        if engine_cfg.enable_kv_transfer_server and (
            engine_cfg.instance_type != "ENCODE"
        ):
            from xllm_service_tpu.runtime.transfer import get_transfer_server

            self._kv_transfer = get_transfer_server(
                engine_cfg.kv_transfer_listen
            )

    # ------------------------------------------------------------------ #
    def _collect_load(self):
        """Heartbeat load snapshot: the engine's own metrics stamped with
        the KV-handoff stall EWMA folded from _kv_stall_samples — the
        goodput controller's live disaggregation-cost signal (0.0 until
        this instance has completed a handoff)."""
        lm = self.engine.get_load_metrics()
        samples = list(self._kv_stall_samples)
        if samples:
            ewma = samples[0][1]
            for _, stall_ms in samples[1:]:
                ewma += 0.3 * (stall_ms - ewma)
            lm.kv_stall_ms_ewma = ewma
        return lm

    @startup_phase("instance")
    def start(self) -> None:
        with _LOCAL_MU:
            _LOCAL_INSTANCES[self.name] = self
        self.engine.start()
        self.http.start()
        self._push_thread.start()
        for t in self._transfer_threads:
            t.start()
        for t in self._stream_threads:
            t.start()
        if self._heartbeat is not None:
            self._heartbeat.start()
        logger.info("instance %s serving on :%d", self.name, self.http.port)

    def crash(self) -> None:
        """UNGRACEFUL death for fault-injection tests/benches: heartbeats
        stop, the HTTP server drops (in-flight requests included), the
        engine halts, and the generations push channel goes silent — all
        with NO deregistration. The master learns via lease expiry /
        disconnected pruning exactly as for a crashed engine process;
        mid-stream requests die (error-finish after removal) instead of
        quietly completing through a still-alive push loop. A later
        stop() still runs the remaining thread teardown."""
        self._crashed = True  # push loop drops everything from here on
        with _LOCAL_MU:
            if _LOCAL_INSTANCES.get(self.name) is self:
                del _LOCAL_INSTANCES[self.name]
        if self._heartbeat is not None:
            self._heartbeat.stop()
        if not getattr(self, "_http_stopped", False):
            self._http_stopped = True
            self.http.stop()
        self.engine.stop()

    def stop(self) -> None:
        with _LOCAL_MU:
            if _LOCAL_INSTANCES.get(self.name) is self:
                del _LOCAL_INSTANCES[self.name]
        if self._heartbeat is not None:
            self._heartbeat.stop()
        if self._master is not None:
            # Graceful shutdown: leave the registry NOW (best-effort) so
            # the master stops routing here immediately — crash death
            # still falls to lease-TTL expiry.
            try:
                self._master.deregister(self.name)
            except Exception:
                pass
        self._push_q.put(None)
        self._push_thread.join(timeout=5.0)
        if self._fabric_evict_thread is not None:
            try:
                self._fabric_evict_q.put_nowait(None)
            except queue.Full:
                pass  # daemon thread; bounded queue must not block stop
        for _ in self._transfer_threads:
            self._transfer_q.put(None)
        for t in self._transfer_threads:
            t.join(timeout=5.0)
        for _ in self._stream_threads:
            try:
                # The lane is bounded and may be saturated by a stuck peer
                # (the exact scenario it isolates) — never let shutdown
                # block behind it; the workers are daemons and the join
                # below is already time-bounded.
                self._stream_q.put(None, timeout=1.0)
            except queue.Full:
                break
        for t in self._stream_threads:
            t.join(timeout=5.0)
        if not getattr(self, "_http_stopped", False):
            self._http_stopped = True
            self.http.stop()
        self.engine.stop()

    @property
    def address(self) -> str:
        return f"{self.http.host}:{self.http.port}"

    # ------------------------------------------------------------------ #
    # decode -> service push (proto analog: Generations RPC)
    # ------------------------------------------------------------------ #

    def _push_loop(self) -> None:
        while True:
            batch = self._push_q.get()
            if batch is None:
                return
            if getattr(self, "_crashed", False):
                continue  # crashed instances push nothing (fault injection)
            batch = list(batch)
            # micro-batch whatever else is queued (DisaggStreamGenerations
            # carries a list for the same reason)
            while True:
                try:
                    nxt = self._push_q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._push_q.put(None)
                    break
                batch.extend(nxt)
            # Partition by destination: master push (default topology) vs
            # relay through the request's prefill instance (alternate
            # topology — service.h:61-71). The master group goes FIRST and
            # relay retries are short with a direct-to-master fallback, so
            # a dead relay peer can't head-of-line-block direct streams.
            groups: Dict[str, List[RequestOutput]] = {}
            for out in batch:
                dest = self._relay_addrs.get(out.service_request_id, "")
                groups.setdefault(dest, []).append(out)
            cont: Dict[str, bool] = {}
            for dest in sorted(groups, key=bool):  # "" (master) first
                group = groups[dest]
                got = None
                backoffs = (0.2, 0.5, 1.0) if dest else (
                    0.2, 0.5, 1.0, 2.0, 5.0, 10.0
                )
                for backoff in backoffs:
                    try:
                        if dest:
                            got = self._relay_generations(dest, group)
                        else:
                            # Stamped with the fence high-water: a master
                            # whose term is older 503s instead of judging
                            # (split-brain window), and the retry lands at
                            # the successor once the heartbeat re-points.
                            got = self._master.push_generations(
                                group, epoch=self._fence_epoch
                            )
                        break
                    except Exception:
                        # Destination briefly unreachable: the batch may
                        # hold a request's only finished=True marker —
                        # retry, don't drop (a drop strands the client
                        # until its timeout).
                        time.sleep(backoff)
                if got is None and dest:
                    # Relay peer is gone: downgrade to the direct topology
                    # rather than stranding the client.
                    logger.warning(
                        "relay peer %s unreachable; pushing %d outputs "
                        "directly to master", dest, len(group),
                    )
                    for out in group:
                        self._relay_addrs.pop(out.service_request_id, None)
                    try:
                        got = self._master.push_generations(
                            group, epoch=self._fence_epoch
                        )
                    except Exception:
                        got = None
                if got is None:
                    logger.error(
                        "generations push to %s failed permanently; "
                        "dropping %d outputs", dest or "master", len(group),
                    )
                    for out in group:
                        if out.finished:
                            self._relay_addrs.pop(
                                out.service_request_id, None
                            )
                    continue
                cont.update(got)
                for out in group:
                    if out.finished:
                        self._relay_addrs.pop(out.service_request_id, None)
            for srid, keep in cont.items():
                with self._push_acked_mu:
                    ev = self._push_acked.get(srid)
                if ev is not None:
                    ev.set()
                if not keep:
                    self._relay_addrs.pop(srid, None)
                    with self._srid_mu:
                        rids = self._srid_map.pop(srid, None) or []
                        self._srid_forget_locked(srid)
                    for rid in rids:
                        self.engine.cancel(rid)

    def _relay_generations(
        self, addr: str, outputs: List[RequestOutput]
    ) -> Dict[str, bool]:
        """Decode side of the alternate topology: hand the token batch to
        the prefill instance, which forwards it to the master and returns
        the master's continue map."""
        from xllm_service_tpu.api.http_utils import post_json
        from xllm_service_tpu.api.protocol import output_to_json

        code, resp = post_json(
            addr,
            "/rpc/relay_generations",
            {"gens": [output_to_json(o) for o in outputs]},
            timeout=5.0,
        )
        if code != 200:
            raise RuntimeError(f"relay peer {addr} returned {code}")
        return resp.get("cont", {})

    # ------------------------------------------------------------------ #
    # HTTP surface
    # ------------------------------------------------------------------ #

    def _metrics_body(self) -> str:
        """Instance exposition: the front-door registry merged with the
        engine's own (runtime/engine.py registers its step/preemption/
        prefix-cache/host-tier series there; FakeEngine has none)."""
        from collections import OrderedDict

        fams = OrderedDict()
        absorb_exposition(fams, self.metrics.render())
        engine_reg = getattr(self.engine, "metrics", None)
        if engine_reg is not None and hasattr(engine_reg, "render"):
            absorb_exposition(fams, engine_reg.render())
        return render_families(fams)

    def handle_get(self, h: HttpJsonApi) -> None:
        route = h.route
        if route == "/hello":
            h.send_json({"message": f"hello from instance {self.name}"})
        elif route == "/health":
            # Breaker probe target: answering at all proves the HTTP plane
            # is up; the payload lets the prober cross-check identity (a
            # port reused by a different instance must not heal the old
            # name's breaker).
            h.send_json(
                {
                    "ok": True,
                    "name": self.name,
                    "role": self.meta.current_type.name,
                }
            )
        elif route == "/metrics":
            body = self._metrics_body().encode()
            h.send_response(200)
            h.send_header("Content-Type", "text/plain; version=0.0.4")
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            h.wfile.write(body)
        elif route == "/v1/models":
            h.send_json(
                {
                    "object": "list",
                    "data": [{"id": self.cfg.model, "object": "model"}]
                    + [
                        {"id": n, "object": "model",
                         "parent": self.cfg.model}
                        for n in sorted(self.lora_names)
                    ],
                }
            )
        elif route == "/trace":
            # Trace-collector pull (docs/OBSERVABILITY.md): this process's
            # ring spans, filtered to one request when ?srid= is given.
            # Timestamps are THIS process's monotonic clock — the master
            # shifts them with the heartbeat-derived offset.
            srid = h.query().get("srid", "")
            spans = (
                self.span_ring.for_request(srid)
                if srid
                else self.span_ring.snapshot()
            )
            h.send_json(
                {
                    "process": self.name,
                    "spans": spans,
                    "ring": self.span_ring.stats(),
                }
            )
        else:
            h.send_error_json(404, f"no route {route}")

    def _span(self, srid: str, stage: str, **fields: Any) -> None:
        """One instance-side span into the flight ring (no-op with the
        XLLM_TRACE hatch off — the serving paths stay allocation-free)."""
        if self.trace_enabled:
            self.span_ring.emit(srid, stage, **fields)

    # ------------------------------------------------------------------ #
    # epoch fencing + takeover reconciliation (docs/FAULT_TOLERANCE.md)
    # ------------------------------------------------------------------ #

    def _fence_epoch_check(self, epoch) -> int:
        """Raise the high-water fencing epoch; returns 0 when `epoch` is
        acceptable (absent / current / newer) or the current fence value
        the caller is behind. Only stamped RPCs participate — direct
        client traffic carries no epoch and always passes."""
        try:
            e = int(epoch)
        except (TypeError, ValueError):
            return 0
        if e <= 0:
            return 0
        with self._fence_mu:
            if e < self._fence_epoch:
                return self._fence_epoch
            self._fence_epoch = e
        return 0

    def _fence_reject(self, h: HttpJsonApi, body) -> bool:
        """412-reject an RPC stamped with a stale master epoch (counted).
        The DISTINCT status + `fenced` marker lets the deposed master
        tell "you are not the master anymore" apart from a client error —
        it must stop dispatching, not blame the request."""
        stamped = (body or {}).get("master_epoch")
        cur = self._fence_epoch_check(stamped)
        if not cur:
            return False
        self._m_fenced.inc()
        # Anomaly trigger: a fenced RPC means split-brain dispatch was
        # just attempted — capture the surrounding span window.
        self.flight.trigger(
            "fenced_rpc",
            str((body or {}).get("service_request_id") or ""),
            stale_epoch=stamped, fence_epoch=cur,
        )
        logger.warning(
            "instance %s fenced an RPC from a deposed master "
            "(epoch %s < %d)", self.name, stamped, cur,
        )
        h.send_json(
            {
                "error": {
                    "message": (
                        f"stale master epoch {stamped} < {cur}: this "
                        "master was deposed"
                    ),
                    "type": "stale_epoch",
                },
                "fenced": True,
                "epoch": cur,
            },
            status=412,
        )
        return True

    def _srid_track(
        self, srid: str, prompt_tokens: int, epoch, delivered: int = 0
    ) -> None:
        """Register one forwarded request's reconcile-manifest entry
        (caller does NOT hold _srid_mu)."""
        if not srid:
            return
        try:
            e = int(epoch or 0)
        except (TypeError, ValueError):
            e = 0
        with self._srid_mu:
            self._srid_info[srid] = {
                "prompt_tokens": int(prompt_tokens),
                "delivered": int(delivered),
                "epoch": e,
            }

    def _srid_note_delivered(self, srid: str, n: int) -> None:
        if not srid or n <= 0:
            return
        with self._srid_mu:
            info = self._srid_info.get(srid)
            if info is not None:
                info["delivered"] += n

    def _srid_forget_locked(self, srid: str) -> None:
        """Drop the manifest entry; caller holds _srid_mu."""
        self._srid_info.pop(srid, None)

    def _handle_reconcile(self, h: HttpJsonApi, body: Dict[str, Any]) -> None:
        """Takeover reconciliation target (POST /reconcile): return this
        instance's in-flight request manifest, current load, and the
        committed prefix-cache block hashes so a freshly elected master
        rebuilds its cluster view instead of starting amnesiac. In-flight
        srids the new master does not claim (`known`) are ORPHANS: a TTL
        timer reaps them — engine requests cancelled, blocks freed — so
        a dead master's requests never leak KV. The epoch fence already
        ran (handle_post), so a stale master can neither read manifests
        nor steal the heartbeat target."""
        try:
            # Chaos hook: a dropped receive exercises the master's
            # skip-and-continue takeover path.
            faults.point(
                "reconcile.recv",
                instance=self.name, epoch=body.get("master_epoch", 0),
            )
        except faults.FaultInjected as fi:
            h.send_error_json(503, str(fi))
            return
        known = set(body.get("known") or [])
        try:
            ttl = float(body.get("orphan_ttl_s") or 10.0)
        except (TypeError, ValueError):
            ttl = 10.0
        new_rpc = str(body.get("master_rpc") or "")
        if (
            new_rpc
            and self._master is not None
            and self._master._addr != new_rpc
        ):
            # Follow the new master: heartbeats, re-registration, and the
            # generations push all re-point here — the old master's
            # in-process lease table died with it, so the next beat gets
            # `reregister` and a fresh lease from the survivor.
            logger.info(
                "instance %s re-pointing control plane %s -> %s "
                "(master takeover)", self.name, self._master._addr, new_rpc,
            )
            self._master._addr = new_rpc
        with self._srid_mu:
            inflight = list(self._srid_map.keys())
            manifest = []
            for srid in inflight:
                info = self._srid_info.get(srid, {})
                manifest.append({
                    "service_request_id": srid,
                    "request_ids": list(self._srid_map.get(srid) or []),
                    "owning_epoch": int(info.get("epoch", 0)),
                    "delivered_tokens": int(info.get("delivered", 0)),
                    "prompt_tokens": int(info.get("prompt_tokens", 0)),
                })
            # Garbage entries (request finished between pops): drop.
            for srid in list(self._srid_info):
                if srid not in self._srid_map:
                    self._srid_info.pop(srid, None)
        orphans = [s for s in inflight if s not in known]
        if orphans:
            t = threading.Timer(
                ttl, self._reap_orphans, args=(list(orphans),)
            )
            t.daemon = True
            t.start()
        snap = getattr(self.engine, "cache_snapshot", None)
        hashes: List[str] = []
        if callable(snap):
            try:
                hashes = [bytes(x).hex() for x in snap()]
            except Exception:
                hashes = []
        h.send_json({
            "ok": True,
            "name": self.name,
            "epoch": self._fence_epoch,
            "manifest": manifest,
            "orphans": orphans,
            "load_metrics": self.engine.get_load_metrics().to_json(),
            "cache_hashes": hashes,
        })

    def _reap_orphans(self, srids: List[str]) -> None:
        """Orphan-TTL expiry: requests no reconciliation claimed are dead
        weight — cancel their engine work (frees slots + KV blocks) and
        drop every per-srid table entry. Requests that finished or were
        re-claimed (srid gone from the map) are skipped."""
        reaped = 0
        for srid in srids:
            with self._srid_mu:
                rids = self._srid_map.pop(srid, None)
                self._srid_info.pop(srid, None)
            if rids is None:
                continue
            for rid in rids:
                try:
                    self.engine.cancel(rid)
                except Exception:
                    pass
            self._relay_addrs.pop(srid, None)
            with self._push_acked_mu:
                self._push_acked.pop(srid, None)
            reaped += 1
        if reaped:
            self._m_orphans.inc(reaped)
            logger.warning(
                "instance %s reaped %d orphaned request(s) unclaimed by "
                "the takeover reconciliation", self.name, reaped,
            )

    def handle_post(self, h: HttpJsonApi) -> None:
        route = h.route
        if route == "/kv/import":  # binary body, not JSON
            self._handle_kv_import(h)
            return
        if route == "/kv/fetch":  # binary body, not JSON
            self._handle_kv_fetch(h)
            return
        body = h.read_json()
        if body is None:
            h.send_error_json(400, "invalid JSON body")
            return
        # Epoch fence FIRST, on every control RPC: a deposed master's
        # dispatch/cancel/flip/probe/reconcile must fail identically.
        if self._fence_reject(h, body):
            return
        if route == "/reconcile":
            self._handle_reconcile(h, body)
        elif route == "/health":
            # POST twin of the GET probe: the master's breaker probes the
            # dispatch (POST) plane, not just GET reachability.
            h.send_json(
                {
                    "ok": True,
                    "name": self.name,
                    "role": self.meta.current_type.name,
                }
            )
        elif route == "/v1/completions":
            self._serve(h, body, chat=False)
        elif route == "/v1/chat/completions":
            self._serve(h, body, chat=True)
        elif route == "/v1/embeddings":
            self._handle_embeddings(h, body)
        elif route == "/encode":
            self._handle_encode(h, body)
        elif route == "/mm/import":
            self._handle_mm_import(h, body)
        elif route == "/mm/open":
            self._handle_mm_open(h, body)
        elif route == "/mm/chunk":
            self._handle_mm_chunk(h, body)
        elif route == "/mm/commit":
            self._handle_mm_commit(h, body)
        elif route == "/mm/abort":
            self._handle_mm_abort(h, body)
        elif route == "/rpc/relay_generations":
            # Prefill side of the alternate PD response topology: forward
            # the decode peer's token batch to the master synchronously so
            # the continue map (cancellation feedback) flows back through
            # the same exchange.
            from xllm_service_tpu.api.protocol import output_from_json

            if self._master is None:
                h.send_error_json(503, "no master connection to relay to")
                return
            try:
                outs = [output_from_json(j) for j in body.get("gens", [])]
            except Exception as e:
                h.send_error_json(400, f"bad generations payload: {e}")
                return
            try:
                cont = self._master.push_generations(
                    outs, epoch=self._fence_epoch
                )
            except Exception as e:
                h.send_error_json(502, f"master push failed: {e}")
                return
            h.send_json({"ok": True, "cont": cont})
        elif route == "/flip":
            # Dynamic PD-ratio role flip (SURVEY §7 hard part 4): the
            # master's registry changed this instance's serving role; now
            # the ENGINE learns it too (round-1 weak item 8 — reference
            # never notifies, instance_mgr.cpp:759-807). MIX engines serve
            # both roles with identical compiled shapes (bucketed prefill +
            # fixed decode batch + persistent jit cache), so no
            # recompilation is needed — the role re-points heartbeat
            # metadata and is observable on /metrics.
            role = str(body.get("role", ""))
            if role not in ("PREFILL", "DECODE", "MIX"):
                h.send_error_json(400, f"bad role {role!r}")
                return
            # current_type is the SERVING role; meta.type stays the
            # DECLARED type (MIX) — clobbering it would make a lease-blip
            # re-register permanently strip flip eligibility.
            self.meta.current_type = InstanceType.parse(role)
            setattr(self.engine, "serving_role", role)
            logger.info("instance %s now serving role %s", self.name, role)
            h.send_json({"ok": True, "role": role})
        elif route == "/cancel":
            srid = body.get("service_request_id", "")
            with self._srid_mu:
                rids = self._srid_map.pop(srid, None) or []
                self._srid_forget_locked(srid)
            for rid in rids:
                self.engine.cancel(rid)
            h.send_json({"ok": True, "cancelled": bool(rids)})
        else:
            h.send_error_json(404, f"no route {route}")

    def _detokenize(
        self, out: RequestOutput, detoks: Dict[int, IncrementalDetokenizer]
    ) -> None:
        """Per-request incremental detokenization: characters spanning token
        boundaries are held back until complete (detoks carries one state
        per sequence index for the request's lifetime)."""
        for s in out.outputs:
            if s.token_ids and not s.text:
                d = detoks.get(s.index)
                if d is None:
                    d = detoks[s.index] = IncrementalDetokenizer(self.tokenizer)
                s.text = d.push(s.token_ids)
                if out.finished:
                    s.text += d.flush()
            for lp in s.logprobs:
                if not lp.data.token:
                    lp.data.token = self.tokenizer.id_to_token(lp.data.token_id)


def main(argv=None) -> None:
    import argparse

    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser("xllm-service-tpu instance")
    parser.add_argument("--model", default="llama3-tiny")
    parser.add_argument("--master-rpc-addr", default="")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--tokenizer-path", default="")
    parser.add_argument("--instance-type", default="MIX")
    parser.add_argument("--checkpoint-path", default="")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--block-size", type=int, default=128)
    parser.add_argument("--num-blocks", type=int, default=0)
    parser.add_argument("--max-running-requests", type=int, default=16)
    parser.add_argument("--max-seq-len", type=int, default=2048)
    parser.add_argument(
        "--prefill-buckets", default="128,256,512,1024,2048",
        help="comma-separated prefill padding buckets",
    )
    parser.add_argument(
        "--kv-cache-dtype", default="auto", choices=["auto", "int8"],
        help="int8 halves decode HBM traffic and doubles pool capacity",
    )
    parser.add_argument(
        "--weight-dtype", default="auto", choices=["auto", "int8", "int4"],
        help="int8: per-out-channel W8 halves weight HBM traffic and "
        "per-device param residency; int4: group-wise W4 quarters them",
    )
    parser.add_argument("--dp-size", type=int, default=1)
    parser.add_argument("--tp-size", type=int, default=1)
    parser.add_argument("--ep-size", type=int, default=1)
    parser.add_argument("--sp-size", type=int, default=1)
    parser.add_argument(
        "--sp-prefill-threshold", type=int, default=0,
        help="uncached-suffix length that routes prefill to the sp ring",
    )
    parser.add_argument(
        "--max-prefill-tokens", type=int, default=8192,
        help="strict per-step prefill budget (long prompts chunk across "
        "steps with decode interleaved)",
    )
    parser.add_argument(
        "--compilation-cache-dir", default="",
        help="persistent XLA jit cache (restarts skip the per-shape "
        "compiles); JAX_COMPILATION_CACHE_DIR wins when set, and without "
        "either the cache lives at a fixed path inside the checkout",
    )
    parser.add_argument(
        "--speculative-tokens", type=int, default=0,
        help="prompt-lookup speculative decoding: draft k tokens/step and "
        "verify in one pass (exact; 0 disables)",
    )
    parser.add_argument(
        "--speculative-ngram-max", type=int, default=3,
        help="longest suffix n-gram the drafter matches",
    )
    parser.add_argument(
        "--sync-engine", action="store_true",
        help="run the engine's step loop at pipeline depth 0: every step "
        "is drained before the next is dispatched (EngineConfig.sync_engine)",
    )
    parser.add_argument(
        "--lora", action="append", default=[], metavar="NAME=PATH",
        help="register a peft-layout LoRA adapter served under model "
        "NAME (repeatable)",
    )
    args = parser.parse_args(argv)
    # The backend is what JAX_PLATFORMS names; unset means the accelerator.
    # jax itself falls back to the CPU when it finds none — a server that
    # was meant for a chip must not quietly serve from the host.
    import jax

    if (
        jax.devices()[0].platform == "cpu"
        and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"
    ):
        parser.exit(
            2,
            "xllm-service-tpu instance: jax found no accelerator; set "
            "JAX_PLATFORMS=cpu to serve from the CPU on purpose\n",
        )
    cfg = EngineConfig(
        model=args.model,
        checkpoint_path=args.checkpoint_path,
        instance_type=args.instance_type,
        dtype=args.dtype,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        max_running_requests=args.max_running_requests,
        max_seq_len=args.max_seq_len,
        prefill_buckets=[int(b) for b in args.prefill_buckets.split(",")],
        kv_cache_dtype=args.kv_cache_dtype,
        weight_dtype=args.weight_dtype,
        dp_size=args.dp_size,
        tp_size=args.tp_size,
        ep_size=args.ep_size,
        sp_size=args.sp_size,
        sp_prefill_threshold=args.sp_prefill_threshold,
        max_prefill_tokens=args.max_prefill_tokens,
        compilation_cache_dir=(
            args.compilation_cache_dir or compile_cache.DEFAULT_DIR
        ),
        speculative_tokens=args.speculative_tokens,
        speculative_ngram_max=args.speculative_ngram_max,
        sync_engine=args.sync_engine,
    )
    lora = {}
    for spec in args.lora:
        name, _, path = spec.partition("=")
        if not name or not path:
            parser.error(f"--lora expects NAME=PATH, got {spec!r}")
        lora[name] = path
    srv = InstanceServer(
        cfg,
        master_rpc_addr=args.master_rpc_addr,
        host=args.host,
        port=args.port,
        tokenizer_path=args.tokenizer_path,
        lora_adapters=lora or None,
    )
    srv.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
