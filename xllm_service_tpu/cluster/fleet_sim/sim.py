"""Discrete-event fleet simulator driving the REAL master stack.

The fidelity bet (and what separates this from a queueing model): the
object under test is the ACTUAL `Scheduler` — real routing policies,
real prefix index and fetch planner, real breaker and redispatch/resume
machinery, real goodput controller and admission front door — and only
the ENGINES are simulated. Each simulated instance is a registration
record in a real `MemoryStore` plus a two-event service model
(prefill-done at TTFT, decode-done at TTFT + (n-1)*TPOT, both inflated
by instance load and straggler factors). Requests enter through
`scheduler.schedule()` / `record_new_request()` exactly as the HTTP
tier submits them, and tokens return through
`scheduler.handle_generation()` exactly as /rpc/generations pushes
them — so attempt-versioned wire fencing, mid-stream token replay, and
strand-ordered delivery all run for real at 10k+ concurrent streams.

Three clocks, deliberately separate:
  * the SIM clock (`self.now`) — advances event-to-event; injected into
    the scheduler's control plane (instance health, goodput EWMAs,
    admission buckets) via the `Scheduler(clock=...)` seam;
  * the STORE clock — frozen at 0, so the election lease never expires
    under a GIL stall and the single simulated master stays master
    (kills are explicit store deletes, not lease timeouts);
  * wall time — only the real master loop (idled at a huge interval)
    and the store's watch thread (removal listeners: redispatch,
    resume, fail) see it; the sim calls
    `scheduler.run_master_upkeep()` itself at simulated heartbeat
    cadence.

Instance death is a store DELETE: the real watch fires the real removal
listeners, which redispatch or token-replay-resume every affected
stream — the simulator only stops producing events for the dead
generation and lets wire-id fencing reject the stale ones.

Hatch: XLLM_FLEET_SIM_CAPACITY (per-instance concurrency knee for the
service-time model, default 16; docs/ARCHITECTURE.md).
"""

from __future__ import annotations

import heapq
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from xllm_service_tpu.cluster.fleet_sim.traces import TraceSpec
from xllm_service_tpu.cluster.instance_mgr import instance_key
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.config import ServiceConfig
from xllm_service_tpu.common.hashing import prefix_block_hashes
from xllm_service_tpu.common.types import (
    FinishReason,
    InstanceMetaInfo,
    InstanceType,
    KvCacheEvent,
    LoadMetrics,
    RequestOutput,
    SequenceOutput,
    Status,
    StatusCode,
    Usage,
)
from xllm_service_tpu.coordination.store import MemoryStore
from xllm_service_tpu.service.request import ServiceRequest
from xllm_service_tpu.service.scheduler import Scheduler

# Service-time model: per-request TTFT/TPOT scale linearly past the
# instance's concurrency knee — the simplest model that produces real
# queueing collapse under overload (which is the phenomenon the
# admission A/B and the scenario guards measure).
BASE_TTFT_S = 0.2
BASE_TPOT_S = 0.03
# Prefix-cache hit: prefill shrinks to this fraction when the routed
# instance already holds the request's shared-prefix block.
PREFIX_HIT_TTFT_FRAC = 0.3


def _capacity() -> int:
    try:
        return max(1, int(os.environ.get("XLLM_FLEET_SIM_CAPACITY", "16")))
    except ValueError:
        return 16


class _SimInstance:
    __slots__ = (
        "index", "name", "key", "meta", "alive", "registered",
        "generation", "inflight", "straggler", "groups", "pending_stored",
    )

    def __init__(self, index: int, meta: InstanceMetaInfo) -> None:
        self.index = index
        self.name = meta.name
        self.key = instance_key(meta)
        self.meta = meta
        self.alive = True
        self.registered = False
        self.generation = 0
        self.inflight = 0
        self.straggler = 1.0
        self.groups: set = set()          # prefix groups served (sim model)
        self.pending_stored: set = set()  # block hashes for next heartbeat


class _SimStream:
    """Client-stream stub implementing the ResponseHandler interface
    (write/write_done/finish/finish_with_error). Terminal transitions
    report once into the sim's completion accounting."""

    __slots__ = ("_on_terminal", "_terminal", "error_code")

    def __init__(self, on_terminal: Callable[["_SimStream"], None]) -> None:
        self._on_terminal = on_terminal
        self._terminal = False
        self.error_code: Optional[StatusCode] = None

    def _finish(self) -> None:
        if not self._terminal:
            self._terminal = True
            self._on_terminal(self)

    def write(self, payload) -> bool:
        return True

    def write_done(self) -> bool:
        self._finish()
        return True

    def finish(self, payload) -> bool:
        self._finish()
        return True

    def finish_with_error(self, code, message) -> bool:
        self.error_code = code
        self._finish()
        return True


@dataclass
class SimReport:
    scenario: str = ""
    num_instances: int = 0
    submitted: int = 0
    completed: int = 0
    shed: int = 0
    failed: int = 0
    unrecovered: int = 0
    peak_concurrent: int = 0
    p50_ttft_s: float = 0.0
    p99_ttft_s: float = 0.0
    goodput_tok_s: float = 0.0       # SLO-met generated tokens / sim second
    total_tok_s: float = 0.0         # all generated tokens / sim second
    slo_ttft_s: float = 0.0
    sheds_by_reason: Dict[str, int] = field(default_factory=dict)
    redispatches: int = 0
    resumes: int = 0
    reshape_flips: int = 0
    wanted_instances: Dict[str, int] = field(default_factory=dict)
    sim_duration_s: float = 0.0
    wall_s: float = 0.0
    events: int = 0

    def to_json(self) -> Dict[str, object]:
        return dict(self.__dict__)


class FleetSim:
    """One simulated fleet run (see module docstring). Single-use: build,
    `run(trace)`, read the report, `close()`."""

    def __init__(
        self,
        num_instances: int = 50,
        seed: int = 0,
        policy: str = "",
        admission: bool = False,
        heartbeat_s: float = 3.0,
        slo_ttft_s: float = 30.0,
        config: Optional[ServiceConfig] = None,
        drain_timeout_s: float = 10.0,
    ) -> None:
        self.num_instances = num_instances
        self.seed = seed
        self.heartbeat_s = heartbeat_s
        self.slo_ttft_s = slo_ttft_s
        # No-progress bound on the post-event completion tail: streams
        # still outstanding past it (e.g. their service events were
        # chaos-dropped) report as unrecovered instead of hanging the run.
        self.drain_timeout_s = drain_timeout_s
        self.now = 0.0
        self._rng = random.Random(seed ^ 0x5EED)
        self._events: List = []   # (t, seq, fn) heap
        self._eseq = 0
        self._emu = threading.Lock()
        self._policy = policy

        cfg = config or ServiceConfig()
        cfg.load_balance_policy = policy or cfg.load_balance_policy
        # The real master loop idles on a huge interval; the sim drives
        # run_master_upkeep() itself at simulated heartbeat cadence.
        cfg.heartbeat_interval_s = 3600.0
        cfg.enable_admission_control = admission
        # acquire() must NEVER park the sim thread in a real wait.
        cfg.admission_queue_timeout_s = 0.0
        self.config = cfg

        # Store clock frozen at 0: the election lease cannot expire, so
        # the simulated master never flaps; instance death is an explicit
        # DELETE, exactly like an etcd lease revoke.
        self.store = MemoryStore(clock=lambda: 0.0)
        self.scheduler = Scheduler(
            cfg, store=self.store, identity="fleet-sim",
            clock=lambda: self.now,
        )
        self._await_master()

        self.instances: Dict[str, _SimInstance] = {}
        self._by_index: List[_SimInstance] = []
        for i in range(num_instances):
            meta = InstanceMetaInfo(
                name=f"sim-{i:03d}",
                rpc_address=f"sim-{i:03d}:1",
                http_address=f"sim-{i:03d}:2",
                model_name="sim-model",
                type=InstanceType.MIX,
                ttft_profiling_data=[
                    (64, BASE_TTFT_S * 1e3), (256, BASE_TTFT_S * 1e3),
                    (1024, BASE_TTFT_S * 1e3),
                ],
                tpot_profiling_data=[
                    (1, 10, BASE_TPOT_S * 1e3), (4, 40, BASE_TPOT_S * 1e3),
                    (8, 100, BASE_TPOT_S * 1e3),
                ],
            )
            inst = _SimInstance(i, meta)
            self.instances[inst.name] = inst
            self._by_index.append(inst)
            self._register(inst)
        self._await_registered()

        # Completion accounting (touched from the sim thread, which
        # delivers inline, and from the store's watch thread).
        self._amu = threading.Lock()
        self.submitted = 0
        self.terminal = 0
        self.failed = 0
        self.shed = 0
        self.inflight_streams = 0
        self.peak_concurrent = 0
        self.ttfts: List[float] = []          # sim-time TTFT per stream
        self._t_submit: Dict[str, float] = {}
        self._slo_tokens = 0
        self._all_tokens = 0

    # ------------------------------------------------------------------ #
    # construction helpers
    # ------------------------------------------------------------------ #

    def _await_master(self, timeout: float = 10.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.scheduler.master_state == "active":
                return
            time.sleep(0.005)
        raise RuntimeError(
            f"sim master never reconciled "
            f"(state={self.scheduler.master_state})"
        )

    def _register(self, inst: _SimInstance) -> None:
        self.store.set(inst.key, inst.meta.serialize())
        inst.registered = True
        inst.alive = True

    def _await_registered(self, timeout: float = 10.0) -> None:
        mgr = self.scheduler.instance_mgr
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(mgr.list_instances()) >= self.num_instances:
                return
            time.sleep(0.005)
        raise RuntimeError(
            f"only {len(mgr.list_instances())}/{self.num_instances} "
            "instances registered"
        )

    # ------------------------------------------------------------------ #
    # event loop
    # ------------------------------------------------------------------ #

    def _push(self, t: float, fn: Callable[[], None]) -> None:
        with self._emu:
            self._eseq += 1
            heapq.heappush(self._events, (t, self._eseq, fn))

    def _pop(self):
        with self._emu:
            if not self._events:
                return None
            return heapq.heappop(self._events)

    def run(self, trace: TraceSpec) -> SimReport:
        """Execute one scenario to completion and return its report."""
        wall0 = time.monotonic()
        for spec in trace.requests:
            self._push(spec.t, self._make_arrival(spec))
        for act in trace.actions:
            if act.kind == "drain":
                self._push(act.t, self._make_drain(act.instance))
            elif act.kind == "rejoin":
                self._push(act.t, self._make_rejoin(act.instance))
        for idx, factor in trace.straggler_factors.items():
            self._by_index[idx].straggler = factor
        self._push(self.heartbeat_s, self._heartbeat_tick)

        events = 0
        while True:
            item = self._pop()
            if item is None:
                # Heap drained; the watch thread may still be recovering
                # or failing the tail — nothing left can create sim work
                # except it.
                if self._await_tail():
                    break
                continue
            t, _, fn = item
            self.now = max(self.now, t)
            events += 1
            # Deterministic chaos seam (ONE site): a dropped tick loses
            # this event — the stream it served must be recovered by the
            # real machinery or counted unrecovered, never hang the sim.
            try:
                faults.point("fleet_sim.tick", t=f"{t:.3f}")
            except faults.FaultInjected:
                continue
            fn()

        report = self._report(trace, events)
        report.wall_s = time.monotonic() - wall0
        return report

    def _await_tail(self, timeout: Optional[float] = None) -> bool:
        """True when every submitted stream reached a terminal state (or
        no further progress happens within `timeout` real seconds)."""
        if timeout is None:
            timeout = self.drain_timeout_s
        deadline = time.monotonic() + timeout
        last = -1
        while time.monotonic() < deadline:
            with self._amu:
                done = self.terminal + self.shed
                outstanding = self.submitted - done
            with self._emu:
                if self._events:
                    return False  # a recovery scheduled new work
            if outstanding <= 0:
                return True
            if done != last:
                last = done
                deadline = time.monotonic() + timeout
            time.sleep(0.01)
        return True

    # ------------------------------------------------------------------ #
    # request lifecycle
    # ------------------------------------------------------------------ #

    def _tokens_for(self, spec) -> List[int]:
        if spec.prefix_group >= 0:
            # Shared 1-block (block_size tokens) prefix per group, unique
            # tail — the REAL chained hashing scores these as hits.
            bs = self.config.block_size
            tail = max(spec.prompt_len, bs + 32) - bs
            return [7000 + spec.prefix_group] * bs + [
                self._rng.randint(1, 4096) for _ in range(tail)
            ]
        return [self._rng.randint(1, 4096) for _ in range(spec.prompt_len)]

    def _make_arrival(self, spec) -> Callable[[], None]:
        def arrive() -> None:
            with self._amu:
                self.submitted += 1
                n = self.submitted - (self.terminal + self.shed)
            srid = f"sim-r{self.submitted}"
            req = ServiceRequest(
                service_request_id=srid,
                model="sim-model",
                stream=True,
                max_tokens=spec.gen_len,
                token_ids=self._tokens_for(spec),
                tenant=spec.tenant,
            )
            status = self.scheduler.schedule(req)
            if not status.ok():
                with self._amu:
                    if status.code == StatusCode.RESOURCE_EXHAUSTED:
                        self.shed += 1
                    else:
                        # No routable instance etc: a front-door failure,
                        # terminal for accounting.
                        self.terminal += 1
                        self.failed += 1
                return
            with self._amu:
                self.inflight_streams += 1
                if self.inflight_streams > self.peak_concurrent:
                    self.peak_concurrent = self.inflight_streams
                self._t_submit[srid] = self.now
            stream = _SimStream(lambda s, r=req: self._on_terminal(r, s))
            dispatch = self.scheduler.record_new_request(
                req, stream, None, self._make_dispatch(req, spec),
            )
            try:
                dispatch()
            except Exception:
                if not self.scheduler.redispatch_request(srid):
                    self.scheduler.fail_request(
                        srid, StatusCode.UNAVAILABLE,
                        "sim dispatch failed with no fallback",
                    )
        return arrive

    def _make_dispatch(self, req: ServiceRequest, spec) -> Callable[[], None]:
        def dispatch() -> None:
            name = req.routing.prefill_name
            inst = self.instances.get(name)
            if inst is None or not inst.alive or not inst.registered:
                raise ConnectionError(f"sim instance {name} is down")
            wire = req.wire_srid or req.service_request_id
            gen = inst.generation
            inst.inflight += 1
            cap = _capacity()
            load = 1.0 + inst.inflight / cap
            ttft = BASE_TTFT_S * load * inst.straggler
            if spec.prefix_group >= 0 and spec.prefix_group in inst.groups:
                ttft *= PREFIX_HIT_TTFT_FRAC
            tpot = BASE_TPOT_S * load * inst.straggler
            n_rest = max(spec.gen_len - 1, 0)
            t_first = self.now + ttft
            self._push(t_first, lambda: self._prefill_done(
                req, spec, inst, wire, gen,
            ))
            self._push(t_first + n_rest * tpot, lambda: self._decode_done(
                req, spec, inst, wire, gen,
            ))
        return dispatch

    def _prefill_done(self, req, spec, inst, wire, gen) -> None:
        if not inst.alive or inst.generation != gen:
            return  # dead attempt; recovery machinery owns the stream
        if spec.prefix_group >= 0:
            inst.groups.add(spec.prefix_group)
            bs = self.config.block_size
            inst.pending_stored.update(prefix_block_hashes(
                req.token_ids[:bs], bs, self.config.murmur_hash3_seed,
            ))
        srid = req.service_request_id
        # Sim-time TTFT: recorded once, at the FIRST attempt that delivers.
        with self._amu:
            t0 = self._t_submit.pop(srid, None)
        if t0 is not None:
            ttft = self.now - t0
            with self._amu:
                self.ttfts.append(ttft)
                if ttft <= self.slo_ttft_s:
                    self._slo_tokens += spec.gen_len
                self._all_tokens += spec.gen_len
        self.scheduler.handle_generation(RequestOutput(
            request_id=srid,
            service_request_id=wire,
            status=Status(StatusCode.OK),
            outputs=[SequenceOutput(index=0, text="t", token_ids=[11])],
            finished=False,
        ))

    def _decode_done(self, req, spec, inst, wire, gen) -> None:
        if inst.generation == gen and inst.inflight > 0:
            inst.inflight -= 1
        if not inst.alive or inst.generation != gen:
            return
        n_rest = max(spec.gen_len - 1, 0)
        self.scheduler.handle_generation(RequestOutput(
            request_id=req.service_request_id,
            service_request_id=wire,
            status=Status(StatusCode.OK),
            outputs=[SequenceOutput(
                index=0, text="d" * n_rest, token_ids=[13] * n_rest,
                finish_reason=FinishReason.LENGTH,
            )],
            usage=Usage(
                num_prompt_tokens=len(req.token_ids),
                num_generated_tokens=spec.gen_len,
            ),
            finished=True,
        ))

    def _on_terminal(self, req: ServiceRequest, stream: _SimStream) -> None:
        with self._amu:
            self.terminal += 1
            self.inflight_streams -= 1
            if stream.error_code is not None:
                self.failed += 1
            self._t_submit.pop(req.service_request_id, None)

    # ------------------------------------------------------------------ #
    # fleet actions + heartbeats
    # ------------------------------------------------------------------ #

    def _make_drain(self, idx: int) -> Callable[[], None]:
        def drain() -> None:
            inst = self._by_index[idx]
            if not inst.registered:
                return
            inst.registered = False
            # Generation bump: events produced by attempts routed to the
            # pre-restart incarnation die with it (wire fencing rejects
            # them anyway; this also keeps the inflight gauge honest).
            inst.generation += 1
            inst.inflight = 0
            inst.alive = False
            # The real watch fires the real removal listeners: every
            # affected stream redispatches (pre-token) or token-replay
            # resumes (mid-stream) onto survivors.
            self.store.remove(inst.key)
        return drain

    def _make_rejoin(self, idx: int) -> Callable[[], None]:
        def rejoin() -> None:
            inst = self._by_index[idx]
            if inst.registered:
                return
            inst.generation += 1
            inst.groups.clear()
            inst.pending_stored.clear()
            self._register(inst)
        return rejoin

    def _heartbeat_tick(self) -> None:
        cap = _capacity()
        for inst in self._by_index:
            if not (inst.alive and inst.registered):
                continue
            stored = inst.pending_stored
            inst.pending_stored = set()
            self.scheduler.handle_instance_heartbeat(
                inst.name,
                load_metrics=LoadMetrics(
                    waiting_requests_num=max(inst.inflight - cap, 0),
                    gpu_cache_usage_perc=min(inst.inflight / cap, 1.0),
                ),
                cache_event=(
                    KvCacheEvent(stored_cache=stored) if stored else None
                ),
            )
        self.scheduler.run_master_upkeep()
        # Repush only while OTHER events remain: once arrivals and service
        # completions drain, the tail is the watch thread's recoveries
        # (wall time, no upkeep needed) — repushing on outstanding>0 would
        # race them and spin the sim clock forward for nothing.
        with self._emu:
            more = len(self._events) > 0
        if more:
            self._push(self.now + self.heartbeat_s, self._heartbeat_tick)

    # ------------------------------------------------------------------ #
    # reporting / teardown
    # ------------------------------------------------------------------ #

    @staticmethod
    def _pct(sorted_vals: List[float], q: float) -> float:
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
        return sorted_vals[i]

    def _report(self, trace: TraceSpec, events: int) -> SimReport:
        sched = self.scheduler
        with self._amu:
            ttfts = sorted(self.ttfts)
            submitted = self.submitted
            terminal = self.terminal
            shed = self.shed
            failed = self.failed
            peak = self.peak_concurrent
            slo_tokens = self._slo_tokens
            all_tokens = self._all_tokens
        duration = max(self.now, trace.duration_s)
        return SimReport(
            scenario=trace.name,
            num_instances=self.num_instances,
            submitted=submitted,
            completed=terminal - failed,
            shed=shed,
            failed=failed,
            unrecovered=max(submitted - terminal - shed, 0),
            peak_concurrent=peak,
            p50_ttft_s=self._pct(ttfts, 0.50),
            p99_ttft_s=self._pct(ttfts, 0.99),
            goodput_tok_s=slo_tokens / duration,
            total_tok_s=all_tokens / duration,
            slo_ttft_s=self.slo_ttft_s,
            sheds_by_reason=dict(sched.admission.sheds),
            redispatches=sched.total_redispatches,
            resumes=sched.total_resumes,
            reshape_flips=sched.goodput.reshape_flips,
            wanted_instances=sched.goodput.wanted_instances(),
            sim_duration_s=duration,
            events=events,
        )

    def close(self) -> None:
        self.scheduler.stop(drain_timeout_s=0.0)
        self.store.close()
