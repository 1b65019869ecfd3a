"""Request-lifecycle spans: structured stage records -> timelines/traces.

The tracer (service/request.py RequestTracer.stage) appends one JSONL
record per stage transition:

    {"type": "stage", "service_request_id": ..., "stage": ...,
     "t_mono_ms": <monotonic ms>, "timestamp_ms": <wall ms>, ...fields}

Stage vocabulary (SPAN_STAGES) follows the request path end to end:
receive -> tokenize -> route -> dispatch -> first_token -> decode ticks ->
finish (or cancel/error), with redispatch interleaved on fault replay.
This module reconstructs per-request timelines from the JSONL and exports
Chrome `trace_event` JSON (chrome://tracing / Perfetto "load trace"),
giving the per-stage latency breakdown P/D-Serve (arXiv:2408.08147) argues
disaggregated serving is tuned by.
"""

from __future__ import annotations

import json
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

SPAN_STAGES = (
    "receive",
    # Admission verdict (service/admission.py): the request was turned
    # away at the front door — 429 + Retry-After, before tokenize ever
    # ran. Terminal: a shed request has no further timeline.
    "shed",
    "tokenize",
    "route",
    "dispatch",
    "redispatch",
    "resume",
    "first_token",
    "decode",
    "finish",
    "cancel",
    "error",
)

# Instance/engine-side stage vocabulary (distributed tracing,
# docs/OBSERVABILITY.md): spans emitted into per-process ring buffers by
# the serving/KV/fabric/mm mixins and the engine loop, merged with the
# master's SPAN_STAGES timeline by assemble_trace().
INSTANCE_SPAN_STAGES = (
    "admit",
    "prefill_chunk",
    "step_batch",
    "handoff_send",
    "handoff_commit",
    "kv_chunk_sent",
    "kv_chunk_landed",
    "decode_admit",
    "fabric_fetch",
    "fabric_landed",
    "encoder_batch",
    "flight_dump",
    # Master-side fabric routing decisions (cluster/prefix_fabric.py,
    # cluster/encoder_fabric.py): dispatch-time plan spans on the same
    # merged timeline.
    "fabric_plan",
    "encoder_route",
)

# The canonical vocabulary the span-stages lint pass enforces: every
# stage literal emitted anywhere in the tree must be one of these.
ALL_SPAN_STAGES = SPAN_STAGES + INSTANCE_SPAN_STAGES

# Terminal stages close a request's timeline.
TERMINAL_STAGES = frozenset(("finish", "cancel", "error", "shed"))

# Engine-loop phase vocabulary (docs/OBSERVABILITY.md "Engine step
# timeline"): what the engine THREAD is doing, exclusive and contiguous
# over every iteration of InferenceEngine._loop_owned. Each phase is a
# `jax.profiler.TraceAnnotation("xllm.engine.<phase>")` on the profiler's
# clock and one child of `xllm_engine_loop_seconds_total{phase=...}`.
# The span-stages lint pass rejects a phase literal outside this tuple.
ENGINE_PHASES = (
    "idle",          # _work.wait with nothing to do
    "housekeeping",  # imports, exports, cancels, schema-row flush
    "schedule",      # chunk cutting, admission, block allocation
    "dispatch",      # capacity pass, host inputs, the executor call
    "device_wait",   # blocking reads of a step's device results
    "emit",          # per-token bookkeeping, stop checks, callbacks
)

# Start-up phase vocabulary (docs/OBSERVABILITY.md "Start-up timeline"):
# what a process is building before it serves, exclusive like the engine's
# phases and timed by the same helper (obs/startup.py `startup_phase`).
# Each phase is a `TraceAnnotation("xllm.startup.<phase>")` and one child
# of `xllm_engine_startup_seconds{phase=...}`. The span-stages lint pass
# rejects a phase literal outside this tuple.
STARTUP_PHASES = (
    "params",    # ModelExecutor: configuration, mesh, compile cache, the parameter tree drawn or loaded, placed, quantized
    "pools",     # pool sizing (_decide_num_blocks, window blocks); the K/V, state and compressed-key pools and the token counts allocated
    "programs",  # the step programs' jit wrappers; warmup / prewarm_programs where warmup_on_start runs them
    "engine",    # InferenceEngine.__init__ outside the executor: block manager, tiers, registry; start()'s thread
    "instance",  # InstanceServer: tokenizer, HTTP plane, worker threads, until the master has registered it
)

# The executor's step programs, by the name of the function a trace and
# JAX's compile events show (`ModelExecutor._step_jit` refuses another):
# the `program` children of `xllm_engine_program_seconds_total` and
# `xllm_engine_program_builds_total` (obs/startup.py); what else a process
# compiles (the build's allocations, the weights' draw) counts as `other`.
STEP_PROGRAMS = (
    "_decode_impl",
    "_prefill_impl",
    "_mixed_impl",
    "_verify_pipe_impl",
    "_mixed_verify_impl",
    "_import_impl",  # a handed-over sequence's blocks written into the pools
)

# Leaf annotations inside the executor's dispatch entry points
# ("xllm.executor.<leaf>"): what the host does before a step launches.
EXECUTOR_LEAVES = ("host_inputs", "launch")

# Device-region vocabulary (docs/OBSERVABILITY.md "Device regions"): which
# part of a step program a compiled op belongs to. A model wraps its code
# in `region(name)`, a `jax.named_scope("xllm.<region>")` that the
# optimized HLO keeps in every op's `op_name`; obs/regions.py reads it back
# out of the compiled text and joins a profile's device ops against it.
# An op's region is the INNERMOST one of its `op_name`. One vocabulary for
# every family: the span-stages lint pass rejects a literal outside it.
DEVICE_REGIONS = (
    "step_io",      # a step program's packed inputs unpacked, its outputs packed
    "embed",        # the token rows out of the embedding table
    "norm",         # a block's pre-mixer and pre-MLP RMSNorm
    "attn_proj",    # q/k/v/o, biases, RoPE, QK-norm; MLA's down/up-projections, the absorb
    "attn",         # the paged, flash and MLA attention kernels and their plain twins
    "attn_select",  # a sparse layer's stage 1: the scores over compressed keys, the pooling to blocks, the top-k, the selected table
    "cache_write",  # the write plan, kv_write_kernel, latent rows, a sparse layer's compressed keys
    "state_mixer",  # power retention; Mamba-2's convolution, scan, update, gated output; a parallel block's ONE residual add of both branches (its attention branch is attn_proj / cache_write / attn)
    "ffn",          # dense gate/up/down, the shared experts
    "moe_route",    # router, top-k, grouping, the counts output
    "moe_experts",  # the grouped expert kernels, their twins, the combine
    "head",         # final norm + unembedding
    "sample",       # keys, penalties, sample_tokens, the logprob gather, the counts' update
    "stack_slice",  # the layer scan outside an inner region: a layer's leaves, caches and state sliced out of the stacks and written back
)
REGION_SCOPE = "xllm."

_TRACE_ANNOTATION = None
_NAMED_SCOPE = None


def annotation(name: str):
    """A `jax.profiler.TraceAnnotation(name)`: a host event on the
    profiler's own clock, recorded only while a profiler session runs.
    JAX is resolved on first use — the master and the load generators
    import `obs` and must not pull JAX in with it."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


def region(name: str):
    """A `jax.named_scope("xllm.<name>")` for one of DEVICE_REGIONS: a
    context manager, and a decorator where a function is one region.
    Metadata only: the compiled program is the same with and without it.
    JAX is resolved on first use, as in `annotation`."""
    global _NAMED_SCOPE
    if name not in DEVICE_REGIONS:
        raise ValueError(f"{name!r} is not one of DEVICE_REGIONS")
    if _NAMED_SCOPE is None:
        from jax import named_scope

        _NAMED_SCOPE = named_scope
    return _NAMED_SCOPE(REGION_SCOPE + name)


class _PhaseScope:
    __slots__ = ("_owner", "_name", "_annotate")

    def __init__(self, owner: "EnginePhases", name: str, annotate: bool):
        self._owner = owner
        self._name = name
        self._annotate = annotate

    def __enter__(self) -> None:
        self._owner._push(self._name, self._annotate)

    def __exit__(self, *exc) -> None:
        self._owner._pop()


class EnginePhases:
    """A thread's time by phase: one helper, two outputs.

    `with phases.phase("dispatch"):` opens the phase's annotation
    (`prefix` + name) and, on leaving, adds the elapsed seconds of `clock`
    to `add(phase, seconds)` (the engine hands in its labelled counter).
    `names` is the vocabulary: the engine loop's by default, a start's
    (`STARTUP_PHASES`, obs/startup.py) where given. Phases are EXCLUSIVE:
    entering one inside another suspends the outer — its interval and
    its annotation close, and both reopen when the inner one leaves — so
    the seconds of all phases sum to the time spent under the outermost
    scope and no annotation of this helper ever encloses another.
    `annotate=False` keeps the counter and opens no annotation: the
    engine uses it around the executor call, whose own leaf annotations
    (EXECUTOR_LEAVES) then stay leaves. Single-threaded by contract (the
    engine thread; one helper a thread for a start); outside any scope
    nothing is recorded."""

    def __init__(
        self,
        add: Callable[[str, float], None],
        clock: Callable[[], float] = time.monotonic,
        annotate: Optional[Callable[[str], Any]] = annotation,
        names: Tuple[str, ...] = ENGINE_PHASES,
        prefix: str = "xllm.engine.",
    ):
        self._add = add
        self._clock = clock
        self._annotate = annotate
        self._names = names
        self._prefix = prefix
        self._stack: List[Tuple[str, bool]] = []
        self._since = 0.0
        self._open: Any = None

    def phase(self, name: str, annotate: bool = True) -> _PhaseScope:
        if name not in self._names:
            raise ValueError(f"{name!r} is not one of {self._names}")
        return _PhaseScope(self, name, annotate)

    def _close(self, now: float) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if self._stack:
            self._add(self._stack[-1][0], now - self._since)

    def _begin(self, now: float) -> None:
        self._since = now
        if self._stack:
            name, annotate = self._stack[-1]
            if annotate and self._annotate is not None:
                self._open = self._annotate(self._prefix + name)
                self._open.__enter__()

    def _push(self, name: str, annotate: bool) -> None:
        now = self._clock()
        self._close(now)
        self._stack.append((name, annotate))
        self._begin(now)

    def _pop(self) -> None:
        now = self._clock()
        self._close(now)
        self._stack.pop()
        self._begin(now)


def load_spans(path: str) -> List[Dict[str, Any]]:
    """Stage records from a tracer JSONL file (non-stage records — the
    raw in/out payload traces — are skipped)."""
    records: List[Dict[str, Any]] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("type") == "stage":
                records.append(rec)
    return records


def build_timeline(
    records: Iterable[Dict[str, Any]],
) -> "OrderedDict[str, List[Dict[str, Any]]]":
    """service_request_id -> stage records in RECORDED order.

    Raises ValueError if any request's records go backwards in time — the
    tracer stamps a single process monotonic clock and appends under one
    lock, so a regression means a corrupted or hand-interleaved trace
    file. The records are deliberately NOT re-sorted: sorting would mask
    exactly the corruption this check exists to surface."""
    by_req: "OrderedDict[str, List[Dict[str, Any]]]" = OrderedDict()
    for rec in records:
        srid = rec.get("service_request_id", "")
        by_req.setdefault(srid, []).append(rec)
    for srid, recs in by_req.items():
        prev = None
        for r in recs:
            t = float(r.get("t_mono_ms", 0.0))
            if prev is not None and t < prev:
                raise ValueError(
                    f"{srid}: non-monotonic stage timestamps "
                    f"({t} after {prev})"
                )
            prev = t
    return by_req


def stage_durations_ms(
    timeline: List[Dict[str, Any]],
) -> List[Tuple[str, float]]:
    """[(stage, ms-until-next-stage)] for one request's ordered records;
    the terminal record gets duration 0."""
    out: List[Tuple[str, float]] = []
    for i, rec in enumerate(timeline):
        t = float(rec.get("t_mono_ms", 0.0))
        if i + 1 < len(timeline):
            dur = float(timeline[i + 1].get("t_mono_ms", 0.0)) - t
        else:
            dur = 0.0
        out.append((str(rec.get("stage", "")), dur))
    return out


_META_KEYS = ("type", "service_request_id", "stage", "t_mono_ms",
              "timestamp_ms")


def to_chrome_trace(records: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome trace_event JSON: per request, each stage becomes a complete
    ("X") slice lasting until the next stage; the terminal stage is an
    instant ("i"). Requests map to tids so the trace viewer stacks them as
    parallel tracks. Extra record fields ride in args."""
    by_req = build_timeline(records)
    events: List[Dict[str, Any]] = []
    for tid, (srid, recs) in enumerate(by_req.items(), start=1):
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "args": {"name": srid},
            }
        )
        for i, rec in enumerate(recs):
            ts_us = float(rec.get("t_mono_ms", 0.0)) * 1000.0
            args = {k: v for k, v in rec.items() if k not in _META_KEYS}
            stage = str(rec.get("stage", ""))
            if i + 1 < len(recs):
                dur_us = (
                    float(recs[i + 1].get("t_mono_ms", 0.0)) * 1000.0 - ts_us
                )
                events.append(
                    {
                        "name": stage,
                        "cat": "request",
                        "ph": "X",
                        "ts": ts_us,
                        "dur": max(dur_us, 0.0),
                        "pid": 1,
                        "tid": tid,
                        "args": args,
                    }
                )
            else:
                events.append(
                    {
                        "name": stage,
                        "cat": "request",
                        "ph": "i",
                        "s": "t",
                        "ts": ts_us,
                        "pid": 1,
                        "tid": tid,
                        "args": args,
                    }
                )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --------------------------------------------------------------------- #
# cross-process clock alignment + trace assembly (distributed tracing)
# --------------------------------------------------------------------- #


class ClockSync:
    """Monotonic-offset estimator for one instance clock against the
    master's, fed by samples piggybacked on heartbeats.

    Define o = master_mono - instance_mono (both in ms). Each heartbeat
    REQUEST carries the instance's send stamp: the master's receive stamp
    gives  recv - send = o + d  with one-way delay d >= 0, an UPPER bound
    of o. Each heartbeat RESPONSE carries the master's reply stamp, which
    the instance echoes on its NEXT beat together with its own receive
    stamp: reply <= recv_i + o, so  reply - recv_i  is a LOWER bound.
    The estimate is the midpoint of the intersection [max lower, min
    upper] over a bounded window; with only upper bounds (first beat) it
    degrades to min-upper, which overestimates o by the minimum one-way
    delay — mapped instance events then land slightly late, never before
    the master RPC that caused them."""

    WINDOW = 64

    def __init__(self) -> None:
        self._uppers: List[float] = []
        self._lowers: List[float] = []

    def sample_upper(self, bound_ms: float) -> None:
        self._uppers.append(float(bound_ms))
        del self._uppers[: -self.WINDOW]

    def sample_lower(self, bound_ms: float) -> None:
        self._lowers.append(float(bound_ms))
        del self._lowers[: -self.WINDOW]

    @property
    def samples(self) -> int:
        return len(self._uppers) + len(self._lowers)

    def offset_ms(self) -> float:
        """Best current estimate of o = master_mono - instance_mono."""
        upper = min(self._uppers) if self._uppers else None
        lower = max(self._lowers) if self._lowers else None
        if upper is not None and lower is not None and lower <= upper:
            return (upper + lower) / 2.0
        if upper is not None:
            return upper
        if lower is not None:
            return lower
        return 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "offset_ms": round(self.offset_ms(), 3),
            "samples": self.samples,
            "upper_ms": round(min(self._uppers), 3) if self._uppers else None,
            "lower_ms": round(max(self._lowers), 3) if self._lowers else None,
        }


def assemble_trace(
    master_process: str,
    master_spans: Iterable[Dict[str, Any]],
    participants: Iterable[Tuple[str, Iterable[Dict[str, Any]], float]],
) -> List[Dict[str, Any]]:
    """ONE merged per-request timeline from every participant's spans.

    `participants` is (process_name, spans, offset_ms) per instance, with
    offset_ms = master_mono - instance_mono (ClockSync.offset_ms): each
    instance record's t_mono_ms is shifted into the MASTER clock domain
    so inter-process durations subtract exactly. Records are returned
    sorted on the aligned clock with a `process` field stamped on each;
    ties keep master-before-instance order (the RPC that caused an
    instance span sorts ahead of it)."""
    merged: List[Dict[str, Any]] = []
    for rec in master_spans:
        r = dict(rec)
        r.setdefault("process", master_process)
        merged.append(r)
    for name, spans, off in participants:
        for rec in spans:
            r = dict(rec)
            r["process"] = name
            r["t_mono_ms"] = float(r.get("t_mono_ms", 0.0)) + float(off)
            merged.append(r)
    merged.sort(
        key=lambda r: (
            float(r.get("t_mono_ms", 0.0)),
            0 if r.get("process") == master_process else 1,
        )
    )
    return merged


_TRACE_META_KEYS = _META_KEYS + ("process",)


def trace_to_chrome(merged: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome trace_event JSON for one ASSEMBLED multi-process trace
    (assemble_trace output): one pid track per process so Perfetto stacks
    master/prefill/decode/encoder timelines in parallel, each span a
    complete ("X") slice lasting until that process's next span (the
    process's last span is an instant)."""
    procs: "OrderedDict[str, List[Dict[str, Any]]]" = OrderedDict()
    for rec in merged:
        procs.setdefault(str(rec.get("process", "")), []).append(rec)
    events: List[Dict[str, Any]] = []
    for pid, (proc, recs) in enumerate(procs.items(), start=1):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": proc},
        })
        for i, rec in enumerate(recs):
            ts_us = float(rec.get("t_mono_ms", 0.0)) * 1000.0
            args = {
                k: v for k, v in rec.items() if k not in _TRACE_META_KEYS
            }
            ev: Dict[str, Any] = {
                "name": str(rec.get("stage", "")),
                "cat": "trace",
                "pid": pid,
                "tid": 1,
                "ts": ts_us,
                "args": args,
            }
            if i + 1 < len(recs):
                nxt = float(recs[i + 1].get("t_mono_ms", 0.0)) * 1000.0
                ev["ph"] = "X"
                ev["dur"] = max(nxt - ts_us, 0.0)
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# p99 blame attribution: stage -> (start anchor, end anchor). Each anchor
# names the FIRST record with that stage in the aligned timeline; missing
# anchors void the stage (blamed 0) rather than guessing.
_BLAME_EDGES = (
    ("queue", "receive", "dispatch"),
    ("prefill", "admit", "handoff_send"),
    ("handoff", "handoff_send", "decode_admit"),
    ("decode", "decode_admit", "finish"),
)


def blame_stages(merged: List[Dict[str, Any]]) -> Dict[str, float]:
    """Per-stage latency blame for one assembled trace: queue vs prefill
    vs handoff vs decode vs host_gap (ms). host_gap is everything the
    named edges don't cover — RPC transit, serving-thread scheduling,
    push batching — so the five always sum to the end-to-end span.
    Colocated (non-PD) traces have no handoff/decode_admit anchors:
    prefill falls back to dispatch->first_token, decode to
    first_token->finish, and handoff blames 0. (A PD trace must NOT use
    the first_token anchor for decode: the prefill side pushes the first
    token BEFORE the handoff, so that edge would double-count the whole
    handoff window and the blame table could never point at it.)"""
    first: Dict[str, float] = {}
    for rec in merged:
        stage = str(rec.get("stage", ""))
        if stage and stage not in first:
            first[stage] = float(rec.get("t_mono_ms", 0.0))
    t_start = min(first.values()) if first else 0.0
    terminal = [first[s] for s in TERMINAL_STAGES if s in first]
    t_end = max(terminal) if terminal else (
        max(first.values()) if first else 0.0
    )
    blame: Dict[str, float] = {}
    covered = 0.0
    for name, a, b in _BLAME_EDGES:
        if a in first and b in first and first[b] >= first[a]:
            dur = first[b] - first[a]
        elif name == "prefill" and "dispatch" in first and "first_token" in first:
            dur = max(first["first_token"] - first["dispatch"], 0.0)
        elif name == "decode" and "first_token" in first and "finish" in first:
            dur = max(first["finish"] - first["first_token"], 0.0)
        else:
            dur = 0.0
        blame[name] = round(dur, 3)
        covered += dur
    blame["host_gap"] = round(max((t_end - t_start) - covered, 0.0), 3)
    blame["total"] = round(max(t_end - t_start, 0.0), 3)
    return blame
