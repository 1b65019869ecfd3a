"""Span-stages pass: distributed-tracing vocabulary + plane coverage.

Five layers, mirroring the fault-points registry idiom
(docs/OBSERVABILITY.md "Distributed tracing", "Engine step timeline"):

* VOCABULARY — scan the package plus the bench entry points for every
  literal stage emitted through a tracing surface
  (`RequestTracer.stage`, `SpanRing.emit`, `InstanceServer._span`,
  `engine.span_hook`, the fabric `_span_hook`s) and require it to be a
  member of the canonical vocabulary (`obs.spans.ALL_SPAN_STAGES`). A
  stage outside the vocabulary renders as an orphan track in the merged
  Perfetto timeline and silently escapes `blame_stages`' edges.

* ENGINE PHASES — every literal handed to `EnginePhases.phase(...)`
  (the engine loop's step timeline) must be in
  `obs.spans.ENGINE_PHASES`, and every executor leaf annotation
  (`_leaf(...)`) in `obs.spans.EXECUTOR_LEAVES`: a phase outside the
  vocabulary has no `xllm_engine_loop_seconds_total` child, and the
  benchmark's readers key on the names.

* START-UP PHASES — every literal handed to `startup_phase(...)`
  (obs/startup.py, docs/OBSERVABILITY.md "Start-up timeline") must be in
  `obs.spans.STARTUP_PHASES`: the helper refuses another name only when
  the line runs, and a start is the one path a test may never take.

* DEVICE REGIONS — every literal handed to `region(...)` (a model's
  `jax.named_scope`, docs/OBSERVABILITY.md "Device regions") must be in
  `obs.spans.DEVICE_REGIONS`: `region()` refuses another name only when
  the code is traced, and a branch no test traces would fail on the chip.

* TRACE PLANES — a registry of RPC-client call sites (one row per
  cross-process plane: dispatch, PD handoff commit, KV stream OPEN,
  fabric fetch, encoder forward, mm stream open) each of which must
  still forward the request's trace context. A refactor that drops the
  `trace` field from one plane breaks that plane's spans out of the
  assembled timeline even though nothing crashes — exactly the silent
  rot a registry row catches.
"""

from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

from xllm_service_tpu.analysis.core import Finding, LintPass, Project

# A stage emission: the surface call with a LITERAL second argument.
# Non-literal stages (e.g. the scheduler's `terminal` variable, whose
# values come from TERMINAL_STAGES) are the vocabulary's job to
# constrain at the definition site, not here.
EMIT_RE = re.compile(
    r"(?:\.stage|\.emit|\bspan_hook|\b_span|\b_span_hook)"
    r"\(\s*[^,()]*,\s*[\r\n ]*[\"']([a-z_]+)[\"']"
)

# An engine-loop phase scope / an executor leaf annotation with a LITERAL
# name: `phase("dispatch")`, `self._phases.phase("idle", ...)`,
# `_leaf("launch")`.
PHASE_RE = re.compile(r"(?<![A-Za-z0-9_])phase\(\s*[\"']([a-z_]+)[\"']")
LEAF_RE = re.compile(r"(?<![A-Za-z0-9_])_leaf\(\s*[\"']([a-z_]+)[\"']")
# A start-up phase: `startup_phase("pools")`, `@startup_phase("engine")`.
STARTUP_RE = re.compile(
    r"(?<![A-Za-z0-9_])startup_phase\(\s*[\"']([a-z_]+)[\"']"
)
# A device region: `region("ffn")`, `@obs_spans.region("sample")`.
REGION_RE = re.compile(r"(?<![A-Za-z0-9_])region\(\s*[\"']([a-z_]+)[\"']")

# Contractual trace-context forwarding sites, one row per RPC plane:
# (repo-relative file, verbatim needle, plane). The needle is the exact
# source text that puts the trace context on that plane's wire.
TRACE_PLANES: Tuple[Tuple[str, str, str], ...] = (
    ("xllm_service_tpu/api/master.py", "trace=trace_ctx",
     "master dispatch -> prefill/decode (augment_forwarded_request)"),
    ("xllm_service_tpu/api/master.py", '"trace": trace_ctx',
     "master dispatch -> legacy /encode body"),
    ("xllm_service_tpu/api/master.py", '"trace": TraceContext(',
     "master dispatch -> encoder-fabric /encode body"),
    ("xllm_service_tpu/api/instance_serving.py",
     'trace=body.get("trace")',
     "forwarded admission -> KV stream session + fabric prefetch"),
    ("xllm_service_tpu/api/instance_kv.py",
     'header["trace"] = self.trace',
     "KV stream session OPEN -> decode peer"),
    ("xllm_service_tpu/api/instance_kv.py",
     'extra["trace"] = body["trace"]',
     "PD handoff commit -> decode peer"),
    ("xllm_service_tpu/api/instance_fabric.py",
     'fetch_header["trace"] = trace',
     "prefix-fabric /kv/fetch frame -> holder"),
    ("xllm_service_tpu/api/instance_mm.py",
     'mm_open["trace"] = body["trace"]',
     "encoder /mm/open stream session -> prefill peer"),
)


class SpanStagesPass(LintPass):
    id = "span-stages"
    title = "trace-span stage vocabulary + trace-plane forwarding registry"

    def __init__(
        self,
        vocab: Optional[Sequence[str]] = None,
        planes: Optional[Sequence[Tuple[str, str, str]]] = None,
        phases: Optional[Sequence[str]] = None,
        leaves: Optional[Sequence[str]] = None,
        regions: Optional[Sequence[str]] = None,
        startup: Optional[Sequence[str]] = None,
    ):
        # Injectable for fixture tests; the repo run uses the canonical
        # vocabularies and the plane registry above.
        self._vocab = vocab
        self.planes = TRACE_PLANES if planes is None else tuple(planes)
        self._phases = phases
        self._leaves = leaves
        self._regions = regions
        self._startup = startup

    @property
    def vocab(self) -> frozenset:
        if self._vocab is None:
            from xllm_service_tpu.obs.spans import ALL_SPAN_STAGES

            self._vocab = ALL_SPAN_STAGES
        return frozenset(self._vocab)

    @property
    def phase_vocabs(self) -> tuple:
        """(pattern, names, what, vocabulary's name, what a name outside
        it costs) per name family."""
        from xllm_service_tpu.obs import spans

        unread = "it would have no counter child and no reader"
        phases, leaves, regions = self._phases, self._leaves, self._regions
        startup = self._startup
        if regions is None:
            regions = spans.DEVICE_REGIONS
        if startup is None:
            startup = spans.STARTUP_PHASES
        if phases is None:
            phases = spans.ENGINE_PHASES
        if leaves is None:
            leaves = spans.EXECUTOR_LEAVES
        return (
            (PHASE_RE, frozenset(phases), "engine phase", "ENGINE_PHASES", unread),
            (LEAF_RE, frozenset(leaves), "executor leaf", "EXECUTOR_LEAVES", unread),
            (STARTUP_RE, frozenset(startup), "start-up phase", "STARTUP_PHASES",
             "startup_phase() refuses it when the line runs"),
            (REGION_RE, frozenset(regions), "device region", "DEVICE_REGIONS",
             "region() refuses it when the code is traced"),
        )

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        vocab = self.vocab
        for src in project.all_lintable():
            for pattern, names, what, where, cost in self.phase_vocabs:
                for m in pattern.finditer(src.text):
                    if m.group(1) in names:
                        continue
                    line = src.text.count("\n", 0, m.start()) + 1
                    findings.append(Finding(
                        self.id, src.rel, line,
                        f"{what} {m.group(1)!r} is not in obs.spans.{where}"
                        f" — {cost}",
                    ))
            for m in EMIT_RE.finditer(src.text):
                stage = m.group(1)
                if stage in vocab:
                    continue
                line = src.text.count("\n", 0, m.start()) + 1
                findings.append(Finding(
                    self.id, src.rel, line,
                    f"span stage {stage!r} is not in the canonical "
                    f"vocabulary (obs.spans.ALL_SPAN_STAGES) — an "
                    f"off-vocabulary stage is invisible to "
                    f"build_timeline/blame_stages",
                ))
        for rel, needle, plane in self.planes:
            src = project.find(rel)
            if src is None:
                findings.append(Finding(
                    self.id, rel, 1,
                    f"trace-plane registry names {rel} ({plane}) but the "
                    f"file is gone — update the registry row",
                ))
                continue
            if needle not in src.text:
                findings.append(Finding(
                    self.id, rel, 1,
                    f"trace plane {plane!r} no longer forwards trace "
                    f"context (needle {needle!r} missing) — spans from "
                    f"that process drop out of the assembled timeline",
                ))
        return findings
