"""Cluster-wide observability: metrics registry + request-lifecycle spans.

The reference service has no aggregated metrics of its own — its /metrics
is a per-instance passthrough (http_service/service.cpp:452-457) and its
only tracing is a mutex-guarded JSONL appender. This package supplies the
layer P/D-Serve (arXiv:2408.08147) and the xLLM technical report
(arXiv:2510.14686) tune disaggregated fleets with: a lock-cheap
Counter/Gauge/Histogram registry with one Prometheus text renderer
(`metrics`), and structured per-request stage spans exportable as Chrome
trace_event JSON (`spans`).
"""

from xllm_service_tpu.obs.metrics import (
    BATCH_BUCKETS,
    LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    absorb_exposition,
    parse_exposition,
    render_families,
)
from xllm_service_tpu.obs.flight import FlightRecorder, SpanRing
from xllm_service_tpu.obs.spans import (
    ALL_SPAN_STAGES,
    DEVICE_REGIONS,
    ENGINE_PHASES,
    EXECUTOR_LEAVES,
    INSTANCE_SPAN_STAGES,
    SPAN_STAGES,
    STARTUP_PHASES,
    STEP_PROGRAMS,
    ClockSync,
    EnginePhases,
    annotation,
    assemble_trace,
    blame_stages,
    build_timeline,
    load_spans,
    region,
    to_chrome_trace,
    trace_to_chrome,
)
from xllm_service_tpu.obs.startup import startup_phase

__all__ = [
    "BATCH_BUCKETS",
    "LATENCY_BUCKETS_MS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "absorb_exposition",
    "parse_exposition",
    "render_families",
    "ALL_SPAN_STAGES",
    "DEVICE_REGIONS",
    "ENGINE_PHASES",
    "EXECUTOR_LEAVES",
    "INSTANCE_SPAN_STAGES",
    "SPAN_STAGES",
    "STARTUP_PHASES",
    "STEP_PROGRAMS",
    "ClockSync",
    "EnginePhases",
    "annotation",
    "region",
    "startup_phase",
    "FlightRecorder",
    "SpanRing",
    "assemble_trace",
    "blame_stages",
    "build_timeline",
    "load_spans",
    "to_chrome_trace",
    "trace_to_chrome",
]
