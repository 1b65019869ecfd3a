"""Observability subsystem tests (ISSUE 2 acceptance):

  * obs.metrics unit behavior — registry, render grouping, histogram
    buckets/percentiles, label escaping;
  * the strict tests/prom_parser.py validator and its regression guards
    (duplicate # TYPE lines, ungrouped series — the master.py hazard);
  * RequestTracer hardening — size rotation, drop counter, stage records;
  * obs.spans — timeline reconstruction + Chrome trace export;
  * a 2-instance fake-engine cluster: GET /metrics returns a parseable
    exposition carrying master-local series, per-instance engine series
    (instance="..."), and TTFT/TPOT/queue-delay histogram buckets; a
    traced request's span file reconstructs the full stage timeline with
    monotonic timestamps;
  * scripts/check_metric_names.py lint (names, _total suffix, histogram
    render series).
"""

import http.client
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from prom_parser import PromFormatError, parse_metrics  # noqa: E402

from xllm_service_tpu.api import FakeEngine, Master
from xllm_service_tpu.api.instance import InstanceServer
from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
from xllm_service_tpu.coordination import MemoryStore
from xllm_service_tpu.obs import (
    MetricsRegistry,
    build_timeline,
    load_spans,
    to_chrome_trace,
)
from xllm_service_tpu.obs.spans import stage_durations_ms
from xllm_service_tpu.service.request import RequestTracer


def wait_until(pred, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def http_get_text(addr, path, timeout=10.0):
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read().decode()
    conn.close()
    return resp.status, data


def http_post(addr, path, body, timeout=30.0):
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    conn.request(
        "POST", path, body=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, (json.loads(data) if data else {})


# --------------------------------------------------------------------- #
# metrics registry units
# --------------------------------------------------------------------- #


class TestRegistry:
    def test_counter_gauge_render_grouped(self):
        reg = MetricsRegistry()
        c = reg.counter("xllm_t_reqs_total", "requests", labelnames=("kind",))
        c.labels(kind="chat").inc()
        c.labels(kind="chat").inc(2)
        c.labels(kind="completion").inc()
        reg.gauge("xllm_t_depth", "queue").set(7)
        text = reg.render()
        fams = parse_metrics(text)
        assert fams["xllm_t_reqs_total"].kind == "counter"
        assert fams["xllm_t_reqs_total"].values(kind="chat") == [3]
        assert fams["xllm_t_reqs_total"].values(kind="completion") == [1]
        assert fams["xllm_t_depth"].values() == [7]
        assert text.count("# TYPE xllm_t_reqs_total") == 1

    def test_counter_requires_total_suffix(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("xllm_t_requests", "missing suffix")
        with pytest.raises(ValueError):
            reg.counter("bad_prefix_total", "wrong namespace")

    def test_create_or_get_and_kind_conflict(self):
        reg = MetricsRegistry()
        a = reg.counter("xllm_t_a_total")
        assert reg.counter("xllm_t_a_total") is a
        with pytest.raises(ValueError):
            reg.gauge("xllm_t_a_total")

    def test_function_backed_metrics(self):
        reg = MetricsRegistry()
        src = {"v": 5}
        reg.gauge("xllm_t_fn_depth").set_function(lambda: src["v"])
        assert 'xllm_t_fn_depth 5' in reg.render()
        src["v"] = 9
        assert 'xllm_t_fn_depth 9' in reg.render()

    def test_label_escaping(self):
        reg = MetricsRegistry()
        g = reg.gauge("xllm_t_esc", labelnames=("who",))
        g.labels(who='a"b\\c\nd').set(1)
        text = reg.render()
        fams = parse_metrics(text)
        assert fams["xllm_t_esc"].samples[0][1]["who"] == 'a\\"b\\\\c\\nd'

    def test_histogram_buckets_and_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("xllm_t_lat_ms", buckets=(1, 10, 100))
        for v in (0.5, 5, 5, 50, 500):
            h.observe(v)
        fams = parse_metrics(reg.render())
        fam = fams["xllm_t_lat_ms"]
        by_le = {
            labels["le"]: v
            for name, labels, v in fam.samples
            if name.endswith("_bucket")
        }
        assert by_le == {"1": 1, "10": 3, "100": 4, "+Inf": 5}
        # percentile: p50 of 5 samples lands in the (1, 10] bucket
        p50 = h.percentile(50)
        assert 1 <= p50 <= 10
        # +Inf clamps to the largest finite bound
        assert h.percentile(99) == 100

    def test_absorb_does_not_double_escape(self):
        from collections import OrderedDict

        from xllm_service_tpu.obs import absorb_exposition, render_families

        reg = MetricsRegistry()
        g = reg.gauge("xllm_t_path", labelnames=("dir",))
        g.labels(dir='C:\\tmp "x"').set(1)
        text = reg.render()
        # two aggregation hops with an extra label each time
        fams = OrderedDict()
        absorb_exposition(fams, text, extra_labels={"instance": "a"})
        hop1 = render_families(fams)
        fams2 = OrderedDict()
        absorb_exposition(fams2, hop1, extra_labels={"plane": "p"})
        hop2 = render_families(fams2)
        # the original escaped value survives both hops unchanged
        assert hop1.count('dir="C:\\\\tmp \\"x\\""') == 1
        assert hop2.count('dir="C:\\\\tmp \\"x\\""') == 1
        assert parse_metrics(hop2)["xllm_t_path"].samples[0][1]["dir"] == (
            'C:\\\\tmp \\"x\\"'
        )

    def test_histogram_reserved_suffixes_rejected(self):
        reg = MetricsRegistry()
        for bad in ("xllm_t_x_bucket", "xllm_t_x_sum", "xllm_t_x_count",
                    "xllm_t_x_total"):
            with pytest.raises(ValueError):
                reg.histogram(bad)


class TestPromParserGuards:
    """Regression guards for the hazards noted in master.py: a duplicate
    # TYPE line or an ungrouped series fails a strict scrape."""

    def test_duplicate_type_rejected(self):
        text = (
            "# TYPE xllm_t_a gauge\nxllm_t_a 1\n"
            "# TYPE xllm_t_a gauge\nxllm_t_a 2\n"
        )
        with pytest.raises(PromFormatError, match="duplicate"):
            parse_metrics(text)

    def test_ungrouped_series_rejected(self):
        text = (
            "# TYPE xllm_t_a gauge\n"
            'xllm_t_a{plane="http"} 1\n'
            "# TYPE xllm_t_b gauge\n"
            "xllm_t_b 1\n"
            'xllm_t_a{plane="rpc"} 2\n'
        )
        with pytest.raises(PromFormatError, match="ungrouped"):
            parse_metrics(text)

    def test_untyped_series_rejected(self):
        with pytest.raises(PromFormatError, match="no TYPE"):
            parse_metrics("xllm_t_stray 1\n")

    def test_histogram_structure_enforced(self):
        # missing +Inf bucket
        text = (
            "# TYPE xllm_t_h histogram\n"
            'xllm_t_h_bucket{le="1"} 1\n'
            "xllm_t_h_sum 1\n"
            "xllm_t_h_count 1\n"
        )
        with pytest.raises(PromFormatError, match=r"\+Inf"):
            parse_metrics(text)


# --------------------------------------------------------------------- #
# tracer hardening + spans
# --------------------------------------------------------------------- #


class TestTracer:
    def test_rotation_bounds_file_size(self, tmp_path):
        tracer = RequestTracer(str(tmp_path), enabled=True, max_bytes=2000)
        for i in range(100):
            tracer.record(f"r{i}", "in", {"pad": "x" * 50})
        tracer.close()
        main = tmp_path / "trace.jsonl"
        rotated = tmp_path / "trace.jsonl.1"
        assert rotated.exists()
        assert main.stat().st_size < 4000
        assert tracer.dropped == 0

    def test_write_failure_counts_drops(self, tmp_path):
        tracer = RequestTracer(str(tmp_path), enabled=True)
        tracer._fh.close()  # simulate the disk going away
        tracer.record("r1", "in", {})
        tracer.stage("r1", "finish")
        assert tracer.dropped == 2
        tracer.close()

    def test_disabled_tracer_is_inert(self, tmp_path):
        tracer = RequestTracer(str(tmp_path / "sub"), enabled=False)
        tracer.record("r1", "in", {})
        tracer.stage("r1", "receive")
        assert not (tmp_path / "sub").exists()
        assert tracer.dropped == 0

    def test_stage_records_roundtrip(self, tmp_path):
        tracer = RequestTracer(str(tmp_path), enabled=True)
        tracer.stage("req-1", "receive", kind="chat")
        tracer.record("req-1", "out", {"not": "a stage"})
        tracer.stage("req-1", "tokenize", prompt_tokens=4)
        tracer.stage("req-1", "finish", outcome="ok")
        tracer.close()
        recs = load_spans(str(tmp_path / "trace.jsonl"))
        assert [r["stage"] for r in recs] == ["receive", "tokenize", "finish"]
        assert recs[1]["prompt_tokens"] == 4
        timeline = build_timeline(recs)["req-1"]
        durs = stage_durations_ms(timeline)
        assert [s for s, _ in durs] == ["receive", "tokenize", "finish"]
        assert all(d >= 0 for _, d in durs)

    def test_chrome_trace_export(self):
        recs = [
            {"type": "stage", "service_request_id": "a", "stage": "receive",
             "t_mono_ms": 10.0},
            {"type": "stage", "service_request_id": "a", "stage": "first_token",
             "t_mono_ms": 25.0, "ttft_ms": 15.0},
            {"type": "stage", "service_request_id": "a", "stage": "finish",
             "t_mono_ms": 40.0},
            {"type": "stage", "service_request_id": "b", "stage": "receive",
             "t_mono_ms": 12.0},
            {"type": "stage", "service_request_id": "b", "stage": "finish",
             "t_mono_ms": 13.0},
        ]
        trace = to_chrome_trace(recs)
        evs = trace["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        assert {e["name"] for e in xs} == {"receive", "first_token"}
        assert {e["name"] for e in instants} == {"finish"}
        recv_a = next(e for e in xs if e["name"] == "receive" and e["tid"] == 1)
        assert recv_a["ts"] == 10_000.0 and recv_a["dur"] == 15_000.0
        # distinct requests land on distinct tracks
        assert len({e["tid"] for e in evs}) >= 2

    def test_non_monotonic_rejected(self):
        recs = [
            {"type": "stage", "service_request_id": "a", "stage": "receive",
             "t_mono_ms": 10.0},
            {"type": "stage", "service_request_id": "a", "stage": "finish",
             "t_mono_ms": 5.0},
        ]
        with pytest.raises(ValueError, match="non-monotonic"):
            build_timeline(recs)


# --------------------------------------------------------------------- #
# cluster e2e: aggregated /metrics + span file
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def obs_cluster(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("obs-trace"))
    store = MemoryStore(clock=lambda: 0.0)
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.2, master_lease_ttl_s=1.0,
        num_ordered_output_streams=8, block_size=16,
        enable_request_trace=True, trace_dir=trace_dir,
    )
    master = Master(cfg, store=store)
    master.start()

    def make_instance(name, itype):
        ecfg = EngineConfig(
            model="fake-echo", instance_name=name, instance_type=itype,
            block_size=16,
        )
        srv = InstanceServer(
            ecfg, master_rpc_addr=master.rpc_address,
            heartbeat_interval_s=0.2, engine=FakeEngine(),
        )
        srv.start()
        return srv

    i0 = make_instance("obs0", "PREFILL")
    i1 = make_instance("obs1", "DECODE")
    assert wait_until(
        lambda: master.scheduler.instance_mgr.counts() == (1, 1, 0)
    )
    yield master, i0, i1, trace_dir
    i0.stop()
    i1.stop()
    master.stop()
    store.close()


def _run_request(master, prompt="observability", max_tokens=8):
    code, body = http_post(
        master.http_address, "/v1/completions",
        {"model": "fake-echo", "prompt": prompt, "max_tokens": max_tokens},
    )
    assert code == 200, body
    return body


class TestClusterMetrics:
    def test_aggregate_parses_and_carries_all_layers(self, obs_cluster):
        master = obs_cluster[0]
        _run_request(master, prompt="metrics-aggregate")
        assert wait_until(
            lambda: "obs0" in master.scheduler.instance_mgr.get_load_metrics()
        )
        # terminal bookkeeping runs on the lane right after the response
        # body is written — wait for it before asserting the counters
        assert wait_until(lambda: master.scheduler.num_inflight == 0)
        code, text = http_get_text(master.http_address, "/metrics")
        assert code == 200
        fams = parse_metrics(text)  # strict: raises on format hazards

        # master-local service series
        assert fams["xllm_service_inflight_requests"].kind == "gauge"
        assert sum(fams["xllm_service_requests_total"].values()) >= 1
        assert sum(fams["xllm_service_finished_total"].values(outcome="ok")) >= 1

        # cluster shape
        assert fams["xllm_cluster_instances"].values(role="prefill") == [1]
        assert fams["xllm_cluster_instances"].values(role="decode") == [1]

        # latency histograms with buckets (acceptance: TTFT/TPOT/queue
        # delay all present as histogram families)
        for name in ("xllm_service_ttft_ms", "xllm_service_tpot_ms",
                     "xllm_service_queue_delay_ms", "xllm_service_e2e_ms"):
            fam = fams[name]
            assert fam.kind == "histogram"
        assert sum(
            1 for n, _l, _v in fams["xllm_service_ttft_ms"].samples
            if n == "xllm_service_ttft_ms_bucket"
        ) >= 16
        # the echoed request actually landed in the distributions
        ttft_counts = [
            v for n, _l, v in fams["xllm_service_ttft_ms"].samples
            if n == "xllm_service_ttft_ms_count"
        ]
        assert ttft_counts and ttft_counts[0] >= 1

        # per-instance engine series scraped + labelled
        for inst in ("obs0", "obs1"):
            assert fams["xllm_engine_waiting_requests"].values(
                instance=inst
            ), f"no engine series for {inst}"
        # instance-manager view keeps its own per-instance gauges
        assert fams["xllm_instance_waiting_requests"].values(instance="obs0")

        # HTTP planes grouped under single TYPE lines
        assert len(fams["xllm_http_requests_total"].values(plane="http")) == 1
        assert len(fams["xllm_http_requests_total"].values(plane="rpc")) == 1
        # event backend: per-plane loop-lag histogram rode the merge
        assert fams["xllm_http_loop_lag_ms"].kind == "histogram"
        assert fams["xllm_http_loop_lag_ms"].values(plane="http")

    def test_instance_metrics_parse_standalone(self, obs_cluster):
        master, i0 = obs_cluster[0], obs_cluster[1]
        code, text = http_get_text(i0.address, "/metrics")
        assert code == 200
        fams = parse_metrics(text)
        assert fams["xllm_engine_waiting_requests"].kind == "gauge"
        assert fams["xllm_engine_kv_cache_usage"].kind == "gauge"

    def test_passthrough_still_verbatim(self, obs_cluster):
        master = obs_cluster[0]
        code, text = http_get_text(
            master.http_address, "/metrics?instance=obs0"
        )
        assert code == 200
        fams = parse_metrics(text)
        # passthrough = the instance's own view: no instance label injected
        assert fams["xllm_engine_waiting_requests"].samples[0][1] == {}

    def test_scrape_failure_skips_instance(self, obs_cluster):
        master = obs_cluster[0]
        mgr = master.scheduler.instance_mgr
        meta = mgr.get_instance("obs0")
        orig = meta.http_address
        meta.http_address = "127.0.0.1:1"  # nothing listens there
        try:
            before = master._m_scrape_failures.get()
            code, text = http_get_text(master.http_address, "/metrics")
            assert code == 200
            fams = parse_metrics(text)  # still a clean exposition
            assert not fams["xllm_engine_waiting_requests"].values(
                instance="obs0"
            )
            assert master._m_scrape_failures.get() > before
        finally:
            meta.http_address = orig


class TestRequestSpans:
    def test_traced_request_reconstructs_timeline(self, obs_cluster):
        master, _i0, _i1, trace_dir = obs_cluster
        body = _run_request(master, prompt="span-me", max_tokens=6)
        srid = body["id"]
        master.scheduler.tracer.flush()
        path = os.path.join(trace_dir, "trace.jsonl")
        assert wait_until(
            lambda: any(
                r["service_request_id"] == srid
                and r["stage"] in ("finish", "cancel")
                for r in load_spans(path)
            )
        )
        recs = [
            r for r in load_spans(path) if r["service_request_id"] == srid
        ]
        timeline = build_timeline(recs)[srid]  # raises on non-monotonic
        stages = [r["stage"] for r in timeline]
        # full lifecycle present, in causal order
        for earlier, later in (
            ("receive", "tokenize"), ("tokenize", "route"),
            ("route", "dispatch"), ("dispatch", "first_token"),
            ("first_token", "finish"),
        ):
            assert stages.index(earlier) < stages.index(later), stages
        # decode ticks sit between first_token and finish
        if "decode" in stages:
            assert (
                stages.index("first_token")
                < stages.index("decode")
                < stages.index("finish")
            )
        ts = [r["t_mono_ms"] for r in timeline]
        assert ts == sorted(ts)
        # stage fields carry the reconstruction payload
        route_rec = next(r for r in timeline if r["stage"] == "route")
        assert route_rec["prefill"] in ("obs0", "obs1")
        fin = next(r for r in timeline if r["stage"] == "finish")
        assert fin["generated_tokens"] >= 1

        trace = to_chrome_trace(recs)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"receive", "tokenize", "route", "dispatch",
                "first_token"} <= names


class TestThreadedPlaneStats:
    def test_threaded_stats_and_metrics(self):
        store = MemoryStore(clock=lambda: 0.0)
        cfg = ServiceConfig(
            host="127.0.0.1", http_port=0, rpc_port=0,
            heartbeat_interval_s=0.5, http_backend="threaded",
            num_ordered_output_streams=4,
        )
        master = Master(cfg, store=store)
        master.start()
        try:
            code, _text = http_get_text(master.http_address, "/hello")
            assert code == 200
            st = master.http.stats()
            assert st["backend"] == "threaded"
            assert st["requests_total"] >= 1
            assert st["accepted_total"] >= 1
            code, text = http_get_text(master.http_address, "/metrics")
            assert code == 200
            fams = parse_metrics(text)
            # threaded planes are no longer silently omitted
            assert fams["xllm_http_requests_total"].values(plane="http")
            assert len(
                fams["xllm_http_accepted_total"].values(plane="rpc")
            ) == 1
        finally:
            master.stop()
            store.close()


class TestStartupPhases:
    """obs/startup.py `startup_phase` (docs/OBSERVABILITY.md "Start-up
    timeline"): the engine loop's scope helper over STARTUP_PHASES."""

    class TickClock:
        def __init__(self):
            self.t = 0

        def __call__(self):
            self.t += 1
            return float(self.t)

    def test_scopes_are_exclusive_and_sum_to_the_outermost(self):
        from xllm_service_tpu.obs import STARTUP_PHASES
        from xllm_service_tpu.obs.startup import StartupTimeline

        clock = self.TickClock()
        tl = StartupTimeline(clock=clock)

        @tl.phase("engine")
        def build_engine():
            with tl.phase("params"):
                with tl.phase("pools"):
                    pass
                with tl.phase("programs"):
                    pass
            return "built"

        with tl.phase("instance"):
            opened = clock.t  # the outermost scope's own reading
            assert build_engine() == "built"
        assert clock.t == 10  # every push and pop read the clock once
        assert tl._thread_phases()._stack == []
        # exclusive: an inner scope suspends the outer, so the phases' own
        # seconds add up to the wall time under the outermost scope
        assert sum(tl.phase_seconds.values()) == clock.t - opened
        assert all(tl.phase_seconds[p] > 0 for p in STARTUP_PHASES)
        assert 1 <= len(STARTUP_PHASES) <= 6

    def test_a_literal_outside_the_vocabulary_is_refused(self):
        from xllm_service_tpu.obs import startup_phase

        with pytest.raises(ValueError):
            startup_phase("warmup")

    def test_each_thread_has_its_own_scopes(self):
        import threading

        from xllm_service_tpu.obs.startup import StartupTimeline

        tl = StartupTimeline()
        inside, release = threading.Event(), threading.Event()

        def other():
            with tl.phase("pools"):
                inside.set()
                assert release.wait(10)

        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with tl.phase("params"):
            assert [n for n, _ in tl._thread_phases()._stack] == ["params"]
        release.set()
        t.join(10)
        assert not t.is_alive()
        assert tl.phase_seconds["params"] > 0 and tl.phase_seconds["pools"] > 0

    def test_an_instance_over_a_fake_engine_counts_its_start(self):
        from xllm_service_tpu.obs.startup import TIMELINE

        before = TIMELINE.phase_seconds["instance"]
        inst = InstanceServer(
            EngineConfig(model="fake", instance_name="startup0"),
            engine=FakeEngine(),
        )
        inst.start()
        inst.stop()
        assert TIMELINE.phase_seconds["instance"] > before


class TestMetricNameLint:
    def test_lint_clean(self, capsys):
        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts"),
        )
        import check_metric_names

        assert check_metric_names.main() == 0
