"""Deterministic fault injection (common/faults.py), the control-plane
retry layer (http_utils RetryBudget / post_json_retrying), and the
instance health circuit breaker (cluster/instance_mgr.py).

Covered injection points (scripts/check_fault_points.py asserts every
point is referenced here or in the other fault suites):
post_json.send, post_json.recv, heartbeat.send, fake_engine.step.
"""

import os
import sys
import threading
import time

import pytest

from xllm_service_tpu.api.http_utils import (
    RequestNotSentError,
    RetryBudget,
    make_http_server,
    post_json,
    post_json_retrying,
    request_was_sent,
)
from xllm_service_tpu.cluster.instance_mgr import (
    HealthState,
    InstanceMgr,
)
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.types import InstanceMetaInfo, InstanceType
from xllm_service_tpu.coordination import MemoryStore


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# FaultPlan semantics
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_no_plan_is_noop(self):
        faults.point("post_json.send", addr="a")  # must not raise

    def test_after_and_count_windows(self):
        faults.install_spec(
            {"seed": 0, "rules": [
                {"point": "p", "action": "drop", "after": 2, "count": 2},
            ]}
        )
        fired = []
        for i in range(6):
            try:
                faults.point("p")
                fired.append(False)
            except faults.FaultInjected:
                fired.append(True)
        # skip 2, fire 2, then exhausted
        assert fired == [False, False, True, True, False, False]

    def test_match_filters_on_ctx_values(self):
        faults.install_spec(
            {"rules": [{"point": "p", "match": "10.0.0.9", "action": "drop"}]}
        )
        faults.point("p", addr="10.0.0.1:80")  # no match
        with pytest.raises(faults.FaultInjected):
            faults.point("p", addr="10.0.0.9:80")

    def test_seeded_prob_is_deterministic(self):
        def run(seed):
            plan = faults.FaultPlan.from_spec(
                {"seed": seed, "rules": [
                    {"point": "p", "action": "drop", "prob": 0.5},
                ]}
            )
            out = []
            for _ in range(32):
                try:
                    plan.fire("p", {})
                    out.append(0)
                except faults.FaultInjected:
                    out.append(1)
            return out

        a, b = run(7), run(7)
        assert a == b
        assert 0 < sum(a) < 32  # actually probabilistic
        assert run(8) != a  # and seed-sensitive

    def test_action_classification(self):
        faults.install_spec(
            {"rules": [
                {"point": "a", "action": "error"},
                {"point": "b", "action": "partition"},
            ]}
        )
        with pytest.raises(faults.FaultInjected) as ei:
            faults.point("a")
        assert request_was_sent(ei.value)  # error = indeterminate
        with pytest.raises(faults.FaultInjected) as ei:
            faults.point("b")
        assert not request_was_sent(ei.value)  # partition = never sent

    def test_delay_sleeps_then_proceeds(self):
        faults.install_spec(
            {"rules": [{"point": "p", "action": "delay", "delay_ms": 30}]}
        )
        t0 = time.monotonic()
        faults.point("p")
        assert time.monotonic() - t0 >= 0.025

    def test_runtime_rule_add_remove(self):
        plan = faults.install_plan(faults.FaultPlan(seed=0))
        rule = plan.add_rule(faults.FaultRule(point="p", action="drop"))
        with pytest.raises(faults.FaultInjected):
            faults.point("p")
        plan.remove_rule(rule)
        faults.point("p")  # rule gone

    def test_bad_action_rejected(self):
        with pytest.raises(ValueError):
            faults.FaultRule(point="p", action="explode")


# ---------------------------------------------------------------------------
# retry layer
# ---------------------------------------------------------------------------


def _echo_server():
    srv = make_http_server(
        "threaded", "127.0.0.1", 0,
        do_post=lambda h: h.send_json({"ok": True, "route": h.route}),
    )
    srv.start()
    return srv


class TestRetryLayer:
    def test_budget_floor_and_deposit(self):
        b = RetryBudget(ratio=0.5, min_tokens=2, max_tokens=3)
        assert b.withdraw() and b.withdraw()
        assert not b.withdraw()
        assert b.exhausted_total == 1
        for _ in range(4):
            b.deposit()
        assert b.withdraw()

    def test_connection_refused_is_not_sent(self):
        with pytest.raises(RequestNotSentError):
            post_json("127.0.0.1:1", "/x", {}, timeout=2.0)

    def test_retrying_recovers_from_send_faults(self):
        srv = _echo_server()
        try:
            addr = f"{srv.host}:{srv.port}"
            faults.install_spec(
                {"rules": [
                    {"point": "post_json.send", "action": "drop", "count": 2},
                ]}
            )
            code, resp = post_json_retrying(
                addr, "/ok", {}, attempts=3, backoff_base_s=0.001
            )
            assert code == 200 and resp["ok"]
        finally:
            srv.stop()

    def test_non_idempotent_never_retries_indeterminate(self):
        srv = _echo_server()
        try:
            addr = f"{srv.host}:{srv.port}"
            faults.install_spec(
                {"rules": [{"point": "post_json.recv", "action": "error"}]}
            )
            with pytest.raises(faults.FaultInjected):
                post_json_retrying(
                    addr, "/gen", {}, attempts=3, backoff_base_s=0.001
                )
            # the rule would have allowed later successes: exactly one try
            plan = faults.get_plan()
            assert plan.rules()[0].fired == 1
        finally:
            srv.stop()

    def test_idempotent_retries_indeterminate(self):
        srv = _echo_server()
        try:
            addr = f"{srv.host}:{srv.port}"
            faults.install_spec(
                {"rules": [
                    {"point": "post_json.recv", "action": "error", "count": 2},
                ]}
            )
            code, _ = post_json_retrying(
                addr, "/cancel", {}, attempts=3, backoff_base_s=0.001,
                idempotent=True,
            )
            assert code == 200
        finally:
            srv.stop()

    def test_budget_exhaustion_stops_retries(self):
        faults.install_spec(
            {"rules": [{"point": "post_json.send", "action": "drop"}]}
        )
        budget = RetryBudget(ratio=0.0, min_tokens=1)
        with pytest.raises(faults.FaultInjected):
            post_json_retrying(
                "127.0.0.1:1", "/x", {}, attempts=10,
                backoff_base_s=0.001, budget=budget,
            )
        # 1 first attempt + 1 budgeted retry, then the bucket refused
        assert budget.exhausted_total >= 1
        plan = faults.get_plan()
        assert plan.rules()[0].fired == 2


# ---------------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------------


def make_mgr(**kw):
    store = MemoryStore()
    mgr = InstanceMgr(
        store, is_master=lambda: True,
        detect_disconnected_interval_s=kw.pop("stale_s", 15.0),
        suspect_failures=kw.pop("suspect", 2),
        eject_failures=kw.pop("eject", 3),
        probe_min_interval_s=kw.pop("probe_interval", 0.0),
    )
    return store, mgr


def reg(mgr, name, itype=InstanceType.DEFAULT):
    mgr._register(
        InstanceMetaInfo(
            name=name, type=itype, rpc_address="127.0.0.1:1",
            http_address="127.0.0.1:1", model_name="m",
        )
    )


class TestCircuitBreaker:
    def test_suspect_then_eject_on_consecutive_failures(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            assert mgr.health_state("i0") == HealthState.HEALTHY
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.HEALTHY
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.SUSPECT
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.EJECTED
            assert mgr.total_ejections == 1
        finally:
            mgr.close(); store.close()

    def test_success_resets_consecutive_failures(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            mgr.record_dispatch_failure("i0")
            mgr.record_dispatch_success("i0")
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.HEALTHY
        finally:
            mgr.close(); store.close()

    def test_routing_skips_ejected_and_deprioritizes_suspect(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0"); reg(mgr, "i1"); reg(mgr, "i2")
            for _ in range(3):
                mgr.record_dispatch_failure("i0")  # ejected
            mgr.record_dispatch_failure("i1")
            mgr.record_dispatch_failure("i1")  # suspect
            assert mgr.routable_prefill_instances() == ["i2"]
            for _ in range(8):
                r = mgr.get_next_instance_pair()
                assert r.prefill_name == "i2"
            # suspect is the last resort once the healthy one ejects
            for _ in range(3):
                mgr.record_dispatch_failure("i2")
            assert mgr.routable_prefill_instances() == ["i1"]
            # all ejected -> nothing routable
            for _ in range(3):
                mgr.record_dispatch_failure("i1")
            assert mgr.routable_prefill_instances() == []
            assert mgr.get_next_instance_pair().prefill_name == ""
            assert mgr.least_loaded(["i0", "i1", "i2"]) == ""
        finally:
            mgr.close(); store.close()

    def test_probe_recovers_ejected_to_probation(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            for _ in range(3):
                mgr.record_dispatch_failure("i0")
            probed = threading.Event()

            def prober(meta):
                probed.set()
                return meta.name == "i0"

            mgr.health_prober = prober
            assert mgr.probe_unhealthy() == 1
            assert probed.wait(2.0)
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                if mgr.health_state("i0") == HealthState.PROBATION:
                    break
                time.sleep(0.01)
            assert mgr.health_state("i0") == HealthState.PROBATION
            assert mgr.total_probe_recoveries == 1
            # probation routes again; one failure re-ejects immediately
            assert mgr.routable_prefill_instances() == ["i0"]
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.EJECTED
        finally:
            mgr.close(); store.close()

    def test_probe_drives_suspect_to_ejected_or_healthy(self):
        """A routing-avoided suspect never sees traffic, so the probe
        supplies the breaker's evidence: failures escalate to ejected,
        success heals to healthy."""
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            mgr.record_dispatch_failure("i0")
            mgr.record_dispatch_failure("i0")
            assert mgr.health_state("i0") == HealthState.SUSPECT
            mgr.health_prober = lambda meta: False
            mgr.probe_unhealthy()
            deadline = time.monotonic() + 2.0
            while (
                mgr.health_state("i0") != HealthState.EJECTED
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert mgr.health_state("i0") == HealthState.EJECTED
            # and the healing direction
            reg(mgr, "i1")
            mgr.record_dispatch_failure("i1")
            mgr.record_dispatch_failure("i1")
            mgr.health_prober = lambda meta: True
            mgr.probe_unhealthy()
            deadline = time.monotonic() + 2.0
            while (
                mgr.health_state("i1") != HealthState.HEALTHY
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert mgr.health_state("i1") == HealthState.HEALTHY
        finally:
            mgr.close(); store.close()

    def test_probe_success_then_dispatch_success_heals(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            for _ in range(3):
                mgr.record_dispatch_failure("i0")
            mgr.health_prober = lambda meta: True
            mgr.probe_unhealthy()
            deadline = time.monotonic() + 2.0
            while (
                mgr.health_state("i0") != HealthState.PROBATION
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            mgr.record_dispatch_success("i0")
            assert mgr.health_state("i0") == HealthState.HEALTHY
        finally:
            mgr.close(); store.close()

    def test_stale_heartbeat_marks_suspect_and_beat_clears(self):
        store, mgr = make_mgr(stale_s=0.2)
        try:
            reg(mgr, "i0")
            with mgr._mu:
                mgr._heartbeat_ts["i0"] = time.monotonic() - 1.0
            assert mgr.mark_stale_suspects() == ["i0"]
            assert mgr.health_state("i0") == HealthState.SUSPECT
            from xllm_service_tpu.common.types import LoadMetrics

            mgr.record_load_metrics_update("i0", LoadMetrics())
            assert mgr.health_state("i0") == HealthState.HEALTHY
        finally:
            mgr.close(); store.close()

    def test_reregistration_resets_breaker(self):
        store, mgr = make_mgr()
        try:
            reg(mgr, "i0")
            for _ in range(3):
                mgr.record_dispatch_failure("i0")
            mgr._remove("i0")
            reg(mgr, "i0")
            assert mgr.health_state("i0") == HealthState.HEALTHY
        finally:
            mgr.close(); store.close()


# ---------------------------------------------------------------------------
# heartbeat / engine-step points exist and are reachable
# ---------------------------------------------------------------------------


class TestInjectionSites:
    def test_heartbeat_send_point(self):
        from xllm_service_tpu.api.client import MasterClient

        faults.install_spec(
            {"rules": [{"point": "heartbeat.send", "action": "drop"}]}
        )
        with pytest.raises(faults.FaultInjected):
            MasterClient("127.0.0.1:1").heartbeat("x")

    def test_fake_engine_step_drop_goes_silent(self):
        from xllm_service_tpu.api.fake_engine import FakeEngine
        from xllm_service_tpu.ops.sampling import SamplingParams
        from xllm_service_tpu.runtime.engine import EngineRequest

        faults.install_spec(
            {"rules": [
                {"point": "fake_engine.step", "action": "drop", "after": 2},
            ]}
        )
        eng = FakeEngine(token_delay_s=0.0, ttft_ms=0.0)
        got, done = [], threading.Event()

        def cb(out):
            got.extend(t for s in out.outputs for t in s.token_ids)
            if out.finished:
                done.set()
            return True

        eng.add_request(EngineRequest(
            request_id="r", prompt_token_ids=[1, 2, 3, 4, 5],
            sampling=SamplingParams(max_new_tokens=5), callback=cb,
        ))
        # Wait for the two tokens before the drop (a loaded machine may
        # take more than any fixed nap to start the engine's thread),
        # THEN hold the stream to silence.
        deadline = time.monotonic() + 30.0
        while len(got) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert got == [5, 4]
        assert not done.wait(0.5)  # stream went silent, never finished
        assert got == [5, 4]

    def test_fake_engine_step_error_surfaces(self):
        from xllm_service_tpu.api.fake_engine import FakeEngine
        from xllm_service_tpu.common.types import StatusCode
        from xllm_service_tpu.ops.sampling import SamplingParams
        from xllm_service_tpu.runtime.engine import EngineRequest

        faults.install_spec(
            {"rules": [
                {"point": "fake_engine.step", "action": "error", "after": 1},
            ]}
        )
        eng = FakeEngine(token_delay_s=0.0, ttft_ms=0.0)
        outs, done = [], threading.Event()

        def cb(out):
            outs.append(out)
            if out.finished:
                done.set()
            return True

        eng.add_request(EngineRequest(
            request_id="r", prompt_token_ids=[1, 2, 3],
            sampling=SamplingParams(max_new_tokens=3), callback=cb,
        ))
        assert done.wait(2.0)
        assert outs[-1].status.code == StatusCode.UNAVAILABLE


# ---------------------------------------------------------------------------
# lint: unique, covered injection-point names
# ---------------------------------------------------------------------------


class TestFaultPointLint:
    def test_lint_clean(self):
        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts"),
        )
        import check_fault_points

        assert check_fault_points.main() == 0
