"""Fleet simulation harness tests (cluster/fleet_sim).

These run the REAL scheduler stack under the simulated clock at small
scale — bench_fleet.py covers the 50-instance / 10k-stream runs; here
the contract is correctness: every submitted stream reaches a terminal
state, rolling restarts recover through the real redispatch/resume
machinery, and the `fleet_sim.tick` chaos seam loses events without
ever hanging the run.
"""

import pytest

from xllm_service_tpu.cluster.fleet_sim import FleetSim, SCENARIOS, make_trace
from xllm_service_tpu.common import faults


def _run(scenario, num_requests, duration_s, num_instances, seed, **kw):
    trace = make_trace(scenario, num_requests, duration_s, num_instances, seed)
    sim = FleetSim(num_instances=num_instances, seed=seed,
                   policy=trace.policy, **kw)
    try:
        return sim.run(trace)
    finally:
        sim.close()


class TestTraces:
    def test_every_scenario_generates_requested_load(self):
        for name in SCENARIOS:
            trace = make_trace(name, 40, 10.0, 4, seed=3)
            assert len(trace.requests) == 40, name
            assert trace.duration_s == 10.0
            assert all(0.0 <= r.t <= 10.0 for r in trace.requests), name
            # Arrivals come back time-sorted so the sim heap seeds cheaply.
            ts = [r.t for r in trace.requests]
            assert ts == sorted(ts), name

    def test_rolling_restart_trace_cycles_every_instance(self):
        trace = make_trace("rolling_restart", 20, 10.0, 4, seed=0)
        drained = {a.instance for a in trace.actions if a.kind == "drain"}
        rejoined = {a.instance for a in trace.actions if a.kind == "rejoin"}
        assert drained == rejoined == set(range(4))

    def test_straggler_trace_marks_slow_instances(self):
        trace = make_trace("straggler", 20, 10.0, 8, seed=0)
        assert trace.straggler_factors
        assert all(f > 1.0 for f in trace.straggler_factors.values())

    def test_unknown_scenario_rejected(self):
        with pytest.raises(ValueError, match="unknown scenario"):
            make_trace("nope", 1, 1.0, 1)


class TestFleetSim:
    def test_burst_completes_every_stream(self):
        rep = _run("burst", 60, 15.0, 4, seed=1)
        assert rep.submitted == 60
        assert rep.completed == 60
        assert rep.failed == 0 and rep.shed == 0 and rep.unrecovered == 0
        assert rep.peak_concurrent >= 1
        assert rep.p50_ttft_s > 0.0
        assert rep.p99_ttft_s >= rep.p50_ttft_s
        assert rep.total_tok_s > 0.0
        # Sim time advances with the trace, not wall time.
        assert rep.sim_duration_s >= 10.0
        assert rep.wall_s < rep.sim_duration_s

    def test_rolling_restart_recovers_every_stream(self):
        rep = _run("rolling_restart", 150, 20.0, 4, seed=2)
        assert rep.submitted == 150
        # The hard contract: every stream reaches a terminal state — no
        # hangs, no silent drops.
        assert rep.unrecovered == 0
        assert rep.completed + rep.failed == 150
        # Cycling ALL 4 instances under load can push a stream past its
        # shared max_redispatch budget (default 2) into the designed
        # fail-fast; that must stay a sliver, not a mode. How many do is
        # wall time: the store's watch thread and the lane threads run
        # beside the sim clock, and the further the watch lags a drain
        # the more arrivals are routed onto the dead instance and burn a
        # redispatch (1-6 in 30 runs of this trace in one idle process,
        # over 3 in half of them; the driver's suite read 2 of 6). A recovery
        # path that is broken fails every stream a drain touches, and a
        # run touches far more than that. bench_fleet's 50-instance guard
        # enforces failed == 0 at real scale.
        recovered = rep.redispatches + rep.resumes
        assert rep.failed <= 15 and rep.failed < recovered / 3
        # Restarting under load must exercise the real recovery path.
        assert recovered > 0

    def test_report_round_trips_to_json(self):
        rep = _run("burst", 10, 5.0, 2, seed=4)
        d = rep.to_json()
        assert d["scenario"] == "burst"
        assert d["completed"] == 10
        assert isinstance(d["sheds_by_reason"], dict)


class TestTickFaultPoint:
    """Chaos seam: every sim event routes through faults.point
    ("fleet_sim.tick"); dropped events must never hang the run."""

    def test_drop_all_ticks_runs_nothing(self):
        plan = faults.FaultPlan(rules=[
            faults.FaultRule(point="fleet_sim.tick", action="drop"),
        ])
        faults.install_plan(plan)
        try:
            rep = _run("burst", 12, 5.0, 2, seed=5, drain_timeout_s=1.0)
        finally:
            faults.clear()
        # Arrivals themselves were dropped: no stream ever existed.
        assert rep.submitted == 0
        assert rep.completed == 0
        assert rep.events > 0  # ticks were popped, just all lost

    def test_dropped_service_events_surface_as_unrecovered(self):
        # Let the first events through (arrivals + their dispatches),
        # then lose everything: the in-flight streams can never finish,
        # and the drain bound must convert them to `unrecovered` rather
        # than hang.
        plan = faults.FaultPlan(rules=[
            faults.FaultRule(point="fleet_sim.tick", action="drop", after=6),
        ])
        faults.install_plan(plan)
        try:
            rep = _run("burst", 10, 4.0, 2, seed=6, drain_timeout_s=1.0)
        finally:
            faults.clear()
        assert 0 < rep.submitted <= 6
        assert rep.unrecovered > 0
        assert rep.unrecovered == rep.submitted - rep.completed - rep.failed
