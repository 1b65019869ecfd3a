"""trace_reduce against a hand-made trace and the recorded fixture."""
import json
import os

import pytest

from benchmarks.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "..", "fixtures", "tpu_v5e_decode_batch.planes.json")


def test_self_times_peel_the_layer_loop():
    # while [0, 100) holds fusion [10, 40) and a kernel [50, 90) that itself
    # holds a copy [60, 70)
    ev = [("while.1", 0, 100), ("fusion.7", 10, 30), ("paged.3", 50, 40), ("copy.2", 60, 10)]
    own = trace_reduce.self_times(ev)
    assert own == {"while.1": 30, "fusion.7": 30, "paged.3": 30, "copy.2": 10}
    assert trace_reduce.op_family("fusion.123") == "fusion"
    assert trace_reduce.program_name("jit__decode_impl(123456)") == "_decode_impl"


def test_busy_idle_programs_and_gap_attribution():
    planes = [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit__decode_impl(1)", 0, 400), ("jit__decode_impl(1)", 600, 400)],
            "XLA Ops": [("while.1", 0, 400), ("fusion.2", 0, 300), ("while.1", 600, 400)],
        }},
        {"name": "/host:CPU", "lines": {
            "engine": [("bench:drain", 380, 250), ("tiny", 590, 5), ("bench:late", 1000, 100)],
            "noise": [("ThreadpoolListener::Record", 0, 1000)],
        }},
    ]
    r = trace_reduce.reduce_planes(planes, chips=1)
    # the window runs on to the end of the host's last span: the chip idles there
    assert r["busy_s"] == pytest.approx(800e-9) and r["window_s"] == pytest.approx(1100e-9)
    assert r["programs"][0] == ["_decode_impl", pytest.approx(800e-9)]
    assert r["program_durations_ns"]["_decode_impl"] == [400, 400]
    assert dict(map(tuple, r["device_ops"]))["while"] == pytest.approx(500e-9)
    assert r["idle_gaps"] == [["bench:drain", pytest.approx(200e-9)]]
    assert trace_reduce.reduce_planes(planes[1:], chips=1) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded fixture")
def test_recorded_fixture():
    with open(FIXTURE) as f:
        fx = json.load(f)
    planes = [{"name": p["name"], "lines": {k: [tuple(e) for e in v] for k, v in p["lines"].items()}}
              for p in fx["planes"]]
    r = trace_reduce.reduce_planes(planes, chips=1)
    for key, want in fx["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any(name in dict(map(tuple, r["programs"])) for name in fx["expect_programs"])
