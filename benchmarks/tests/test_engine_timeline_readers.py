"""The three readers of the engine step timeline (PR 28) on hand-made
windows, and the name an idle gap takes when one of the program's
annotations and a PjRt event cover it together."""
import importlib.util
import os

import pytest

from benchmarks.harness import stack, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Window:
    """The part of run.Window the readers use."""

    def __init__(self, start="", end="", trace=None):
        self.counters_start = stack.parse_metrics(start)
        self.counters_end = stack.parse_metrics(end)
        self.trace = trace

    def counter_delta(self, name):
        a, b = self.counters_start.get(name), self.counters_end.get(name)
        return None if a is None or b is None else b - a


def exposition(steps, wait_sum, wait_count, **phases):
    lines = [f"xllm_engine_decode_steps_total {steps}",
             f"xllm_engine_queue_wait_ms_sum {wait_sum}",
             f"xllm_engine_queue_wait_ms_count {wait_count}",
             'xllm_engine_queue_wait_ms_bucket{le="+Inf"} %d' % wait_count]
    lines += [f'xllm_engine_loop_seconds_total{{phase="{p}"}} {v}' for p, v in phases.items()]
    return "\n".join(lines) + "\n"


def test_queue_wait_is_the_histograms_mean_over_the_window():
    w = Window(exposition(10, 1000.0, 4), exposition(30, 4000.0, 16))
    assert reader("engine_queue_wait_ms").compute(w) == pytest.approx(250.0)
    # the parent program has no such series; a window without requests has no mean
    assert reader("engine_queue_wait_ms").compute(Window("a 1\n", "a 2\n")) is None
    assert reader("engine_queue_wait_ms").compute(
        Window(exposition(10, 1000.0, 4), exposition(30, 1000.0, 4))) is None


def test_host_ms_per_step_leaves_out_idle_and_device_wait():
    start = exposition(100, 0, 0, idle=5.0, housekeeping=1.0, schedule=2.0,
                       dispatch=3.0, device_wait=50.0, emit=4.0)
    end = exposition(300, 0, 0, idle=6.0, housekeeping=1.1, schedule=2.2,
                     dispatch=3.4, device_wait=90.0, emit=4.3)
    w = Window(start, end)
    # the bare name holds the sum over all phases (parse_metrics): not read
    assert w.counter_delta("xllm_engine_loop_seconds_total") == pytest.approx(42.0)
    assert reader("engine_host_ms_per_step").compute(w) == pytest.approx(1e3 * 1.0 / 200)
    assert reader("engine_host_ms_per_step").compute(Window("a 1\n", "a 2\n")) is None
    assert reader("engine_host_ms_per_step").compute(Window(start, start)) is None  # no step


def test_idle_named_share():
    gaps = [["xllm.executor.step_keys", 0.03], ["PjitFunction(_threefry_seed)", 0.01],
            ["xllm.engine.device_wait", 0.05], ["no host span", 0.01]]
    w = Window(trace={"idle_gaps": gaps})
    assert reader("idle_named_share").compute(w) == pytest.approx(80.0)
    unnamed = Window(trace={"idle_gaps": [["shard_args", 0.041], ["np.asarray(jax.Array)", 0.5]]})
    assert reader("idle_named_share").compute(unnamed) == 0.0
    assert reader("idle_named_share").compute(Window(trace=None)) is None
    assert reader("idle_named_share").compute(Window(trace={"idle_gaps": []})) is None


def planes(host_events):
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit__decode_impl(1)", 0, 400), ("jit__decode_impl(1)", 600, 400)],
            "XLA Ops": [("fusion.1", 0, 400), ("fusion.1", 600, 400)],
        }},
        {"name": "/host:CPU", "lines": {"python": host_events}},
    ]


def test_a_gap_under_a_leaf_and_a_pjrt_event_takes_the_leafs_name():
    # the chip idles over [400, 600); the leaf annotation encloses PjRt's
    # event, as xllm.executor.step_keys encloses PjitFunction(_threefry_seed)
    leaf, pjrt = ("xllm.executor.step_keys", 390, 230), ("PjitFunction(_threefry_seed)", 420, 150)
    r = trace_reduce.reduce_planes(planes([leaf, pjrt]), chips=1)
    assert r["idle_gaps"] == [["xllm.executor.step_keys", pytest.approx(200e-9)]]
    # both cover the whole gap: a tie, and the first event in the line wins;
    # the profiler stores an enclosing event before the ones it encloses
    leaf, pjrt = ("xllm.executor.step_keys", 390, 230), ("PjitFunction(_threefry_seed)", 395, 220)
    r = trace_reduce.reduce_planes(planes([leaf, pjrt]), chips=1)
    assert r["idle_gaps"][0][0] == "xllm.executor.step_keys"
    r = trace_reduce.reduce_planes(planes([pjrt, leaf]), chips=1)
    assert r["idle_gaps"][0][0] == "PjitFunction(_threefry_seed)"
    # an enclosing engine phase would swallow the leaf's name the same way:
    # why the engine keeps its own annotation closed around the executor call
    phase = ("xllm.engine.dispatch", 380, 300)
    r = trace_reduce.reduce_planes(planes([phase, leaf, pjrt]), chips=1)
    assert r["idle_gaps"][0][0] == "xllm.engine.dispatch"
