"""The five readers of the start-up timeline (PR 58) on hand-made windows:
each reads `counters_start` alone, gives a number whenever the program has
the series (0.0 for an empty sum) and nothing on a program without them."""
import importlib.util
import os

import pytest

from benchmarks.harness import setup_series, stack

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("setup_build_s", "setup_first_step_s", "setup_trace_lower_s",
           "setup_compile_s", "setup_cache_hit_share")


def compute(name, start):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Window:
        counters_start = stack.parse_metrics(start)
        counters_end = {}  # a reader that touched the window's end would fail

    return mod.compute(Window())


def exposition(phases=(), first=None, seconds=(), builds=()):
    lines = [f'xllm_engine_startup_seconds{{phase="{p}"}} {v}' for p, v in phases]
    if first is not None:
        lines.append(f"xllm_engine_first_step_seconds {first}")
    lines += [f'xllm_engine_program_seconds_total{{program="{p}",stage="{s}"}} {v}'
              for p, s, v in seconds]
    lines += [f'xllm_engine_program_builds_total{{cache="{c}",program="{p}"}} {v}'
              for p, c, v in builds]
    return "xllm_engine_decode_steps_total 7\n" + "\n".join(lines) + "\n"


WARM = exposition(
    phases=[("params", 20.5), ("pools", 1.25), ("programs", 0.0), ("engine", 0.5),
            ("instance", 0.25)],
    first=61.5,
    seconds=[("_decode_impl", "trace", 1.0), ("_decode_impl", "lower", 0.5),
             ("_decode_impl", "compile", 0.25), ("_decode_impl", "cache_read", 2.0),
             ("_mixed_impl", "trace", 2.0), ("_mixed_impl", "lower", 1.5),
             ("_mixed_impl", "compile", 0.0), ("_mixed_impl", "cache_read", 3.0),
             ("other", "trace", 4.0), ("other", "lower", 8.0),
             ("other", "compile", 16.0), ("other", "cache_read", 0.75)],
    builds=[("_decode_impl", "hit", 2), ("_mixed_impl", "hit", 1), ("other", "hit", 5),
            ("other", "miss", 2), ("other", "none", 30), ("_mixed_impl", "none", 0)],
)


def test_series_present():
    assert compute("setup_build_s", WARM) == pytest.approx(22.5)
    assert compute("setup_first_step_s", WARM) == pytest.approx(61.5)
    # the step programs' Python, without `other`
    assert compute("setup_trace_lower_s", WARM) == pytest.approx(5.0)
    # compiling and reading, every program
    assert compute("setup_compile_s", WARM) == pytest.approx(22.0)
    # 8 hits of 10 answers; the 30 the cache kept nothing of are in neither
    assert compute("setup_cache_hit_share", WARM) == pytest.approx(80.0)


@pytest.mark.parametrize("name", READERS)
def test_a_parent_without_the_series_gives_nothing(name):
    assert compute(name, "xllm_engine_decode_steps_total 7\n") is None


def test_series_present_and_empty_give_zero():
    empty = exposition(
        phases=[(p, 0) for p in ("params", "pools", "programs", "engine", "instance")],
        first=0,
        seconds=[("other", s, 0) for s in ("trace", "lower", "compile", "cache_read")],
        builds=[("other", c, 0) for c in ("hit", "miss", "none")],
    )
    for name in READERS[:4]:
        assert compute(name, empty) == 0.0, name
    # a share of nothing is no number: the start asked no cache
    assert compute("setup_cache_hit_share", empty) is None
    no_request = exposition(builds=[("other", "none", 12), ("_decode_impl", "none", 1)])
    assert compute("setup_cache_hit_share", no_request) is None
    assert compute("setup_cache_hit_share", exposition(builds=[("other", "miss", 3)])) == 0.0


def test_children_takes_labels_in_name_order():
    snap = stack.parse_metrics(WARM)
    builds = setup_series.children(snap, "xllm_engine_program_builds_total")
    assert builds[("hit", "_decode_impl")] == 2  # cache, then program
    seconds = setup_series.children(snap, "xllm_engine_program_seconds_total")
    assert seconds[("_mixed_impl", "cache_read")] == 3.0
    assert setup_series.children(snap, "xllm_engine_program") is None  # a prefix is no series


def test_the_manifest_lists_the_five_in_every_cell():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        man = json.load(f)
    cells = [w["name"] for w in man["workloads"]]
    ours = [e for e in man["per_layer"] if e["name"] in READERS]
    assert tuple(e["name"] for e in ours) == READERS
    for e in ours:
        assert e["moves"] == "setup_s" and e["source"] == "program_counter"
        assert e["workloads"] == cells
