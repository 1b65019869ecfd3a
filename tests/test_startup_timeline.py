"""Start-up timeline (docs/OBSERVABILITY.md "Start-up timeline"): JAX's
compile events booked by step program and stage, the persistent cache's
hits and misses carried to the compile that follows them, and the series
of a real (tiny, CPU) engine. No test asserts on a wall clock beyond "it
moved" or "it did not"."""

import json
import os
import subprocess
import sys
import threading

import pytest
from jax import monitoring

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.obs import STARTUP_PHASES, STEP_PROGRAMS
from xllm_service_tpu.obs import startup
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, COMPILE, READ = (
    startup._TRACE, startup._LOWER, startup._COMPILE, startup._CACHE_READ,
)
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


@pytest.fixture
def timeline():
    """The process's timeline with its listeners in place, and what it
    held before the test (other tests of this process compile too)."""
    tl = startup.TIMELINE
    tl.install()
    seconds, builds = dict(tl.program_seconds), dict(tl.program_builds)

    def moved():
        out = {
            k: round(v - seconds[k], 6)
            for k, v in tl.program_seconds.items() if v != seconds[k]
        }
        out.update({
            k: v - builds[k]
            for k, v in tl.program_builds.items() if v != builds[k]
        })
        return out

    return moved


@pytest.mark.parametrize("event,fun_name,key", [
    (TRACE, "_decode_impl", ("_decode_impl", "trace")),
    (LOWER, "jit(_mixed_impl)", ("_mixed_impl", "lower")),
    (LOWER, "jit__mixed_impl", ("_mixed_impl", "lower")),
    (TRACE, "_where", ("other", "trace")),
    (LOWER, "jit(_lambda)", ("other", "lower")),
    (TRACE, "_import_impl", ("_import_impl", "trace")),
])
def test_a_duration_is_booked_under_its_program_and_stage(
    timeline, event, fun_name, key
):
    monitoring.record_event_duration_secs(event, 0.5, fun_name=fun_name)
    assert timeline() == {key: 0.5}


def test_a_compile_nobody_cached_counts_none(timeline):
    monitoring.record_event_duration_secs(
        COMPILE, 2.0, fun_name="jit(_decode_impl)"
    )
    assert timeline() == {
        ("_decode_impl", "compile"): 2.0, ("_decode_impl", "none"): 1,
    }


def test_a_hit_and_its_read_ride_to_the_compile_that_follows(timeline):
    # the order JAX fires them in, inside the backend-compile scope
    monitoring.record_event(HIT)
    monitoring.record_event_duration_secs(READ, 0.25)
    monitoring.record_event_duration_secs(
        COMPILE, 1.0, fun_name="jit(_mixed_impl)"
    )
    assert timeline() == {
        ("_mixed_impl", "compile"): 0.75, ("_mixed_impl", "cache_read"): 0.25,
        ("_mixed_impl", "hit"): 1,
    }
    # and are used up: the next compile asked no cache
    monitoring.record_event_duration_secs(COMPILE, 1.0, fun_name="jit(iota)")
    assert timeline()[("other", "none")] == 1


def test_a_miss_counts_under_the_compile_of_its_own_thread(timeline):
    def elsewhere():
        monitoring.record_event(HIT)  # never followed by a compile here

    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(10)
    assert not t.is_alive()
    monitoring.record_event(MISS)
    monitoring.record_event_duration_secs(
        COMPILE, 3.0, fun_name="jit(_prefill_impl)"
    )
    assert timeline() == {
        ("_prefill_impl", "compile"): 3.0, ("_prefill_impl", "miss"): 1,
    }


def test_a_scope_inside_another_books_no_seconds_of_its_own(timeline):
    # JAX announces a scope's start with a scalar event: a library
    # function traced, and a constant compiled, inside a program's trace
    monitoring.record_scalar(TRACE, 0.0, fun_name="_decode_impl")
    monitoring.record_scalar(TRACE, 0.0, fun_name="_where")
    monitoring.record_event_duration_secs(TRACE, 0.1, fun_name="_where")
    monitoring.record_scalar(COMPILE, 0.0, fun_name="jit(iota)")
    monitoring.record_event_duration_secs(COMPILE, 0.2, fun_name="jit(iota)")
    monitoring.record_event_duration_secs(TRACE, 1.0, fun_name="_decode_impl")
    assert timeline() == {
        ("_decode_impl", "trace"): 1.0, ("other", "none"): 1,
    }
    # the thread is at the top again
    monitoring.record_scalar(TRACE, 0.0, fun_name="_where")
    monitoring.record_event_duration_secs(TRACE, 0.1, fun_name="_where")
    assert timeline()[("other", "trace")] == 0.1


def test_a_second_install_adds_no_second_listener(timeline):
    startup.TIMELINE.install()
    monitoring.record_event_duration_secs(LOWER, 0.5, fun_name="jit(_decode_impl)")
    assert timeline() == {("_decode_impl", "lower"): 0.5}


def test_a_step_program_outside_the_vocabulary_is_refused():
    ex = object.__new__(ModelExecutor)

    def _sampling_impl():
        pass

    with pytest.raises(ValueError, match="STEP_PROGRAMS"):
        ex._step_jit(_sampling_impl)


# --------------------------------------------------- a real engine, tiny


def _engine():
    cfg = EngineConfig(
        model="llama3-tiny", dtype="float32", block_size=16, num_blocks=64,
        max_running_requests=4, max_seq_len=256, prefill_buckets=[32],
    )
    return InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))


def _serve(eng, rid):
    done = threading.Event()

    def on_output(out):
        if out.finished:
            done.set()
        return True

    eng.add_request(EngineRequest(
        rid, list(range(1, 25)),
        SamplingParams(temperature=0.0, max_new_tokens=6), on_output,
    ))
    assert done.wait(120)


def _series(eng):
    out = {}
    for line in eng.metrics.render().splitlines():
        if line and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            out[head] = float(val)
    return out


def test_a_served_request_leaves_its_programs_on_the_engines_metrics():
    tl = startup.TIMELINE
    phases0 = dict(tl.phase_seconds)
    eng = _engine()
    assert tl.phase_seconds["params"] > phases0["params"]
    assert tl.phase_seconds["pools"] > phases0["pools"]
    assert tl.phase_seconds["programs"] > phases0["programs"]
    assert tl.phase_seconds["engine"] > phases0["engine"]
    before = _series(eng)
    eng.start()
    try:
        _serve(eng, "a")
        _serve(eng, "b")  # the steps of an idle engine are variants too
        warm = _series(eng)
        lowerings = eng.executor.lowering_count()
        _serve(eng, "c")
        after = _series(eng)
    finally:
        eng.stop()
    assert not eng._thread.is_alive()

    def program(snap, name, stage):
        return snap[
            f'xllm_engine_program_seconds_total{{program="{name}",stage="{stage}"}}'
        ]

    # this engine's decode program was traced and lowered (compiled or
    # read from the tests' cache), and a repeated step adds nothing
    for stage in ("trace", "lower"):
        assert program(warm, "_decode_impl", stage) > program(
            before, "_decode_impl", stage
        )
    assert eng.executor.lowering_count() == lowerings
    builds = "xllm_engine_program_"
    assert {k: v for k, v in after.items() if k.startswith(builds)} == {
        k: v for k, v in warm.items() if k.startswith(builds)
    }
    # set once: by the first step of the first engine this process served
    first = "xllm_engine_first_step_seconds"
    assert warm[first] > 0 and after[first] == warm[first]
    assert before[first] in (0.0, warm[first])
    assert eng._phase_inc["device_wait"].__name__ != "first"  # hook is out
    # a fixed, small set of children
    seconds = [k for k in after if k.startswith(builds + "seconds_total{")]
    assert len(seconds) == (len(STEP_PROGRAMS) + 1) * 4
    assert {
        f'xllm_engine_startup_seconds{{phase="{p}"}}' for p in STARTUP_PHASES
    } <= set(after)
    for gone in ("hits_total", "prewarm_ms_total"):
        assert "xllm_engine_compile_cache_" + gone not in after
    assert "xllm_engine_compile_cache_misses_total" in after


_TWO_STARTS = """
import json, sys, threading
import jax
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.obs import startup
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

def one_start():
    cfg = EngineConfig(
        model="llama3-tiny", dtype="float32", block_size=16, num_blocks=64,
        max_running_requests=4, max_seq_len=256, prefill_buckets=[32],
        compilation_cache_dir=sys.argv[1],
    )
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    eng.start()
    done = threading.Event()
    eng.add_request(EngineRequest(
        "r", list(range(1, 25)),
        SamplingParams(temperature=0.0, max_new_tokens=4),
        lambda out: (done.set() if out.finished else None) or True,
    ))
    assert done.wait(120)
    eng.stop()
    return {"/".join(k): v for k, v in startup.TIMELINE.program_builds.items()}

first = one_start()
second = one_start()
print(json.dumps({"first": first, "second": second}))
"""


def test_a_second_start_reads_its_programs_from_the_cache(tmp_path):
    """Two starts in a process of their own over an EMPTY cache directory
    (minimum compile time 0, minimum entry size -1): the first start's
    step programs are misses, the second's are hits."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", XLLM_COMPILE_CACHE_MIN_COMPILE_S="0",
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLLM_COMPILE_CACHE", None)
    p = subprocess.run(
        [sys.executable, "-c", _TWO_STARTS, str(tmp_path / "cache")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    first, second = got["first"], got["second"]
    stepped = [
        prog for prog in STEP_PROGRAMS
        if any(second[f"{prog}/{c}"] for c in startup.BUILD_CACHE)
    ]
    assert "_decode_impl" in stepped or "_mixed_impl" in stepped
    for prog in stepped:
        assert first[f"{prog}/miss"] >= 1 and first[f"{prog}/hit"] == 0
        # the second start added hits alone
        assert second[f"{prog}/miss"] == first[f"{prog}/miss"]
        assert second[f"{prog}/hit"] == first[f"{prog}/miss"]
        assert second[f"{prog}/none"] == first[f"{prog}/none"] == 0
