"""Engine: seconds of the set-up the program spent BUILDING, from inside:
the sum of xllm_engine_startup_seconds{phase} (the executor's parameters
and pools, its step programs' wrappers and warm-up, the engine, the
instance until the master has registered it; exclusive scopes, so they
add up) as the window starts. The harness's own share of a set-up (its
weights, reference and warm-up traffic) is in none of them. A program
without the series gives nothing."""

from benchmarks.harness.setup_series import children


def compute(w):
    phases = children(w.counters_start, "xllm_engine_startup_seconds")
    return None if phases is None else sum(phases.values())
