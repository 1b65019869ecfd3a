"""Model step: seconds this start spent in the backend compiler or reading
executables from the persistent cache: xllm_engine_program_seconds_total
over the stages `compile` and `cache_read` of ALL programs (`other` too:
the build's allocations, the weights' draw, the harness's reference), as
the window starts. The part of a set-up a warm cache takes away. A program
without the series gives nothing."""

from benchmarks.harness.setup_series import children

STAGES = ("compile", "cache_read")


def compute(w):
    seconds = children(w.counters_start, "xllm_engine_program_seconds_total")
    if seconds is None:
        return None
    return sum(v for (_, stage), v in seconds.items() if stage in STAGES)
