"""Model step: seconds of Python the step programs cost this start:
xllm_engine_program_seconds_total over the stages `trace` and `lower` of
every program but `other` (what the build and the harness trace), as the
window starts. No cache shortens them: a warm start pays them as a cold
one does. A program without the series gives nothing."""

from benchmarks.harness.setup_series import children

STAGES = ("trace", "lower")


def compute(w):
    seconds = children(w.counters_start, "xllm_engine_program_seconds_total")
    if seconds is None:
        return None
    return sum(
        v for (program, stage), v in seconds.items()
        if program != "other" and stage in STAGES
    )
