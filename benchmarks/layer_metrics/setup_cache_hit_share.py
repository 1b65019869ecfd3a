"""Model step: of the compiles that the persistent cache answered, the
share it answered with an executable, in percent:
xllm_engine_program_builds_total `hit` / (`hit` + `miss`) over all
programs, as the window starts. Near 100: this run's `setup_s` is a warm
one; near 0: a cold one. Compiles the cache kept nothing of (`none`) are
in neither. A program without the series, or a start that asked no cache,
gives nothing."""

from benchmarks.harness.setup_series import children


def compute(w):
    builds = children(w.counters_start, "xllm_engine_program_builds_total")
    if builds is None:
        return None
    by_cache = {"hit": 0.0, "miss": 0.0}
    for (cache, _), n in builds.items():  # labels in name order: cache, program
        if cache in by_cache:
            by_cache[cache] += n
    asked = by_cache["hit"] + by_cache["miss"]
    return 100.0 * by_cache["hit"] / asked if asked else None
