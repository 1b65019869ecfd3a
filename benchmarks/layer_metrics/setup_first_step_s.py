"""Engine: seconds from the import of the program's package to the first
step program's results read by the engine thread
(xllm_engine_first_step_seconds, set once): the restart as the first caller
feels it. A program without the gauge gives nothing."""


def compute(w):
    return w.counters_start.get("xllm_engine_first_step_seconds")
