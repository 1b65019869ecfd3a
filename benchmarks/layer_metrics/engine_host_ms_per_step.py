"""Engine: host time of the engine thread per step, in ms: the window's
seconds of xllm_engine_loop_seconds_total over every phase but `idle`
(nothing to do) and `device_wait` (blocked on the device), divided by the
steps of the window (xllm_engine_decode_steps_total). Labelled series
stand under their full name in the counters (stack.parse_metrics). A
program without the series gives nothing."""

SERIES = "xllm_engine_loop_seconds_total{"
NOT_HOST = ('phase="idle"', 'phase="device_wait"')


def compute(w):
    steps = w.counter_delta("xllm_engine_decode_steps_total")
    names = [
        k for k in w.counters_end
        if k.startswith(SERIES) and not any(p in k for p in NOT_HOST)
    ]
    deltas = [w.counter_delta(k) for k in names]
    if not steps or not deltas or any(d is None for d in deltas):
        return None
    return 1e3 * sum(deltas) / steps
