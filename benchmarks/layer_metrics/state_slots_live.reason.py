"""Engine: state slots owned by a sequence (decoding or mid-prefill),
mean over the window's steps (xllm_engine_state_slots_in_use sum / count,
observed every step). A program without the series gives nothing."""
from benchmarks.harness import readers


def compute(w):
    return readers.hist_mean(w, "xllm_engine_state_slots_in_use")
