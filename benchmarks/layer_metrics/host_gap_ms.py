"""Engine: mean of xllm_engine_host_gap_ms over the window (host clock:
the gap between one step's drain and the next dispatch)."""
from benchmarks.harness import readers


def compute(w):
    return readers.hist_mean(w, "xllm_engine_host_gap_ms")
