"""Engine + block manager: mean active rows per decode step over the
window (xllm_engine_decode_batch_size sum / count)."""
from benchmarks.harness import readers


def compute(w):
    return readers.hist_mean(w, "xllm_engine_decode_batch_size")
