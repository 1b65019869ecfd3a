"""Engine: add_request to the dispatch of the request's first prefill
chunk, mean over the window (xllm_engine_queue_wait_ms sum / count; the
program's own counter, observed once per request). With the window's mean
of xllm_engine_ttft_ms (first dispatch to first token) it sums to what
the tap reads as engine_ttft_ms. A program without the series gives
nothing."""
from benchmarks.harness import readers


def compute(w):
    return readers.hist_mean(w, "xllm_engine_queue_wait_ms")
