"""Engine: the share of the window's steps that carried a prefill chunk
beside their decode rows: xllm_engine_prefill_chunks_total /
xllm_engine_decode_steps_total, in percent (one chunk a step at most:
the budget is one). The decode rows of such a step wait for the chunk:
it is what moves tpot_p90_ms in a cell of long prompts."""


def compute(w):
    chunks = w.counter_delta("xllm_engine_prefill_chunks_total")
    steps = w.counter_delta("xllm_engine_decode_steps_total")
    return None if chunks is None or not steps else 100.0 * chunks / steps
