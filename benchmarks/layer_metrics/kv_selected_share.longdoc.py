"""Engine: pages the selected rows READ over pages their contexts HOLD, in
percent, over the window: xllm_engine_sparse_pages_selected_total /
xllm_engine_sparse_pages_live_total (deltas; the engine books both from the
positions of every row it dispatches: 64 a row past dense_len against
cdiv(context, 64)). What a dense launch would have read is 100; lower is the
mechanism at work, and it falls as contexts grow. A program without the
counters gives nothing."""


def compute(w):
    read = w.counter_delta("xllm_engine_sparse_pages_selected_total")
    held = w.counter_delta("xllm_engine_sparse_pages_live_total")
    if read is None or held is None or held <= 0:
        return None
    return 100.0 * read / held
