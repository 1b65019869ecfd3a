"""Engine: the share of the window's sparse-layer token rows (decode rows and
chunk rows) that took the selected path, in percent:
xllm_engine_attn_rows_selected_total / (selected + dense) (deltas): whether
the traffic reaches the mechanism at all. A program without the counters
gives nothing."""


def compute(w):
    sel = w.counter_delta("xllm_engine_attn_rows_selected_total")
    dense = w.counter_delta("xllm_engine_attn_rows_dense_total")
    if sel is None or dense is None or sel + dense <= 0:
        return None
    return 100.0 * sel / (sel + dense)
