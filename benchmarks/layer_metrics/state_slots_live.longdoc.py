"""Engine: state slots owned by a sequence (decoding or mid-prefill), mean
over the window's steps (xllm_engine_state_slots_in_use sum / count,
observed every step), for the family whose lightning layers hold a state
slot beside the sparse layers' K/V blocks: how many of the pool's 32 slots
the open loop keeps busy. A program without the series, or another family,
gives nothing."""
from benchmarks.harness import readers


def compute(w):
    if w.config.get("family") != "minicpm_sala":
        return None
    return readers.hist_mean(w, "xllm_engine_state_slots_in_use")
