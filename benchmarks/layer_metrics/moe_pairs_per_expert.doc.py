"""Engine: routed pairs that fell to a held expert, per step and expert
layer: the mean of xllm_engine_moe_pairs_per_expert over the window (one
observation a held expert and drained step): the group size the grouped
expert product works at. A program without the series gives nothing."""
from benchmarks.harness import readers


def compute(w):
    return readers.hist_mean(w, "xllm_engine_moe_pairs_per_expert")
