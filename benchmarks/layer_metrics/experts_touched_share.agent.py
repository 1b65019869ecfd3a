"""Engine: held experts a layer's tokens touched / held experts, a layer and
step, over the window, in percent: xllm_engine_moe_experts_touched_total /
xllm_engine_moe_experts_held_total (deltas): how much of a layer's 1.61 GB of
expert matrices a step streams. A mixed step's 512 + R rows touch every
expert; a decode step of R rows about 1 - (1 - 8/256)^R of them. A program
without both counters gives nothing."""
from benchmarks.harness import counts_laguna as cl


def compute(w):
    share = cl.touched_share(w)
    return None if share is None else 100.0 * share
