"""Model step: device milliseconds a step program's execution (mixed or
decode) spends in the region `attn_proj`: q/k/v/o, biases, RoPE, QK-norm;
MLA's down- and up-projections and the absorb (harness/regions.py)."""
from benchmarks.harness import regions


def compute(w):
    return regions.ms_per_step(w, ("attn_proj",))
