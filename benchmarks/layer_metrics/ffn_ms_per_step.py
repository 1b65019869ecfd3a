"""Model step: device milliseconds a step program's execution (mixed or
decode) spends in the regions `ffn` (dense gate/up/down, the shared
experts), `moe_route` and `moe_experts` (harness/regions.py)."""
from benchmarks.harness import regions


def compute(w):
    return regions.ms_per_step(w, ("ffn", "moe_route", "moe_experts"))
