"""Model step: share of the traced device time that has a device region:
own time of the ops whose region is neither `unnamed` (no scope in the
compiled text, or an op of a program nobody mapped) nor `ambiguous` (two
step programs give the key different regions) / own time of all ops in
the trace (harness/regions.py, obs.regions)."""
from benchmarks.harness import regions


def compute(w):
    return regions.named_share(w)
