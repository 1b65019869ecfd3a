"""Model step: share of the traced device time in the region
`stack_slice`: what the layer scan does outside an inner region (a
layer's leaves, caches and state sliced out of the stacks and written
back) (harness/regions.py)."""
from benchmarks.harness import regions


def compute(w):
    return regions.share(w, ("stack_slice",))
