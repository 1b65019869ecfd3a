"""Model step: device milliseconds a step program's execution (mixed or
decode) spends in the regions `head` (final norm + unembedding) and
`sample` (keys, penalties, the sampler, the logprob gather)
(harness/regions.py)."""
from benchmarks.harness import regions


def compute(w):
    return regions.ms_per_step(w, ("head", "sample"))
