"""Engine: add_request to the first token's callback (the tap), median."""
from benchmarks.harness import readers


def compute(w):
    return readers.median(readers.engine_ttfts_ms(w))
