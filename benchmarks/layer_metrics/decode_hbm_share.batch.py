"""Model step: (weight bytes of every traced step + KV bytes of the
contexts of the tokens they emitted) / peak HBM bandwidth / the steps'
device time. Bytes from counts.py (a lower bound: blocks are padded,
activations left out), time from the trace."""
from benchmarks.harness import readers


def compute(w):
    return readers.step_bytes_share(w, readers.STEP_PROGRAMS, with_weights=True)
