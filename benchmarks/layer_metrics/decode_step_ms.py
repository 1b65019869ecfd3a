"""Model step: device duration of the decode program's executions in the
traced span, median."""
from benchmarks.harness import readers


def compute(w):
    return readers.median(readers.program_durations_ms(w, readers.DECODE_PROGRAMS))
