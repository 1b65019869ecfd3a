"""Process start to window start: loading, building, weights, the
correctness check, warm-up traffic and, on a first run, compilation."""


def compute(w):
    return w.setup_s
