"""Per request (last token - first token) / (output tokens - 1) at the
client, 90th percentile over the window's requests with two tokens or
more; a failed request is +inf."""
from benchmarks.harness import readers


def compute(w):
    vals = []
    for r in w.measured():
        if not w.ok(r):
            vals.append(None)
        elif r["completion_tokens"] >= 2:
            vals.append(
                (r["chunk_times"][-1] - r["chunk_times"][0]) * 1e3
                / (r["completion_tokens"] - 1)
            )
    return readers.percentile_with_failures(vals, 90)
