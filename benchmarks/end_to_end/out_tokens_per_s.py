"""Output tokens that reached the client inside the window, over the
window's seconds. Every request counts, measured or warm-up: a token is
work the chip did in the window. A stream chunk is one engine step's
tokens; where a request's chunks do not add up to its reported
completion_tokens the chunks are weighted to that total."""
from benchmarks.harness import stats


def compute(w):
    total = 0.0
    for r in w.records:
        n = sum(r["chunk_tokens"])
        if not n:
            continue
        scale = (r["completion_tokens"] or n) / n
        total += stats.tokens_in_window(
            r["chunk_times"], [c * scale for c in r["chunk_tokens"]], 0.0, w.seconds
        )
    return total / w.seconds
