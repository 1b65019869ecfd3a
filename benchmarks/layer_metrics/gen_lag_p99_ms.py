"""Load generator: send time - due time at the client, 99th percentile.
A starved generator must not read as a fast server."""
from benchmarks.harness import stats


def compute(w):
    vals = [(r["t_send"] - r["due"]) * 1e3 for r in w.measured() if r.get("t_send") is not None]
    return stats.percentile(vals, 99) if vals else None
