"""Master + instance front end: median of (client TTFT - engine TTFT).
Client TTFT runs from the due time to the first streamed chunk; engine
TTFT from the tap, add_request to the first callback. What is left is
the way in (HTTP, routing, dispatch to the instance) and the way out
(push channel, SSE)."""
from benchmarks.harness import readers


def compute(w):
    vals = []
    for r in w.measured():
        tap = readers.tap_of(w, r)
        if w.ok(r) and tap and tap["times"]:
            client = (r["chunk_times"][0] - r["due"]) * 1e3
            vals.append(client - (tap["times"][0] - tap["t_add"]) * 1e3)
    return readers.median(vals)
