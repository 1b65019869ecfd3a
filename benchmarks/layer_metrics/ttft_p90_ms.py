"""Engine (read at the client): due time to first streamed token, 90th
percentile over the window's requests; a failed request is +inf. The
wait for a prefill chunk inside the engine is 93 % of the client's median
TTFT (PERF.md section 5), so this tail is filed under the engine's layer.
Recorded, not judged: over 144 requests it spreads 6-7 % from run to run
on one code (PERF.md section 2), more than a bound of 10 % can carry."""
from benchmarks.harness import readers


def compute(w):
    return readers.percentile_with_failures(readers.client_ttfts_ms(w), 90)
