"""Due time to first streamed token at the client, median over the
window's requests; a failed request is +inf."""
from benchmarks.harness import readers


def compute(w):
    return readers.percentile_with_failures(readers.client_ttfts_ms(w), 50)
