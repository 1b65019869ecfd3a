"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.doc).
FLOPs (harness/counts_granite.py, lower bounds): every chunk token and
decode row the tap saw in the traced span through the mixers' projections,
the shared MLP and the router, through the experts this holder has of its
top 10 (counts_granite.routed_pairs_per_token: the model's number, 5 a
layer, not a count of what the router chose), the scan (chunk form for a
chunk, the recurrence for a decode row), attention over the causal pairs of
the one GQA layer, and the head for each decode row and chunk."""
from benchmarks.harness import counts_granite as cg


def compute(w):
    if w.trace is None or w.config.get("family") != "granite":
        return None
    steps, seconds = cg.traced_steps(w)
    chunk = int(w.engine["max_prefill_tokens"])
    starts, contexts = cg.traced_chunk_starts(w, chunk), cg.traced_decode_contexts(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cg.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
