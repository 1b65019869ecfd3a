"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.assist).
FLOPs (harness/counts_solar.py, lower bounds): every chunk token and decode
row the tap saw in the traced span through the mixers' projections, the
shared expert and the router, through the experts this holder has of its
top 8 (counts_solar.routed_pairs_per_token: the model's number, 0.5 a
layer, not a count of what the router chose), the delta rule (chunk form
for a chunk, the recurrence for a decode row, from their equations),
attention over the causal pairs of the two GQA layers, and the head for
each decode row and chunk."""
from benchmarks.harness import counts_solar as cs


def compute(w):
    if w.trace is None or w.config.get("family") != "solar":
        return None
    steps, seconds = cs.traced_steps(w)
    chunk = int(w.engine["max_prefill_tokens"])
    starts, contexts = cs.traced_chunk_starts(w, chunk), cs.traced_decode_contexts(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cs.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
