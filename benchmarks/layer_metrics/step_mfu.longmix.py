"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.think).
FLOPs (harness/counts_mimo.py, lower bounds): every chunk token and decode
row the tap saw in the traced span through the mixers' projections, the
dense first layer and the routers, through the experts this holder has of
its top 8 (counts_mimo.routed_pairs_per_token: the model's number, 0.5 a
routed layer, not a count of what the router chose), attention over the
pairs each kind of layer can see (the whole causal context on the two full
layers, at most 128 positions on the five window layers), and the head for
each decode row and chunk."""
from benchmarks.harness import counts_mimo as cm


def compute(w):
    if w.trace is None or w.config.get("family") != "mimo":
        return None
    steps, seconds = cm.traced_steps(w)
    chunk = int(w.engine["max_prefill_tokens"])
    starts, contexts = cm.traced_chunk_starts(w, chunk), cm.traced_decode_contexts(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cm.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
