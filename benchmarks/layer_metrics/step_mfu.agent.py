"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.longmix).
FLOPs (harness/counts_laguna.py, lower bounds): every chunk token and decode
row the tap saw in the traced span through both kinds' projections and gates
(48 query heads on a full layer, 64 on a window layer), the dense first
layer, the routers, the shared experts and 8 routed pairs a token and expert
layer (all 256 experts are held), attention over the pairs each kind of
layer can see (the whole causal context on the two full layers, at most 512
positions on the three window layers) at the kind's TRUE query heads, and
the head for each decode row and chunk."""
from benchmarks.harness import counts_laguna as cl


def compute(w):
    if w.trace is None or w.config.get("family") != "laguna":
        return None
    steps, seconds = cl.traced_steps(w)
    chunk = int(w.engine["max_prefill_tokens"])
    starts, contexts = cl.traced_chunk_starts(w, chunk), cl.traced_decode_contexts(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cl.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
