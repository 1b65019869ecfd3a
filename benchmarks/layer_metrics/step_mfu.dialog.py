"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.assist).
FLOPs (harness/counts_falcon_h1.py, lower bounds): every chunk token and
decode row the tap saw in the traced span through both mixers' projections
and the dense MLP of the nine blocks, the scan (chunk form for a chunk, the
recurrence for a decode row), attention over the causal pairs in every
block, and the head over the vocabulary rows held for each decode row and
chunk."""
from benchmarks.harness import counts_falcon_h1 as cf


def compute(w):
    if w.trace is None or w.config.get("family") != "falcon_h1":
        return None
    steps, seconds = cf.traced_steps(w)
    chunk = int(w.engine["max_prefill_tokens"])
    starts, contexts = cf.traced_chunk_starts(w, chunk), cf.traced_decode_contexts(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cf.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
