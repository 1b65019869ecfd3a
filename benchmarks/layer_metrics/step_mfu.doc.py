"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time. FLOPs (harness/counts_deepseek.py, lower bounds):
every chunk token and decode row the tap saw in the traced span through
the matrices, through the experts this holder has of its top 6
(counts_deepseek.routed_pairs_per_token: the model's number, 1.5 a layer,
not a count of what the router chose), the head for each decode row and
chunk, attention over the causal pairs in the MATERIALISED form's count
(the cheaper form: a lower bound on either)."""
from benchmarks.harness import counts_deepseek as cd


def compute(w):
    if w.trace is None or w.config.get("family") != "deepseek":
        return None
    steps, seconds = cd.traced_steps(w)
    m, chunk = w.model, int(w.engine["max_prefill_tokens"])
    starts, contexts = cd.traced_chunk_starts(w, chunk), cd.traced_decode_contexts(w)
    tokens = len(starts) * chunk + len(contexts)
    if not steps or not seconds or not tokens:
        return None
    flops = (
        tokens * (cd.token_matrix_flops(m) + cd.routed_pairs_per_token(m) * cd.expert_pair_flops(m))
        + (len(starts) + len(contexts)) * cd.head_flops(m)
        + sum(cd.chunk_attention_flops(m, s, chunk, absorbed=False) for s in starts)
        + sum(cd.decode_attention_flops(m, c, absorbed=False) for c in contexts)
    )
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
