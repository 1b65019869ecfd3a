"""Model step: model FLOPs of the traced steps / 197 TFLOP/s / the step
programs' device time, the share of the WHOLE step (as step_mfu.dialog).
FLOPs (harness/counts_minicpm_sala.py, lower bounds): every chunk token and
decode row the tap saw in the traced span through both kinds of mixer's
projections and gates and the dense MLP of the eight layers, the lightning
recurrence a token, attention over the tokens a row ATTENDS (its selected
blocks' past dense_len, its context under it), stage 1's scores over the
visible compressed keys, and the head over the whole vocabulary for each
decode row and chunk."""
from benchmarks.harness import counts_minicpm_sala as cs


def compute(w):
    if w.trace is None or w.config.get("family") != "minicpm_sala":
        return None
    steps, seconds = cs.traced_steps(w)
    starts, chunk, contexts = cs.traced_rows(w)
    if not steps or not seconds or not (starts or contexts):
        return None
    flops = cs.model_flops(w.model, starts, chunk, contexts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
