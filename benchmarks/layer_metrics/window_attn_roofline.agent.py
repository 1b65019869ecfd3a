"""Kernels: the window layers' decode attention launches' share of their
roofline, which HBM bandwidth bounds: (K and V bytes of the last
min(context, 512) tokens of every decode row of the traced span, over the
three window layers: harness/counts_laguna.py, TRUE bytes) / peak HBM
bandwidth / summed device time of the "window_paged_attention_kernel" custom
calls. The kernel fetches whole blocks, five where the window of four
straddles one, so it cannot pass about four fifths. A program without the
kernel gives nothing."""
from benchmarks.harness import counts_laguna as cl


def compute(w):
    if w.trace is None or w.config.get("family") != "laguna":
        return None
    seconds = cl.kernel_seconds(w, cl.WINDOW_DECODE_KERNEL)
    contexts = cl.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    need = cl.window_decode_bytes(w.model, contexts, w.engine.get("dtype", "bfloat16"))
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / seconds
