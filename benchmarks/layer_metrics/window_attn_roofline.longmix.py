"""Kernels: the window layers' decode attention launches' share of their
roofline, which HBM bandwidth bounds: (K and V bytes of the last
min(context, 128) tokens of every decode row of the traced span, over the
five window layers: harness/counts_mimo.py, TRUE bytes: 192 + 128 lanes a
KV head, whatever the pool pads) / peak HBM bandwidth / summed device time
of the "window_paged_attention_kernel" custom calls. The kernel fetches
whole blocks, two where the window straddles one, so it cannot pass about
a half. A program without the kernel gives nothing."""
from benchmarks.harness import counts_mimo as cm


def compute(w):
    if w.trace is None or w.config.get("family") != "mimo":
        return None
    seconds = cm.kernel_seconds(w, cm.WINDOW_DECODE_KERNEL)
    contexts = cm.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    need = cm.window_decode_bytes(w.model, contexts, w.engine.get("dtype", "bfloat16"))
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / seconds
