"""Kernels: the full layers' decode attention launches' share of their
roofline, which HBM bandwidth bounds: (K and V bytes of the whole context
of every decode row of the traced span, over the two full layers:
harness/counts_mimo.py, TRUE bytes: 4 KV heads of 192 + 128 lanes,
whatever the pool pads) / peak HBM bandwidth / summed device time of the
"paged_attention_kernel" custom calls: the accepted decode kernel at a new
head shape (a query group of 16, key rows wider than value rows). A
program without the kernel gives nothing."""
from benchmarks.harness import counts_mimo as cm


def compute(w):
    if w.trace is None or w.config.get("family") != "mimo":
        return None
    seconds = cm.kernel_seconds(w, cm.FULL_DECODE_KERNEL)
    contexts = cm.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    need = cm.full_decode_bytes(w.model, contexts, w.engine.get("dtype", "bfloat16"))
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / seconds
