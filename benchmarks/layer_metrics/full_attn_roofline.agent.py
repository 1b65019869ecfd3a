"""Kernels: the full layers' decode attention launches' share of their
roofline, which HBM bandwidth bounds: (K and V bytes of the whole context
of every decode row of the traced span, over the two full layers:
harness/counts_laguna.py, TRUE bytes: 8 KV heads of 128 + 128 lanes) / peak
HBM bandwidth / summed device time of the "paged_attention_kernel" custom
calls: the accepted decode kernel at a query group of 6 (48 heads over 8,
padded to 8 sublanes: the pad is not counted). A program without the kernel
gives nothing."""
from benchmarks.harness import counts_laguna as cl


def compute(w):
    if w.trace is None or w.config.get("family") != "laguna":
        return None
    seconds = cl.kernel_seconds(w, cl.FULL_DECODE_KERNEL)
    contexts = cl.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    need = cl.full_decode_bytes(w.model, contexts, w.engine.get("dtype", "bfloat16"))
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / seconds
