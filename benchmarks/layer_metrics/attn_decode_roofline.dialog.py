"""Kernels: the paged decode attention launches' share of their roofline at
a query group of 5 (20 query heads over 4 KV heads of 128), which HBM
bandwidth bounds: (K and V bytes of the whole context of every decode row
of the traced span, over the nine blocks: harness/counts_falcon_h1.py, TRUE
bytes, whatever a 128-token block pads) / peak HBM bandwidth / summed
device time of the "paged_attention_kernel" custom calls. A program
without the kernel, or another family, gives nothing."""
from benchmarks.harness import counts_falcon_h1 as cf


def compute(w):
    if w.trace is None or w.config.get("family") != "falcon_h1":
        return None
    seconds = cf.kernel_seconds(w, cf.DECODE_KERNEL)
    contexts = cf.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    need = cf.decode_kv_bytes(w.model, contexts, w.engine.get("dtype", "bfloat16"))
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / seconds
