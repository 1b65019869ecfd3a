"""Kernels: the MLA decode kernel's share of its roofline, which neither
side bounds alone (218 FLOP a cached byte beside a ridge of 240): the
larger of (latent bytes of the traced decode rows' contexts / 819 GB/s)
and (their absorbed-form FLOPs / 197 TFLOP/s), over the summed device
time of the "mla_paged_attention_kernel" custom calls. Bytes and FLOPs
from harness/counts_deepseek.py (true widths). A program without the
kernel gives nothing."""
from benchmarks.harness import counts_deepseek as cd


def compute(w):
    if w.trace is None or w.config.get("family") != "deepseek":
        return None
    seconds = cd.kernel_seconds(w, "%mla_paged_attention_kernel")
    contexts = cd.traced_decode_contexts(w)
    if not seconds or not contexts:
        return None
    peaks = w.counts.peaks(w.device_kind)
    dtype = w.engine.get("dtype", "bfloat16")
    hbm_s = sum(contexts) * cd.latent_bytes_per_token(w.model, dtype) / peaks["hbm_bytes_per_s"]
    mxu_s = sum(cd.decode_attention_flops(w.model, c) for c in contexts) / peaks["flops_bf16"]
    return 100.0 * max(hbm_s, mxu_s) / seconds
