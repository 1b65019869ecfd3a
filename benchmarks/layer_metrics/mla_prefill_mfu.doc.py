"""Kernels: the MLA prefill kernel's share of the chip's peak: FLOPs of
the traced chunks' attention in the form the kernel computes (absorbed:
2 x (576 + 512) a head and causal pair; harness/counts_deepseek.py) / 197
TFLOP/s / summed device time of the "mla_prefill_kernel" custom calls.
Chunks as counts_deepseek.traced_chunk_starts reads them. A program
without the kernel gives nothing."""
from benchmarks.harness import counts_deepseek as cd


def compute(w):
    if w.trace is None or w.config.get("family") != "deepseek":
        return None
    seconds = cd.kernel_seconds(w, "%mla_prefill_kernel")
    chunk = int(w.engine["max_prefill_tokens"])
    starts = cd.traced_chunk_starts(w, chunk)
    if not seconds or not starts:
        return None
    flops = sum(cd.chunk_attention_flops(w.model, s, chunk) for s in starts)
    return 100.0 * flops / w.counts.peaks(w.device_kind)["flops_bf16"] / seconds
