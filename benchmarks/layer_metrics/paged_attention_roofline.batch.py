"""Kernels: the paged-attention kernel's share of its roofline, which
HBM bandwidth bounds at decode: (K and V bytes of the contexts of the
tokens the engine emitted inside the traced span, from the tap and
counts.py) / peak HBM bandwidth / summed device time of the
"paged_attention_kernel" custom calls in the trace. The bytes are a lower
bound on what the kernel reads (blocks are padded to 128 tokens)."""
from benchmarks.harness import readers

KERNEL = "%paged_attention_kernel"


def compute(w):
    if w.trace is None:
        return None
    kernel_ns = sum(v for k, v in w.trace["ops"].items() if k.startswith(KERNEL))
    contexts = readers.traced_token_contexts(w)
    if not kernel_ns or not contexts:
        return None
    need = sum(contexts) * w.counts.kv_bytes_per_token(
        w.model, w.engine.get("dtype", "bfloat16"), int(w.engine.get("tp_size", 1))
    )
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / (kernel_ns / 1e9)
