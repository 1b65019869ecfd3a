"""Kernels: the Mamba-2 decode state update's share of its roofline, which
HBM bandwidth bounds: (SSM state bytes read and written for the decode rows
of the traced span: one row a token the tap saw emitted there after its
request's first, whatever its context; bytes from harness/counts_granite.py,
the state's true numbers, convolution rows and activations left out) / peak
HBM bandwidth / summed device time of the "mamba_update_kernel" custom
calls. A program without the kernel gives nothing."""
from benchmarks.harness import counts_granite as cg

KERNEL = "%mamba_update_kernel"


def compute(w):
    if w.trace is None or w.config.get("family") != "granite":
        return None
    seconds = cg.kernel_seconds(w, KERNEL)
    rows = len(cg.traced_decode_contexts(w))
    if not seconds or not rows:
        return None
    return 100.0 * w.counts.hbm_time_s(cg.update_kernel_bytes(w.model, rows), w.device_kind) / seconds
