"""Kernels: the Mamba-2 decode state update's share of its roofline at TWO
B/C groups, state 256 and 128-lane heads, which HBM bandwidth bounds: (SSM
state bytes read and written for the decode rows of the traced span: one
row a token the tap saw emitted there after its request's first, whatever
its context, over the nine blocks; bytes from harness/counts_falcon_h1.py,
the state's true numbers, the B and C planes, convolution rows and
activations left out) / peak HBM bandwidth / summed device time of the
"mamba_update_kernel" custom calls. A program without the kernel, or
another family, gives nothing."""
from benchmarks.harness import counts_falcon_h1 as cf


def compute(w):
    if w.trace is None or w.config.get("family") != "falcon_h1":
        return None
    seconds = cf.kernel_seconds(w, cf.UPDATE_KERNEL)
    rows = len(cf.traced_decode_contexts(w))
    if not seconds or not rows:
        return None
    return 100.0 * w.counts.hbm_time_s(cf.update_kernel_bytes(w.model, rows), w.device_kind) / seconds
