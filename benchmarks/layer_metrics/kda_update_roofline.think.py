"""Kernels: the KDA decode state update's share of its roofline, which HBM
bandwidth bounds: (delta-rule state bytes read and written for the decode
rows of the traced span: one row a token the tap saw emitted there after
its request's first, whatever its context; bytes from
harness/counts_solar.py, the state's true numbers, convolution rows,
columns and activations left out) / peak HBM bandwidth / summed device time
of the "kda_update_kernel" custom calls. A program without the kernel gives
nothing."""
from benchmarks.harness import counts_solar as cs

KERNEL = "%kda_update_kernel"


def compute(w):
    if w.trace is None or w.config.get("family") != "solar":
        return None
    seconds = cs.kernel_seconds(w, KERNEL)
    rows = len(cs.traced_decode_contexts(w))
    if not seconds or not rows:
        return None
    return 100.0 * w.counts.hbm_time_s(cs.update_kernel_bytes(w.model, rows), w.device_kind) / seconds
