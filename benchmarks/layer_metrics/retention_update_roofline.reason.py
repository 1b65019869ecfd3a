"""Kernels: the decode state update's share of its roofline, which HBM
bandwidth bounds: (state bytes read and written for the live decode rows
of the traced span: one row a token the engine emitted there after its
request's first, from the tap; bytes from harness/counts_brumby.py, true
features, the normaliser left out) / peak HBM bandwidth / summed device
time of the "retention_update_kernel" custom calls. A program without
the kernel gives nothing."""
from benchmarks.harness import counts_brumby, readers

KERNEL = "%retention_update_kernel"


def compute(w):
    if w.trace is None:
        return None
    kernel_ns = sum(v for k, v in w.trace["ops"].items() if k.startswith(KERNEL))
    rows = readers.traced_decode_rows(w)
    if not kernel_ns or not rows:
        return None
    need = 2 * rows * counts_brumby.state_bytes_per_row(w.model)
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / (kernel_ns / 1e9)
