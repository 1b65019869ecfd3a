"""Kernels: the lightning decode state update's share of its roofline, which
HBM bandwidth bounds: (state bytes read and written for the decode rows of
the traced span: one row a token the tap saw emitted there after its
request's first, whatever its context, over the six lightning layers; bytes
from harness/counts_minicpm_sala.py, the state's true 32 x 128 x 128 float32
a layer, q, k, v and the outputs left out) / peak HBM bandwidth / summed
device time of the launches counts_minicpm_sala.UPDATE_KERNELS names. A
program without the kernel, or another family, gives nothing."""
from benchmarks.harness import counts_minicpm_sala as cs


def compute(w):
    if w.trace is None or w.config.get("family") != "minicpm_sala":
        return None
    seconds = cs.kernel_seconds(w, *cs.UPDATE_KERNELS)
    rows = len(cs.traced_decode_contexts(w))
    if not seconds or not rows:
        return None
    return 100.0 * w.counts.hbm_time_s(cs.update_kernel_bytes(w.model, rows), w.device_kind) / seconds
