"""Kernels: stage 1 of the block-sparse layers (scores over the compressed
keys, the pooling to blocks, the top-64, the selected table) as a share of
its roofline: the larger of (the compressed-key bytes the traced rows that
select must read: a decode row its visible keys, a chunk its last row's once
/ peak HBM bandwidth) and (their q . c_j FLOPs / 197 TFLOP/s), over the
device time of the region `attn_select` (harness/regions.py): by region, so
a later kernel under any name still reads. The softmax, the pooling and the
top-k are not in the numerator: they are the latency this share shows. A
program that names no such region, or another family, gives nothing."""
from benchmarks.harness import counts_minicpm_sala as cs
from benchmarks.harness import regions


def compute(w):
    if w.trace is None or w.config.get("family") != "minicpm_sala":
        return None
    r = regions.window_regions(w)
    seconds = (r or {}).get("ns", {}).get(cs.SELECT_REGION, 0.0) / 1e9
    starts, chunk, contexts = cs.traced_rows(w)
    if not seconds or not (starts or contexts):
        return None
    need = cs.roofline_seconds(
        w, cs.stage1_flops(w.model, starts, chunk, contexts),
        cs.stage1_bytes(w.model, starts, chunk, contexts))
    return 100.0 * need / seconds
