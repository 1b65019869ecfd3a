"""Kernels: stage 2 of the block-sparse layers (attention over the pages a
query group selected) as a share of its roofline, counted from the WORK: the
larger of (the true K and V bytes of the tokens the traced rows attend: a
decode row and a chunk row past dense_len its 64 selected blocks' rows, the
own block's up to the row / peak HBM bandwidth) and (their score and context
FLOPs / 197 TFLOP/s), over the summed device time of the launches that did
it (harness/counts_minicpm_sala.py STAGE2_KERNELS: the decode kernel, a KV
head a row). A kernel that shares pages between neighbouring rows reads the
same work in less time, under whatever name the counts file then lists. A
program without the launches, or another family, gives nothing."""
from benchmarks.harness import counts_minicpm_sala as cs


def compute(w):
    if w.trace is None or w.config.get("family") != "minicpm_sala":
        return None
    seconds = cs.kernel_seconds(w, *cs.STAGE2_KERNELS)
    starts, chunk, contexts = cs.traced_rows(w)
    if not seconds or not (starts or contexts):
        return None
    need = cs.roofline_seconds(
        w, cs.stage2_flops(w.model, starts, chunk, contexts),
        cs.stage2_bytes(w.model, starts, chunk, contexts, w.engine.get("dtype", "bfloat16")))
    return 100.0 * need / seconds
