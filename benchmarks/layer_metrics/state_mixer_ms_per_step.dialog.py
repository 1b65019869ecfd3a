"""Model step: device milliseconds a step program's execution (mixed or
decode) spends in the region `state_mixer` (harness/regions.py): in a
parallel block the Mamba-2 branch (`W_in`, the lane multipliers, the
convolution, the update kernel or the chunk scan, the gated norm, `W_out`)
and the ONE residual add of both branches. Read for this family alone; a
program that names no regions gives nothing."""
from benchmarks.harness import regions


def compute(w):
    if w.config.get("family") != "falcon_h1":
        return None
    return regions.ms_per_step(w, ("state_mixer",))
