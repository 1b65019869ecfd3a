"""Model step: (weight bytes of every traced step + state bytes read and
written for its live decode rows) / peak HBM bandwidth / the steps'
device time. Bytes from harness/counts_brumby.py (lower bounds: true
features, activations and the normaliser left out), rows from the tap,
time from the trace. Nothing to read for another family."""
from benchmarks.harness import counts_brumby, readers


def compute(w):
    if w.config.get("family") != "brumby":
        return None
    durs = readers.program_durations_ms(w, readers.STEP_PROGRAMS)
    if not durs:
        return None
    need = len(durs) * counts_brumby.decode_weight_bytes(
        w.model, w.engine.get("dtype", "bfloat16")
    ) + 2 * readers.traced_decode_rows(w) * counts_brumby.state_bytes_per_row(w.model)
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / (sum(durs) / 1e3)
