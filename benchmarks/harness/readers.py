"""Helpers shared by the per-layer readers of benchmarks/layer_metrics (a reader a
later PR adds may use them or not)."""

from benchmarks.harness import stats

# Programs of one engine step that emits decode tokens, by the names the
# TPU trace shows (PERF.md section 3).
DECODE_PROGRAMS = ("_decode_impl",)
STEP_PROGRAMS = ("_decode_impl", "_mixed_impl")


def client_ttfts_ms(w):
    """Due time to first streamed chunk for every measured request; None
    for one that failed."""
    return [
        (r["chunk_times"][0] - r["due"]) * 1e3 if w.ok(r) else None
        for r in w.measured()
    ]


def percentile_with_failures(vals, q):
    return stats.percentile(stats.with_failures(vals), q) if vals else None


def hist_mean(w, name):
    """Mean of a Prometheus histogram over the window, from its _sum and
    _count at window start and end."""
    s, c = w.counter_delta(name + "_sum"), w.counter_delta(name + "_count")
    return None if not c or s is None else s / c


def tap_of(w, r):
    return w.taps.get(r.get("key"))


def engine_ttfts_ms(w):
    out = []
    for r in w.measured():
        tap = tap_of(w, r)
        if w.ok(r) and tap and tap["times"]:
            out.append((tap["times"][0] - tap["t_add"]) * 1e3)
    return out


def median(vals):
    return stats.percentile(vals, 50) if vals else None


def program_durations_ms(w, programs):
    if w.trace is None:
        return []
    return [
        d / 1e6 for p in programs
        for d in w.trace["program_durations_ns"].get(p, ())
    ]


def traced_emissions(w):
    """(tap, tokens of the request emitted before, tokens) of every
    emission the tap saw while the profiler was RECORDING: run.py's
    `trace_span` runs from when `start_trace` returned to when
    `stop_trace` was called, not to when the profile had been written
    (seconds later: a count over that span held 2.3x the traced steps'
    tokens, PERF.md PR 31 and PR 33). The one place that joins the tap's
    clock to the trace; it checks itself against the program's own
    counter in every traced run that uses it (`check_traced_tokens`)."""
    if w.trace is None or w.trace_span is None:
        return []
    a, b = (w.t_zero + t for t in w.trace_span)
    out = []
    for tap in w.taps.values():
        seen = 0
        for t, n in zip(tap["times"], tap["counts"]):
            if a <= t < b:
                out.append((tap, seen, n))
            seen += n
    check_traced_tokens(w, out)
    return out


def traced_token_contexts(w):
    """Context length of every token the traced steps emitted: prompt +
    tokens emitted before it."""
    return [
        tap["prompt_len"] + seen + k
        for tap, seen, n in traced_emissions(w) for k in range(n)
    ]


def _decode_rows(emissions) -> int:
    return sum(n - (1 if seen == 0 else 0) for _, seen, n in emissions)


def traced_decode_rows(w) -> int:
    """Decode rows of the traced steps: tokens they emitted that were
    not their request's first (that one comes out of the prefill chunk,
    not out of a decode row)."""
    return _decode_rows(traced_emissions(w))


TRACED_TOKENS_RANGE = (0.8, 1.25)


def check_traced_tokens(w, emissions) -> None:
    """Decode rows counted from the tap / (step programs in the trace x
    the counter's mean decode rows a step) has to be about 1: the counter
    is the whole window's mean and the trace 3 s of it, so a few percent
    of play are honest; the fault this guards is a factor of 2.3. Written
    to `w.checks`, which run.py logs and holds to the range."""
    steps = len(program_durations_ms(w, STEP_PROGRAMS))
    mean = hist_mean(w, "xllm_engine_decode_batch_size")
    if not steps or not mean:
        return
    rows = _decode_rows(emissions)
    lo, hi = TRACED_TOKENS_RANGE
    w.checks["traced_rows_ratio"] = {
        "value": rows / (steps * mean), "low": lo, "high": hi,
        "rows_from_tap": rows, "traced_steps": steps, "counter_rows_per_step": mean,
    }


def step_bytes_share(w, programs, with_weights):
    """(bytes the traced steps need) / peak HBM bandwidth / their device
    time, in percent."""
    durs = program_durations_ms(w, programs)
    if not durs:
        return None
    tp = int(w.engine.get("tp_size", 1))
    dtype = w.engine.get("dtype", "bfloat16")
    kv = sum(traced_token_contexts(w)) * w.counts.kv_bytes_per_token(w.model, dtype, tp)
    need = kv
    if with_weights:
        need += len(durs) * w.counts.decode_weight_bytes(w.model, dtype, tp)
    return 100.0 * w.counts.hbm_time_s(need, w.device_kind) / (sum(durs) / 1e3)
