"""The one helper that joins the tap's clock to the trace
(readers.traced_emissions), on a hand-made window: 125 rows a step, a
step every 50 ms, 3.0 s traced, the profile written 4 s after the stop
was called. Counted over the recording it holds the traced steps' tokens;
counted to where the profile was written (run.py's `trace_span` before PR
33) it holds 2.3x, and the helper's check of itself refuses that."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import counts, readers, stack

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROWS, STEP_S, T_ZERO = 125, 0.05, 1000.0
RECORDING = (8.0, 11.0)  # start_trace returned, stop_trace called
WRITTEN = 15.0  # stop_trace returned


def reader(name):
    return run.load_metric("layer_metrics", name)


def config_file(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def window(span, rows_mean=ROWS):
    """ROWS requests of 256 prompt tokens that each emit a token a step
    from t = 0 to t = 20 s; 60 step programs traced."""
    steps = int(20.0 / STEP_S)
    taps = {
        str(i): {"prompt_len": 256, "counts": [1] * steps,
                 "times": [T_ZERO + (k + 0.5) * STEP_S for k in range(steps)]}
        for i in range(ROWS)
    }
    trace = {
        "window_s": 3.0,
        "program_durations_ns": {"_decode_impl": [23.4e6] * 42, "_mixed_impl": [35.2e6] * 18},
        "ops": {"%paged_attention_kernel.7 = custom-call": 0.7e9,
                "%retention_update_kernel.3 = custom-call": 1.9e9},
    }
    hist = "xllm_engine_decode_batch_size_sum %d\nxllm_engine_decode_batch_size_count %d\n"
    config = config_file("qwen2.5-3b")
    return run.Window(
        taps=taps, trace=trace, trace_span=span, t_zero=T_ZERO, config=config, counts=counts,
        device_kind="TPU v5 lite",
        counters_start=stack.parse_metrics(hist % (0, 0)),
        counters_end=stack.parse_metrics(hist % (rows_mean * 900, 900)),
    )


def test_the_helper_counts_the_traced_steps_tokens():
    w = window(RECORDING)
    traced = 60 * ROWS  # step programs in the trace x rows a step
    assert readers.traced_decode_rows(w) == pytest.approx(traced, rel=0.02)
    contexts = readers.traced_token_contexts(w)
    assert len(contexts) == readers.traced_decode_rows(w)
    # a row's context: its prompt + what it has emitted, 160 to 219 tokens here
    assert min(contexts) == 256 + 160 and max(contexts) == 256 + 219
    c = w.checks["traced_rows_ratio"]
    assert c["value"] == pytest.approx(1.0, rel=0.02) and not run.checks_off(w.checks)
    assert (c["low"], c["high"]) == (0.8, 1.25) and c["traced_steps"] == 60


def test_the_interval_to_where_the_profile_was_written_counted_2_3_times_as_much():
    old = window((RECORDING[0], WRITTEN))
    assert readers.traced_decode_rows(old) / (60 * ROWS) == pytest.approx(7.0 / 3.0, rel=0.02)
    assert old.checks["traced_rows_ratio"]["value"] == pytest.approx(2.33, rel=0.02)
    assert run.checks_off(old.checks) == ["traced_rows_ratio"]


@pytest.mark.parametrize("rows_mean,off", [(ROWS, False), (ROWS / 0.79, True), (ROWS / 0.81, False),
                                           (ROWS / 1.24, False), (ROWS / 1.26, True)])
def test_the_check_refuses_a_ratio_outside_its_range(rows_mean, off):
    w = window(RECORDING, rows_mean=rows_mean)
    readers.traced_emissions(w)
    assert bool(run.checks_off(w.checks)) is off


def test_a_first_token_is_no_decode_row_and_no_trace_gives_nothing():
    w = window(RECORDING)
    for tap in list(w.taps.values())[:10]:  # ten requests whose first token falls in the trace
        tap["times"], tap["counts"] = tap["times"][170:], tap["counts"][170:]
    assert len(readers.traced_token_contexts(w)) - readers.traced_decode_rows(w) == 10
    w = window(RECORDING)
    w.trace = None
    assert readers.traced_emissions(w) == [] and w.checks == {}
    w = window(None)
    assert readers.traced_emissions(w) == [] and readers.traced_decode_rows(w) == 0


def test_the_two_batch_shares_by_hand():
    w = window(RECORDING)
    m = w.config
    per_token = counts.kv_bytes_per_token(m)  # 36 layers x 2 KV heads x 128 x K and V x 2 B
    assert per_token == 36 * 2 * 128 * 2 * 2 == 36864
    kv = sum(readers.traced_token_contexts(w)) * per_token
    assert reader("paged_attention_roofline.batch").compute(w) == pytest.approx(
        100.0 * kv / 819e9 / 0.7)
    steps_s = (42 * 23.4 + 18 * 35.2) / 1e3
    want = 100.0 * (kv + 60 * counts.decode_weight_bytes(m)) / 819e9 / steps_s
    assert reader("decode_hbm_share.batch").compute(w) == pytest.approx(want)
    assert 0 < want < 100


def test_the_reason_readers_count_through_the_same_helper():
    from benchmarks.harness import counts_brumby

    brumby = config_file("brumby-14b")
    w = window(RECORDING)
    w.config = brumby
    rows = readers.traced_decode_rows(w)
    need = 2 * rows * counts_brumby.state_bytes_per_row(brumby)
    assert reader("retention_update_roofline.reason").compute(w) == pytest.approx(
        100.0 * need / 819e9 / 1.9)
    old = window((RECORDING[0], WRITTEN))
    old.config = brumby
    assert reader("retention_update_roofline.reason").compute(old) == pytest.approx(
        7.0 / 3.0 * 100.0 * need / 819e9 / 1.9, rel=0.02)
    assert "traced_rows_ratio" in w.checks
    assert not hasattr(counts_brumby, "live_decode_rows")  # one helper, in readers
