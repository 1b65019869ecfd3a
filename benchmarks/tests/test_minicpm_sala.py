"""The MiniCPM-SALA family's additions (PR 56): its counts by hand, its
configuration against the catalog's row, its traffic mix through the
generator, its seven readers over a made-up window (and over a program or a
family that lacks what they read), a whole rehearsal on the CPU with
`correct` true, and its controls at a size the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_minicpm_sala as cs, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "minicpm-sala.longdoc-steady"
READERS = ("step_mfu.longdoc", "sparse_attn_roofline.longdoc", "sparse_select_roofline.longdoc",
           "lightning_update_roofline.longdoc", "kv_selected_share.longdoc",
           "attn_rows_selected_share.longdoc", "state_slots_live.longdoc")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def run(*args, script=("benchmarks", "run.py"), timeout=1500):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, *script), *args],
                          cwd=ROOT, env=e, capture_output=True, text=True, timeout=timeout)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_counts_by_hand():
    m = load("configs", "minicpm-sala")
    c = cs.param_counts(m)
    assert cs.kinds(m) == {"sparse": 2, "lightning": 6}
    # ISSUE 56's arithmetic: a lightning layer 285.2 M, a sparse layer 253.8 M, the vocabulary 601.7 M
    assert c["mlp"] == 3 * 4096 * 16384 == 201_326_592
    assert c["lightning"] == 5 * 4096 * 4096 + 2 * 128 + 4096 == 83_890_432
    assert c["sparse"] == 3 * 4096 * 4096 + 2 * 4096 * 256 + 256 == 52_429_056
    assert c["embed"] == c["head"] == 73448 * 4096 == 300_843_008
    assert c["total"] == 6 * (c["lightning"] + c["mlp"]) + 2 * (c["sparse"] + c["mlp"]) + 2 * c["embed"]
    assert 2_820_000_000 < c["total"] < 2_821_000_000  # 2,820 M: 5.64 GB
    assert cs.decode_weight_bytes(m) == 2 * (c["layers"] + c["head"])  # 5.04 GB: a floor of 6.2 ms
    assert 6.1e-3 < counts.hbm_time_s(cs.decode_weight_bytes(m), "TPU v5 lite") < 6.2e-3
    # a live row: 32 x 128 x 128 float32 a lightning layer, read AND written
    assert cs.state_bytes_per_row(m) == 6 * 32 * 128 * 128 * 4 == 12_582_912
    assert cs.update_kernel_bytes(m, 20) == 2 * 20 * 12_582_912  # 25 MB a live row a step
    assert cs.kv_bytes_per_token(m) == 2 * 2 * 2 * 128 * 2 == 2048
    assert cs.compressed_bytes_per_token(m) == 2 * 2 * 128 * 4 / 16 == 128
    # a row past dense_len attends 63 whole blocks and its own up to itself, whatever its context
    assert cs.attended_tokens(m, 8192) == 8192 and not cs.selects(m, 8192)
    assert cs.attended_tokens(m, 8193) == 63 * 64 + 1 and cs.selects(m, 8193)
    assert cs.attended_tokens(m, 32768) == 64 * 64 == cs.attended_tokens(m, 49152)
    assert cs.visible_keys(m, 31) == 0 and cs.visible_keys(m, 32) == 1 and cs.visible_keys(m, 32768) == 2047
    # ISSUE 56 by the bytes: a decode row at 32,768 reads 2 x 2.1 MB of selected K and V a
    # sparse layer and 1.0 MB of compressed keys (float32 here: twice the issue's bfloat16)
    assert cs.stage2_bytes(m, [], 4096, [32768]) == 4096 * 2048 == 2 * 2 * 2_097_152
    assert cs.stage1_bytes(m, [], 4096, [32768]) == 2047 * 2 * 2 * 128 * 4
    # a 4,096-row chunk at 28,672: every row selects; the compressed keys are read once
    rows = range(28673, 32769)
    assert cs.stage2_bytes(m, [28672], 4096, []) == sum(cs.attended_tokens(m, r) for r in rows) * 2048
    assert cs.stage1_bytes(m, [28672], 4096, []) == 2047 * 2048
    assert cs.stage1_flops(m, [28672], 4096, []) == sum(cs.visible_keys(m, r) for r in rows) * 2 * 2 * 128 * 32
    # a chunk under dense_len takes the flash launch: nothing of it is stage 2's
    assert cs.stage2_bytes(m, [0, 4096], 4096, []) == 0 == cs.stage1_flops(m, [0, 4096], 4096, [])
    assert cs.lightning_flops_per_token(m) == 6 * 32 * 128 * 128
    assert cs.model_flops(m, [], 4096, [9000]) == (
        cs.token_matrix_flops(m) + 6 * cs.lightning_flops_per_token(m)
        + cs.attended_tokens(m, 9000) * 2 * 4 * 128 * 32
        + cs.visible_keys(m, 9000) * 2 * 2 * 128 * 32 + cs.head_flops(m))


def test_the_counts_hold_against_the_programs_parameter_tree():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import family as family_mod
    from xllm_service_tpu.models import granite

    m = load("configs", "minicpm-sala")
    cfg = family_mod.load(m).model_config(m["name"], m)
    tree = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    norms = size(tree["layers"]["attn_norm"]) + size(tree["layers"]["mlp_norm"]) + size(tree["final_norm"])
    assert size(tree) - norms == cs.param_counts(m)["total"]
    S, ck = granite.state_shapes(cfg, 1, 1)
    assert int(np.prod(S)) * 4 == cs.state_bytes_per_row(m)
    assert int(np.prod(ck)) * 4 == cs.compressed_bytes_per_token(m) * 64


def test_the_configuration_is_the_published_one_cut_as_issue_56_says():
    m = load("configs", "minicpm-sala")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
        assert m["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if m.get(k) != v]
        assert differs == ["num_hidden_layers"] == m["reduced"]
    assert (m["num_hidden_layers"], m["num_hidden_layers_published"], m["layer_ids"]) == (
        8, 32, list(range(9, 17)))
    assert m["vocab_size"] == 73448 and m["engine"]["block_size"] == m["sparse_config"]["block_size"]
    assert m["engine"]["max_prefill_tokens"] == 4096 == m["engine"]["prefill_buckets"][0]
    # the check's prompts (1 to 3 chunks and 64 tokens) cross dense_len from 2 chunks on
    assert 2 * 4096 + 64 > m["sparse_config"]["dense_len"] >= 2 * 4096
    assert any("DEPARTURE: the switch" in a for a in m["assumed"])
    assert any("DEPARTURE: stage 1's softmax" in a for a in m["assumed"])
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in man["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == m["source"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["why"] == load("cells", CELL)["why"]
    mine = [e for e in man["per_layer"] if e["name"].endswith(".longdoc")]
    assert sorted(e["name"] for e in mine) == sorted(READERS)
    assert all(e["workloads"] == [CELL] and e["moves"] == "tpot_p90_ms" for e in mine)


def test_the_mix_is_whole_chunks_that_all_cross_dense_len():
    cell, traffic = load("cells", CELL), load("traffic", "longdoc-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 2000.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] == 8192 and lens[-1] <= 49152 and all(n % 4096 == 0 for n in lens)
    assert lens[len(lens) // 2] == 16384 and 19000 < sum(lens) / len(lens) < 21500
    assert 0.10 < sum(n >= 32768 for n in lens) / len(lens) < 0.20
    assert 0.08 < sum(n == 8192 for n in lens) / len(lens) < 0.18
    # the share of the mix's prompt rows past dense_len: what attn_rows_selected_share reads
    assert 0.55 < sum(n - 8192 for n in lens) / sum(lens) < 0.65
    outs = sorted(r["out_len"] for r in plan["requests"])
    assert outs[0] >= 16 and outs[-1] <= 512 and 85 <= outs[len(outs) // 2] <= 115
    assert plan["loop"] == "open" and plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 2000.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws = traffic["warm_shapes"]
    assert min(ws["background_prompts"]) >= 8192  # a row past dense_len before the window
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.03)
    assert len(cell["sweep"]["points"]) >= 4
    assert {p["rate_per_s"] for p in cell["sweep"]["points"]} >= {cell["knee_per_s"]}


class FakeWindow:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.counts = counts
        self.device_kind = "TPU v5 lite"
        self.checks = {}

    model = property(lambda self: self.config)
    engine = property(lambda self: self.config["engine"])

    def counter_delta(self, name):
        return self.deltas.get(name)


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("r_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def window(with_program=True, config="minicpm-sala"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the two that end in
        # [10, 12) start at 0 (dense) and 4,096 (dense); see `late` for a selected chunk
        "a": {"prompt_len": 16384, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 12288 + 5, 6, 7
        "b": {"prompt_len": 12288, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%lightning_update_kernel.3 = ...": 0.3e6, "%paged_attention_kernel.5 = ...": 0.2e6} \
        if with_program else {"%fusion.5 = ...": 0.05e6}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [150e6, 150e6], "_decode_impl": [9e6]}}
    deltas = {}
    if with_program:
        deltas = {"xllm_engine_attn_rows_selected_total": 600.0, "xllm_engine_attn_rows_dense_total": 400.0,
                  "xllm_engine_sparse_pages_selected_total": 600 * 64.0,
                  "xllm_engine_sparse_pages_live_total": 600 * 256.0,
                  "xllm_engine_state_slots_in_use_sum": 12000.0,
                  "xllm_engine_state_slots_in_use_count": 1000.0}
    w = FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0,
                   deltas=deltas, counters_start={}, counters_end={})
    if with_program:  # what harness/regions.py would have joined
        w._regions = {"ns": {"attn_select": 0.1e6, "attn": 0.5e6}, "total_ns": 300e6, "steps": 3}
    return w


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    ctx = [12293, 12294, 12295]
    # three decode rows: 2 x 12.6 MB of state each in 0.3 ms of launches
    assert reader("lightning_update_roofline.longdoc").compute(w) == pytest.approx(
        100 * 3 * 2 * 12_582_912 / 819e9 / 0.3e-3)
    # ... their selected tokens' K and V (both chunks of the span are under dense_len) in 0.2 ms
    need = sum(cs.attended_tokens(m, c) for c in ctx) * 2048
    assert reader("sparse_attn_roofline.longdoc").compute(w) == pytest.approx(
        100 * need / 819e9 / 0.2e-3)
    # ... and their visible compressed keys in 0.1 ms of the region
    keys = sum(cs.visible_keys(m, c) for c in ctx) * 2048
    assert reader("sparse_select_roofline.longdoc").compute(w) == pytest.approx(
        100 * keys / 819e9 / 0.1e-3)
    model = cs.model_flops(m, [0, 4096], 4096, ctx)
    assert reader("step_mfu.longdoc").compute(w) == pytest.approx(100 * model / 197e12 / 0.309)
    assert reader("kv_selected_share.longdoc").compute(w) == pytest.approx(25.0)
    assert reader("attn_rows_selected_share.longdoc").compute(w) == pytest.approx(60.0)
    assert reader("state_slots_live.longdoc").compute(w) == pytest.approx(12.0)
    for name in READERS[:4]:
        assert 0 < reader(name).compute(w) < 100, name


def test_a_selected_chunk_is_stage_twos_work_and_flops_can_bound_it():
    """A 4,096-row chunk past dense_len: 8,192 (row, KV head) launches' worth
    of selected pages; the roofline takes the larger of its two times."""
    m = load("configs", "minicpm-sala")
    nbytes, flops = cs.stage2_bytes(m, [28672], 4096, []), cs.stage2_flops(m, [28672], 4096, [])
    assert 33e9 < nbytes < 35e9 and 0.5e12 < flops < 0.6e12  # ISSUE 56: 17 GB and 0.27 TFLOP a sparse layer, two of them
    w = window()
    assert cs.roofline_seconds(w, flops, nbytes) == pytest.approx(nbytes / 819e9)
    assert cs.roofline_seconds(w, 100 * flops, nbytes) == pytest.approx(100 * flops / 197e12)


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 56 cannot build the configuration at all; a
    program without the kernels' names, the region or the counters, another
    family's window, and a run without a trace all read as nothing and
    raise nothing."""
    w = window(with_program=False)
    for name in READERS[1:6]:
        assert reader(name).compute(w) is None, name
    other = window(config="falcon-h1-34b")
    for name in READERS[:4] + READERS[6:]:
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in READERS[:4]:
        assert reader(name).compute(w) is None, name
    # ... and the other hybrids' readers give nothing in this family's window
    for name in ("kda_update_roofline.think", "step_mfu.think", "ssm_update_roofline.assist",
                 "step_mfu.dialog", "state_slots_live.dialog", "full_attn_roofline.longmix"):
        assert reader(name).compute(window()) is None, name


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-minicpm-sala-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"lightning-xla"' in p.stdout and "select-xla+gather" in p.stdout
    # the check's prompts cross the switch: the engine booked selected rows
    assert res["metrics"]["attn_rows_selected_share.longdoc"]["value"] > 50.0
    assert res["metrics"]["kv_selected_share.longdoc"]["value"] < 35.0


def control(mode):
    args = ["--config", "rehearse-minicpm-sala-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896", "--long-tokens", "64"]
    p = run(*args, script=("benchmarks", "tests", "control_minicpm_sala.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["dense", "no-local", "stale-ck", "state-bf16", "no-rope",
                                  "zero-carry", "decay-l0", "w-int8"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)


def test_the_timed_sizes_stay_sound(sound):
    long = control("long")
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 100 * max(sound["logprob_mse_max"], 1e-12)


def test_the_draws_gains_are_read_by_the_reference():
    p = run("--config", "rehearse-minicpm-sala-tiny", "--mode", "shares", "--seeds", "21", "--rehearse",
            script=("benchmarks", "tests", "control_minicpm_sala.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    rms = last_json(p.stdout)["rms_by_layer"]
    assert 0.7 < rms["stream"][0] < 1.3  # h0 has unit RMS
    for branch in ("mixer", "mlp"):  # no branch vanishes from the stream
        assert all(0.03 < r / s < 3.0 for r, s in zip(rms[branch], rms["stream"])), (branch, rms)
