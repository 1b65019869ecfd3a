"""The Solar-Open2 family's additions (PR 46): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its readers over a made-up window (and over a program that
lacks what they read), a whole rehearsal on the CPU with `correct` true and
with the broken sampler false, and its ten controls at a size the CPU
holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_solar as cs, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "solar-open2-250b.think-steady"


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def run(*args, script=("benchmarks", "run.py"), timeout=900):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, *script), *args],
                          cwd=ROOT, env=e, capture_output=True, text=True, timeout=timeout)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_counts_by_hand():
    m = load("configs", "solar-open2-250b")
    c = cs.param_counts(m)
    # ISSUE 46's arithmetic (M): q, k, v, o 134.22; the two rank-128 pairs 3.15; beta 0.26; the
    # convolution (4 taps and a bias over 24,576 lanes) 0.12; dt_bias and A_log
    assert c["kda"] == 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 \
        + 24576 * 5 + 8192 + 64 == 137_756_736
    assert c["attention"] == 2 * 4096 * 8192 + 2 * 4096 * 1024 + 4096 * 8192 == 109_051_904
    assert c["expert"] == 3 * 4096 * 1280 == 15_728_640 and c["shared"] == c["expert"]
    assert c["router"] == 4096 * 320 and c["mlp"] == 20 * c["expert"] + c["shared"] + c["router"]
    assert round(c["kda_layer"] / 1e6, 1) == 469.4 and round(c["attention_layer"] / 1e6, 1) == 440.7
    assert c["embed"] == c["head"] == 24576 * 4096 and 2 * c["embed"] == 201_326_592
    assert c["total"] == 6 * c["kda_layer"] + 2 * c["attention_layer"] + 2 * c["embed"] == 3_898_868_096
    assert abs(c["total"] / 3898.8e6 - 1) < 1e-4  # 3,898.8 M: 7.80 GB at 2 bytes
    assert cs.decode_weight_bytes(m) == 2 * (c["total"] - c["embed"])  # 7.60 GB: a floor of 9.3 ms
    assert 9.2e-3 < counts.hbm_time_s(cs.decode_weight_bytes(m), "TPU v5 lite") < 9.4e-3
    assert cs.state_bytes_per_row(m) == 6 * 64 * 128 * 128 * 4 == 6 * 4_194_304  # 4.19 MB a layer
    assert cs.slot_bytes(m) == 25_165_824 + 6 * 3 * 24576 * 4 == 26_935_296  # 26.94 MB a slot
    assert cs.update_kernel_bytes(m, 50) == 50 * 6 * 8_388_608  # 8.39 MB a row and layer: 2.5 GB
    assert cs.kv_bytes_per_token(m) == 2 * 2 * 8 * 128 * 2 == 8192
    assert cs.recurrence_flops_per_token(m) == 7 * 64 * 128 * 128
    pairs = 64 * 65 // 2
    assert cs.chunk_flops(m, 512) == 8 * 64 * (10 * 128 * pairs + 6 * 64 * 128 * 128)
    assert cs.routed_pairs_per_token(m) == 8 * 8 * 20 / 320 == 4.0  # 0.5 held pairs a layer
    assert cs.expert_pair_flops(m) == 6 * 4096 * 1280
    assert cs.token_matrix_flops(m) == 2 * (6 * c["kda"] + 2 * c["attention"] + 8 * (c["shared"] + c["router"]))
    assert cs.attention_pair_flops(m) == 2 * 4 * 128 * 64 and cs.head_flops(m) == 2 * c["head"]
    # one chunk at 512 cached tokens and one decode row at context 900
    per_token = cs.token_matrix_flops(m) + 4 * cs.expert_pair_flops(m)
    want = (513 * per_token + 6 * (cs.chunk_flops(m, 512) + cs.recurrence_flops_per_token(m))
            + (512 * 512 + 512 * 513 // 2 + 900) * cs.attention_pair_flops(m) + 2 * cs.head_flops(m))
    assert cs.model_flops(m, [512], 512, [900]) == want


def test_the_configuration_is_the_published_one_cut_as_issue_46_says():
    m = load("configs", "solar-open2-250b")
    published = {  # the catalog row's `config`, every key
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                               "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
        "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    }
    assert {k: m[k] for k in published} == published
    assert cs.kinds(m) == ("attention", "kda", "kda", "kda") * 2
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"]) == (8, 20, 24576)
    assert (m["num_hidden_layers_published"], m["n_routed_experts_published"],
            m["vocab_size_published"]) == (48, 320, 196608)
    assert m["experts_held"] == [0, 20] and m["family"] == "solar" and "sixteen chips" in m["deployment"]
    assumed = " ".join(m["assumed"])
    for said in ("kda_gate_rank 128", "softmax", "PER LANE", "kda_chunk_size 64", "float32",
                 "max_running_requests 96"):
        assert said in assumed, said
    e = m["engine"]
    assert e["max_prefill_tokens"] == 8 * m["kda_chunk_size"] and e["prefill_buckets"] == [512]
    assert e["max_running_requests"] == 96 and e["max_seq_len"] == 8192 and e["block_size"] == 128


def test_the_mix_is_whole_chunks_under_the_sliced_vocabulary():
    cell, traffic = load("cells", CELL), load("traffic", "think-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 45.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 512 and lens[-1] <= 4096 and all(n % 512 == 0 for n in lens)
    assert lens[len(lens) // 2] == 1024  # the median prompt: two chunks
    outs = sorted(r["out_len"] for r in plan["requests"])
    assert outs[0] >= 128 and outs[-1] <= 1024 and plan["loop"] == "open"
    assert 400 <= outs[len(outs) // 2] <= 500
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 45.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws = traffic["warm_shapes"]
    assert max(ws["background_prompts"]) + ws["background_output"] <= 8192 and ws["probe_prompt"] == 4096
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.02)
    assert len(cell["sweep"]["points"]) >= 4


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


def window(with_program=True, config="solar-open2-250b"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 2048, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 1024 + 5, 6, 7
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%kda_update_kernel.3 = ...": 0.3e6, "%moe_grouped_kernel.7 = ...": 30e6} if with_program else {}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [60e6, 60e6], "_decode_impl": [15e6]}}
    deltas = {"xllm_engine_decode_steps_total": 1000.0, "xllm_engine_prefill_chunks_total": 400.0}
    if with_program:
        deltas.update({"xllm_engine_state_slots_in_use_sum": 61000.0,
                       "xllm_engine_state_slots_in_use_count": 1000.0,
                       "xllm_engine_moe_pairs_per_expert_sum": 70000.0,
                       "xllm_engine_moe_pairs_per_expert_count": 10000.0})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0, deltas=deltas)


def test_the_committed_schedule_is_among_the_steadiest_the_step_time_model_finds():
    """`schedule_seed` was taken from the steadiest few of rank_schedules.py's
    step-time model (no chip), after the first one, 29, read over half the
    bound of `tpot_p90_ms` on the chip: the model says so of both."""
    spec = importlib.util.spec_from_file_location("rank_schedules", os.path.join(HERE, "rank_schedules.py"))
    rank_schedules = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rank_schedules)
    with open(os.path.join(BENCH, "traffic", "think-steady.json")) as f:
        committed = json.load(f)["schedule_seed"]
    rows = rank_schedules.rank(sorted(set(range(16)) | {29, committed}), 12)
    place = {s: i for i, (_, _, s) in enumerate(rows)}
    assert place[committed] < len(rows) // 4 and place[29] >= 3 * len(rows) // 4
    tpot, ttft, _ = rows[place[committed]]
    assert tpot < 0.045 / 2 and ttft < 0.05 / 2  # a quarter of the bounds, in the model


def test_the_draw_is_what_tells_a_bfloat16_state_from_a_sound_run():
    """study_solar.py (the family's reference against the program's dense
    oracle in bfloat16, the state rounded as `state-bf16` rounds it): with
    the committed draw a bfloat16 state reads many times a sound run after
    the check's 64 tokens; with the first round's draw (no sink, a standing
    value of 8) it reads like one, which is what ISSUE 46's tenth control
    did on the chip."""
    spec = importlib.util.spec_from_file_location("study_solar", os.path.join(HERE, "study_solar.py"))
    study_solar = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(study_solar)
    (sound, fault), = study_solar.study({}, [1], chunks=(1,))
    assert fault > 8 * sound and sound < 2e-4
    first = dict(SINK_LANES=0, V_BIAS_MEAN=8.0, A_RANGE=(1e-2, 1e-1))
    (sound, fault), = study_solar.study(first, [1], chunks=(1,))
    assert fault < 2 * sound


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    # three decode rows x 6 layers x 4.19 MB, read and written, in 0.3 ms of kernel
    need = 2 * 3 * cs.state_bytes_per_row(m)
    assert reader("kda_update_roofline.think").compute(w) == pytest.approx(100 * need / 819e9 / 0.3e-3)
    assert 0 < reader("kda_update_roofline.think").compute(w) < 100
    model = cs.model_flops(m, [0, 512], 512, [1029, 1030, 1031])
    assert reader("step_mfu.think").compute(w) == pytest.approx(100 * model / 197e12 / 0.135)
    assert 0 < reader("step_mfu.think").compute(w) < 100
    assert reader("state_slots_live.think").compute(w) == pytest.approx(61.0)
    # the two accepted readers the cell is appended to name no family
    assert reader("moe_pairs_per_expert.doc").compute(w) == pytest.approx(7.0)
    assert reader("prefill_step_share.doc").compute(w) == pytest.approx(40.0)
    assert w.checks == {}  # three step programs: a ratio of so few says nothing


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 46 (no such kernel in a trace, no such series),
    and another family's window: every new reader returns None and raises
    nothing; without a trace every traced one does."""
    w = window(with_program=False)
    assert reader("kda_update_roofline.think").compute(w) is None
    assert reader("state_slots_live.think").compute(w) is None
    other = window(config="granite-4.0-h-small")
    for name in ("kda_update_roofline.think", "step_mfu.think", "state_slots_live.think"):
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in ("kda_update_roofline.think", "step_mfu.think"):
        assert reader(name).compute(w) is None, name
    # ... and the other hybrid's readers give nothing in this family's window
    for name in ("ssm_update_roofline.assist", "step_mfu.assist", "state_slots_live.assist"):
        assert reader(name).compute(window()) is None, name


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-solar-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"kda-xla"' in p.stdout
    slots = res["metrics"]["state_slots_live.think"]["value"]
    assert 0 < slots <= 8  # the hybrid's slot accounting feeds the series
    assert res["metrics"]["moe_pairs_per_expert.doc"]["value"] > 0
    assert 0 < res["metrics"]["prefill_step_share.doc"]["value"] <= 100
    for name in ("kda_update_roofline.think", "step_mfu.think", "step_mfu.assist"):
        assert name not in res["metrics"]  # device metrics: nothing to read on the CPU


def test_the_family_with_a_broken_sampler_is_not_correct():
    p = run("--workload", "rehearse-solar-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and '"ok": false' in p.stdout


def control(mode):
    args = ["--config", "rehearse-solar-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896", "--long-tokens", "64"]
    p = run(*args, script=("benchmarks", "tests", "control_solar.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["state-bf16", "w-int8", "zero-carry", "no-conv-carry", "beta-1x",
                                  "scalar-decay", "no-delta", "no-gate", "no-shared", "wrong-expert"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert not any(low["verdicts"]), low
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)


def test_the_timed_sizes_stay_sound(sound):
    long = control("long")
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 10 * max(sound["logprob_mse_max"], 1e-13)
