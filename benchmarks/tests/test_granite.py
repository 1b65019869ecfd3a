"""The Granite 4.0-H family's additions (PR 42): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its readers over a made-up window (and over a program that
lacks what they read), a whole rehearsal on the CPU with `correct` true and
with the broken sampler false, and its controls at a size the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_granite as cg, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "granite-4.0-h-small.assist-steady"


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
    m = load("configs", "granite-4.0-h-small")
    c = cg.param_counts(m)
    # ISSUE 42's arithmetic (M): in_proj 68.68, out_proj 33.55, the convolution and the vectors
    assert c["mamba"] == 4096 * (8192 + 8448 + 128) + 8448 * 4 + 8448 + 3 * 128 + 8192 * 4096
    assert round(c["mamba"] / 1e6, 2) == 102.28 and c["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert c["expert"] == 3 * 4096 * 768 == 9_437_184 and c["shared"] == 3 * 4096 * 1536
    assert c["router"] == 4096 * 72 and c["mlp"] == 36 * c["expert"] + c["shared"] + c["router"]
    assert c["embed"] == 50176 * 4096 and c["total"] == 9 * c["mamba_layer"] + c["attention_layer"] + c["embed"]
    assert abs(c["total"] / 4757e6 - 1) < 0.01 and round(c["total"] / 1e6) == 4757
    assert cg.decode_weight_bytes(m) == 2 * c["total"]  # 9.51 GB: a floor of 11.6 ms
    assert 11.5e-3 < counts.hbm_time_s(cg.decode_weight_bytes(m), "TPU v5 lite") < 11.7e-3
    assert cg.state_bytes_per_row(m) == 9 * 128 * 64 * 128 * 4 == 37_748_736  # 4.19 MB a layer
    assert cg.slot_bytes(m) == 37_748_736 + 9 * 3 * 8448 * 4 == 38_661_120  # 38.66 MB a slot
    assert cg.update_kernel_bytes(m, 44) == 2 * 44 * 37_748_736  # 8.39 MB a row and layer, 3.3 GB
    assert cg.kv_bytes_per_token(m) == 2 * 1 * 8 * 128 * 2 == 4096
    assert cg.scan_flops_per_token(m) == 6 * 128 * 64 * 128
    pairs = 256 * 257 // 2
    assert cg.chunk_flops(m, 256) == 2 * 128 * pairs + 2 * 8192 * pairs + 4 * 256 * 8192 * 128
    assert cg.routed_pairs_per_token(m) == 10 * 10 * 36 / 72 == 50.0  # 5 held pairs a layer
    assert cg.expert_pair_flops(m) == 6 * 4096 * 768
    assert cg.token_matrix_flops(m) == 2 * (9 * c["mamba"] + c["attention"] + 10 * (c["shared"] + c["router"]))
    assert cg.attention_pair_flops(m) == 1 * 4 * 128 * 32 and cg.head_flops(m) == 2 * c["embed"]
    # one chunk at 256 cached tokens and one decode row at context 700
    per_token = cg.token_matrix_flops(m) + 50 * cg.expert_pair_flops(m)
    want = (257 * per_token + 9 * (cg.chunk_flops(m, 256) + cg.scan_flops_per_token(m))
            + (256 * 256 + pairs + 700) * cg.attention_pair_flops(m) + 2 * cg.head_flops(m))
    assert cg.model_flops(m, [256], 256, [700]) == want


def test_the_configuration_is_the_published_one_cut_as_issue_42_says():
    m = load("configs", "granite-4.0-h-small")
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 10,
        "num_key_value_heads": 8, "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    }
    assert {k: m[k] for k in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert m["layer_types"] == period * 4 and cg.kinds(m) == tuple(period)
    assert m["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (m["num_hidden_layers"], m["num_local_experts"], m["vocab_size"]) == (10, 36, 50176)
    assert (m["num_hidden_layers_published"], m["num_local_experts_published"],
            m["vocab_size_published"]) == (40, 72, 100352)
    assert m["experts_held"] == [0, 36] and m["family"] == "granite" and "two chips" in m["deployment"]
    e = m["engine"]
    assert e["max_prefill_tokens"] == m["mamba_chunk_size"] and e["prefill_buckets"] == [256]
    assert e["max_running_requests"] == 64 and e["max_seq_len"] == 4096 and e["block_size"] == 128


def test_the_mix_is_whole_chunks_under_the_sliced_vocabulary():
    cell, traffic = load("cells", CELL), load("traffic", "assist-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 45.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 256 and lens[-1] <= 2048 and all(n % 256 == 0 for n in lens)
    assert lens[len(lens) // 2] == 512  # the median prompt: two chunks
    outs = [r["out_len"] for r in plan["requests"]]
    assert min(outs) >= 32 and max(outs) <= 768 and plan["loop"] == "open"
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 45.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws = traffic["warm_shapes"]
    assert max(ws["background_prompts"]) + ws["background_output"] <= 4096 and ws["probe_prompt"] == 2048
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.02)


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


def window(with_program=True, config="granite-4.0-h-small"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 1024, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 512 + 5, 6, 7
        "b": {"prompt_len": 512, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%mamba_update_kernel.3 = ...": 0.4e6, "%moe_grouped_kernel.7 = ...": 30e6} if with_program else {}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [40e6, 40e6], "_decode_impl": [15e6]}}
    deltas = {"xllm_engine_decode_steps_total": 1000.0, "xllm_engine_prefill_chunks_total": 400.0}
    if with_program:
        deltas.update({"xllm_engine_state_slots_in_use_sum": 44000.0,
                       "xllm_engine_state_slots_in_use_count": 1000.0,
                       "xllm_engine_moe_pairs_per_expert_sum": 70000.0,
                       "xllm_engine_moe_pairs_per_expert_count": 10000.0})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0, deltas=deltas)


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    # three decode rows x 9 layers x 4.19 MB, read and written, in 0.4 ms of kernel
    need = 2 * 3 * cg.state_bytes_per_row(m)
    assert reader("ssm_update_roofline.assist").compute(w) == pytest.approx(100 * need / 819e9 / 0.4e-3)
    assert reader("ssm_update_roofline.assist").compute(w) < 100
    model = cg.model_flops(m, [0, 256], 256, [517, 518, 519])
    assert reader("step_mfu.assist").compute(w) == pytest.approx(100 * model / 197e12 / 0.095)
    assert reader("state_slots_live.assist").compute(w) == pytest.approx(44.0)
    # the two accepted readers the cell is appended to name no family
    assert reader("moe_pairs_per_expert.doc").compute(w) == pytest.approx(7.0)
    assert reader("prefill_step_share.doc").compute(w) == pytest.approx(40.0)
    assert w.checks == {}  # three step programs: a ratio of so few says nothing


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 42 (no such kernel in a trace, no such series),
    and another family's window: every new reader returns None and raises
    nothing; without a trace every traced one does."""
    w = window(with_program=False)
    assert reader("ssm_update_roofline.assist").compute(w) is None
    assert reader("state_slots_live.assist").compute(w) is None
    other = window(config="deepseek-v2")
    for name in ("ssm_update_roofline.assist", "step_mfu.assist", "state_slots_live.assist"):
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in ("ssm_update_roofline.assist", "step_mfu.assist"):
        assert reader(name).compute(w) is None, name


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-granite-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"mamba-xla"' in p.stdout
    slots = res["metrics"]["state_slots_live.assist"]["value"]
    assert 0 < slots <= 8  # the hybrid's slot accounting feeds the series
    assert res["metrics"]["moe_pairs_per_expert.doc"]["value"] > 0
    assert 0 < res["metrics"]["prefill_step_share.doc"]["value"] <= 100
    for name in ("ssm_update_roofline.assist", "step_mfu.assist", "step_mfu.doc"):
        assert name not in res["metrics"]  # device metrics: nothing to read on the CPU


def test_the_family_with_a_broken_sampler_is_not_correct():
    p = run("--workload", "rehearse-granite-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and '"ok": false' in p.stdout


def control(mode):
    args = ["--config", "rehearse-granite-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896"]
    p = run(*args, script=("benchmarks", "tests", "control_granite.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["w-int8", "state-bf16", "zero-carry", "no-conv-carry",
                                  "wrong-expert", "no-shared", "attn-scale"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)


def test_the_timed_sizes_stay_sound(sound):
    long = control("long")
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 10 * max(sound["logprob_mse_max"], 1e-13)
