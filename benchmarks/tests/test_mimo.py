"""The MiMo-V2-Flash family's additions (PR 49): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its four readers over a made-up window (and over a program that
lacks what they read), a whole rehearsal on the CPU with `correct` true and
with the broken sampler false, and its controls at a size the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_mimo as cm, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "mimo-v2-flash.longmix-steady"
READERS = ("window_attn_roofline.longmix", "full_attn_roofline.longmix", "step_mfu.longmix",
           "kv_window_share.longmix")


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
    m = load("configs", "mimo-v2-flash")
    c = cm.param_counts(m)
    # ISSUE 49's arithmetic (M): q 50.33, o 33.55; k 3.15 / 6.29, v 2.10 / 4.19
    assert c["full"] == 4096 * 64 * 192 + 4096 * 64 * 128 + 4096 * 4 * 192 + 4096 * 4 * 128 == 89_128_960
    assert c["window"] == 4096 * 64 * 320 + 4096 * 8 * 320 == 94_371_840
    assert c["dense"] == 3 * 4096 * 16384 == 201_326_592 and c["expert"] == 3 * 4096 * 2048 == 25_165_824
    assert c["router"] == 4096 * 256 and c["routed"] == 16 * c["expert"] + c["router"] == 403_701_760
    assert c["embed"] == c["head"] == 19072 * 4096 and 2 * c["embed"] == 156_237_824
    assert c["total"] == 2 * c["full"] + 5 * c["window"] + c["dense"] + 6 * c["routed"] + 2 * c["embed"] \
        == 3_429_892_096  # 3,429.9 M: 6.86 GB at 2 bytes
    assert cm.kinds(m) == ("attention",) + ("window",) * 5 + ("attention",)
    assert (cm.full_layers(m), cm.window_layers(m), cm.dense_layers(m), cm.routed_layers(m)) == (2, 5, 1, 6)
    assert cm.decode_weight_bytes(m) == 2 * (c["total"] - c["embed"])  # 6.70 GB: a floor of 8.2 ms
    assert 8.1e-3 < counts.hbm_time_s(cm.decode_weight_bytes(m), "TPU v5 lite") < 8.3e-3
    # TRUE bytes: 192 + 128 lanes a KV head, 2 bytes: 2,560 B a token and full layer, 5,120 a window layer
    assert cm.full_kv_bytes_per_token(m) == 2 * 4 * 320 * 2 == 5120 == cm.kv_bytes_per_token(m)
    assert cm.window_kv_bytes_per_token(m) == 5 * 8 * 320 * 2 == 25600
    assert cm.window_decode_bytes(m, [100, 128, 5000]) == (100 + 128 + 128) * 25600
    assert cm.full_decode_bytes(m, [100, 128, 5000]) == 5228 * 5120
    assert cm.window_chunk_pairs(0, 512, 128) == 128 * 129 // 2 + 384 * 128
    assert cm.window_chunk_pairs(1024, 512, 128) == 512 * 128
    assert cm.routed_pairs_per_token(m) == 6 * 8 * 16 / 256 == 3.0  # 0.5 held pairs a routed layer
    assert cm.expert_pair_flops(m) == 6 * 4096 * 2048
    assert cm.token_matrix_flops(m) == 2 * (2 * c["full"] + 5 * c["window"] + c["dense"] + 6 * c["router"])
    assert cm.attention_pair_flops(m) == 2 * 320 * 64 and cm.head_flops(m) == 2 * c["head"]
    # one chunk at 512 cached tokens and one decode row at context 900
    per_token = cm.token_matrix_flops(m) + 3 * cm.expert_pair_flops(m)
    full = 512 * 512 + 512 * 513 // 2 + 900
    window = 512 * 128 + 128
    want = (513 * per_token + (2 * full + 5 * window) * cm.attention_pair_flops(m) + 2 * cm.head_flops(m))
    assert cm.model_flops(m, [512], 512, [900]) == want


def test_the_configuration_is_the_published_one_cut_as_issue_49_says():
    m = load("configs", "mimo-v2-flash")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    cut = {"num_hidden_layers": 7, "n_routed_experts": 16, "vocab_size": 19072}
    assert {k: m[k] for k in row["config"]} == {**row["config"], **cut}  # every key, the lists whole
    assert m["source"] == row["source_url"] and m["reduced"] == sorted(cut, key=list(cut).index)
    assert (m["num_hidden_layers_published"], m["n_routed_experts_published"],
            m["vocab_size_published"]) == (48, 256, 152576)
    assert m["layers_held"] == [0, 6, 7, 8, 9, 10, 11] and m["experts_held"] == [0, 16]
    assert [m["hybrid_layer_pattern"][l] for l in m["layers_held"]] == [0, 1, 1, 1, 1, 1, 0]
    assert [m["moe_layer_freq"][l] for l in m["layers_held"]] == [0, 1, 1, 1, 1, 1, 1]
    assert m["family"] == "mimo" and "sixteen chips" in m["deployment"]
    assumed = " ".join(m["assumed"])
    for said in ("attention_value_scale 0.707 multiplies the VALUES", "rotary_dim", "192 ** -0.5",
                 "attention_chunk_size 128", "multi-token-prediction", "e_score_correction_bias",
                 "ONE context bucket", "max_running_requests"):
        assert said in assumed, said
    e = m["engine"]
    assert e["max_prefill_tokens"] == 512 and e["prefill_buckets"] == [512] and e["block_size"] == 128
    assert e["max_seq_len"] == 16384 and e["hbm_utilization"] == 0.9 and e["tp_size"] == 1


def test_the_mix_is_whole_chunks_short_and_long_in_one_queue():
    cell, traffic = load("cells", CELL), load("traffic", "longmix-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 600.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 512 and lens[-1] <= 15360 and all(n % 512 == 0 for n in lens)
    assert lens[len(lens) // 2] == 2048  # the median prompt: four chunks
    assert 0.25 < sum(n <= 1024 for n in lens) / len(lens) < 0.42  # a third a screen or under
    assert 0.06 < sum(n >= 8192 for n in lens) / len(lens) < 0.16  # a tenth a whole file
    outs = sorted(r["out_len"] for r in plan["requests"])
    assert outs[0] >= 32 and outs[-1] <= 768 and plan["loop"] == "open"
    assert 170 <= outs[len(outs) // 2] <= 230
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 600.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws = traffic["warm_shapes"]
    assert max(ws["background_prompts"]) + ws["background_output"] <= 16384
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.02)
    sweep = cell["sweep"]  # six rates on schedule 38, the knee re-read on the committed one
    assert len(sweep["points_schedule_38"]) >= 4 and len(sweep["points"]) >= 3
    assert {p["rate_per_s"] for p in sweep["points"]} >= {cell["rate_per_s"], cell["knee_per_s"]}
    assert "SIXTEENTH" in traffic["users"] and "short and long in one queue" in traffic["users"]


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


def gauges(full, window):
    return {'xllm_engine_kv_blocks_live{pool="full"}': full, 'xllm_engine_kv_blocks_live{pool="window"}': window,
            'xllm_engine_kv_block_bytes{pool="full"}': 786432.0,
            'xllm_engine_kv_block_bytes{pool="window"}': 3932160.0}


def window(with_program=True, config="mimo-v2-flash"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 2048, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 1024 + 5, 6, 7
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%window_paged_attention_kernel.3 = ...": 0.1e6, "%paged_attention_kernel.5 = ...": 0.05e6,
           "%moe_grouped_kernel.7 = ...": 30e6} if with_program else {"%paged_attention_kernel.5 = ...": 0.05e6}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [60e6, 60e6], "_decode_impl": [15e6]}}
    deltas = {"xllm_engine_decode_steps_total": 1000.0, "xllm_engine_prefill_chunks_total": 400.0}
    snaps = (gauges(900.0, 60.0), gauges(1100.0, 64.0)) if with_program else ({}, {})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0,
                      deltas=deltas, counters_start=snaps[0], counters_end=snaps[1])


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    # three decode rows: 128 tokens x 25,600 B each over the window layers in 0.1 ms of launches
    assert reader("window_attn_roofline.longmix").compute(w) == pytest.approx(
        100 * 3 * 128 * 25600 / 819e9 / 0.1e-3)
    # ... and their whole contexts x 5,120 B over the full layers in 0.05 ms
    assert reader("full_attn_roofline.longmix").compute(w) == pytest.approx(
        100 * (1029 + 1030 + 1031) * 5120 / 819e9 / 0.05e-3)
    model = cm.model_flops(m, [0, 512], 512, [1029, 1030, 1031])
    assert reader("step_mfu.longmix").compute(w) == pytest.approx(100 * model / 197e12 / 0.135)
    for name in READERS[:3]:
        assert 0 < reader(name).compute(w) < 100, name
    share = lambda f, wn: 100 * wn * 3932160 / (wn * 3932160 + f * 786432)
    assert reader("kv_window_share.longmix").compute(w) == pytest.approx(
        (share(900, 60) + share(1100, 64)) / 2)
    assert 20 < reader("kv_window_share.longmix").compute(w) < 30
    assert w.checks == {}  # three step programs: a ratio of so few says nothing


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 49 (no window launch in a trace, no such gauges),
    and another family's window: every new reader returns None and raises
    nothing; without a trace every traced one does."""
    w = window(with_program=False)
    assert reader("window_attn_roofline.longmix").compute(w) is None
    assert reader("kv_window_share.longmix").compute(w) is None
    other = window(config="solar-open2-250b")
    for name in READERS[:3]:
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in READERS[:3]:
        assert reader(name).compute(w) is None, name
    # ... and the other hybrids' readers give nothing in this family's window
    for name in ("kda_update_roofline.think", "step_mfu.think", "ssm_update_roofline.assist",
                 "step_mfu.assist"):
        assert reader(name).compute(window()) is None, name


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-mimo-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"window-xla"' in p.stdout


def test_the_family_with_a_broken_sampler_is_not_correct():
    p = run("--workload", "rehearse-mimo-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and '"ok": false' in p.stdout


def control(mode):
    args = ["--config", "rehearse-mimo-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896", "--long-tokens", "64"]
    p = run(*args, script=("benchmarks", "tests", "control_mimo.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["no-window", "no-sink", "theta-swapped", "full-rotary", "unscaled",
                                  "bias-weight", "w-int8", "wrong-expert", "stale-block"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert not any(low["verdicts"]), low
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)


def test_the_timed_sizes_stay_sound(sound):
    long = control("long")  # 7 chunks of 128, window blocks freed behind them, then 64 tokens
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 10 * max(sound["logprob_mse_max"], 1e-13)


def test_an_int8_cache_is_refused_by_name_not_served():
    """There is no `kv-int8` control: the build refuses the cache format."""
    code = ("from xllm_service_tpu.common.config import EngineConfig\n"
            "from xllm_service_tpu.runtime.executor import ModelExecutor\n"
            "ModelExecutor(EngineConfig(model='mimo-tiny', dtype='float32', num_blocks=64, block_size=4,\n"
            "    max_seq_len=64, max_running_requests=2, kv_cache_dtype='int8'))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT), timeout=300)
    assert p.returncode != 0 and "WindowFamilyUnsupported" in p.stderr and "kv_cache_dtype" in p.stderr
