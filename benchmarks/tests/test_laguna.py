"""The Laguna family's additions (PR 60): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its four readers and the window pool's accepted one over a
made-up window (and over a program that lacks what they read), a whole rehearsal on the CPU with `correct` true and
with the broken sampler false, and its controls at a size the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_laguna as cl, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "laguna-xs.2.agent-steady"
TRACED = ("step_mfu.agent", "full_attn_roofline.agent", "window_attn_roofline.agent")
COUNTED = ("experts_touched_share.agent",)
SHARED = "kv_window_share.longmix"  # the other window family's reader: no family in it, so the cell is on its list


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
    m = load("configs", "laguna-xs.2")
    c = cl.param_counts(m)
    # ISSUE 60's arithmetic (M): full 12.58 q + 2 x 2.10 + 12.58 o + 0.10 gate; window 16.78, 4.19, 16.78, 0.13
    assert c["full"] == 2048 * (2 * 48 * 128 + 2 * 8 * 128 + 48) == 29_458_432
    assert c["window"] == 2048 * (2 * 64 * 128 + 2 * 8 * 128 + 64) == 37_879_808
    assert c["expert"] == c["shared"] == 3 * 2048 * 512 == 3_145_728 and c["router"] == 2048 * 256
    assert c["routed"] == 256 * c["expert"] + c["shared"] + c["router"] == 808_976_384
    assert c["dense"] == 3 * 2048 * 8192 and c["embed"] == c["head"] == 100352 * 2048
    assert c["total"] == 2 * c["full"] + 3 * c["window"] + c["dense"] + 4 * c["routed"] + 2 * c["embed"] \
        == 3_869_835_264  # 3,869.8 M: 7.74 GB at 2 bytes
    assert cl.kinds(m) == ("attention", "window", "window", "window", "attention")
    assert (cl.layers_of(m, "attention"), cl.layers_of(m, "window"), cl.dense_layers(m),
            cl.routed_layers(m)) == (2, 3, 1, 4)
    assert (cl.query_heads(m, "attention"), cl.query_heads(m, "window")) == (48, 64)
    assert cl.decode_weight_bytes(m) == 2 * (c["total"] - c["embed"])  # 7.33 GB: a floor of 8.9 ms
    assert 8.8e-3 < counts.hbm_time_s(cl.decode_weight_bytes(m), "TPU v5 lite") < 9.1e-3
    # a cached token: 8,192 B over the 2 full layers, 12,288 B over the 3 window layers
    assert cl.full_kv_bytes_per_token(m) == 2 * 8 * 256 * 2 == 8192 == cl.kv_bytes_per_token(m)
    assert cl.window_kv_bytes_per_token(m) == 3 * 8 * 256 * 2 == 12288
    assert cl.window_decode_bytes(m, [100, 512, 30000]) == (100 + 512 + 512) * 12288
    assert cl.full_decode_bytes(m, [100, 512, 30000]) == 30612 * 8192
    assert cl.routed_pairs_per_token(m) == 4 * 8 and cl.expert_pair_flops(m) == 6 * 2048 * 512
    assert cl.token_matrix_flops(m) == 2 * (2 * c["full"] + 3 * c["window"] + c["dense"]
                                            + 4 * (c["router"] + c["shared"]))
    assert cl.attention_pair_flops(m, "attention") == 4 * 128 * 48  # TRUE heads: no pad of 6 to 8
    assert cl.attention_pair_flops(m, "window") == 4 * 128 * 64
    # one chunk at 1,024 cached tokens and one decode row at context 900
    per_token = cl.token_matrix_flops(m) + 32 * cl.expert_pair_flops(m)
    full = 512 * 1024 + 512 * 513 // 2 + 900
    window = 512 * 512 + 512
    want = (513 * per_token + 2 * full * 4 * 128 * 48 + 3 * window * 4 * 128 * 64
            + 2 * cl.head_flops(m))
    assert cl.model_flops(m, [1024], 512, [900]) == want
    # a step that touches every expert streams 6.44 GB of them: 7.9 ms
    assert 7.8e-3 < counts.hbm_time_s(4 * 256 * c["expert"] * 2, "TPU v5 lite") < 7.9e-3


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    m = load("configs", "laguna-xs.2")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert {k: m[k] for k in row["config"]} == {**row["config"], "num_hidden_layers": 5}
    assert m["source"] == row["source_url"] and m["reduced"] == ["num_hidden_layers"]
    assert m["num_hidden_layers_published"] == 40 and m["layers_held"] == [0, 1, 2, 3, 4]
    assert [m["layer_types"][l] for l in m["layers_held"]] == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert [m["mlp_layer_types"][l] for l in m["layers_held"]] == ["dense"] + ["sparse"] * 4
    assert m["family"] == "laguna" and "eight pipeline stages" in m["deployment"]
    assert "no layer shared between chips" in m["deployment"]
    assumed = " ".join(m["assumed"])
    for said in ("gate per HEAD", "33.44 B", "34.07 B", "NO selection bias", "added unweighted",
                 "no QK-norm", "Split-half", "i - 512 < j <= i", "ONE context bucket",
                 "transcribed offline", "1 : 3"):
        assert said in assumed, said
    e = m["engine"]
    assert e["max_prefill_tokens"] == 512 and e["prefill_buckets"] == [512] and e["block_size"] == 128
    assert e["max_seq_len"] == 33792 and e["hbm_utilization"] == 0.9 and e["tp_size"] == 1
    assert m["bytes"]["window_pool_blocks"] == 1 + e["max_running_requests"] * 6 + 8


def test_the_mix_is_whole_chunks_from_a_screen_to_a_repository_slice():
    cell, traffic = load("cells", CELL), load("traffic", "agent-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 600.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 1024 and lens[-1] <= 32768 and all(n % 512 == 0 for n in lens)
    assert 5632 <= lens[len(lens) // 2] <= 6656  # the median prompt: twelve chunks
    assert 7500 < sum(lens) / len(lens) < 9300  # mean about 8,400
    assert 0.04 < sum(n >= 20480 for n in lens) / len(lens) < 0.10
    outs = sorted(r["out_len"] for r in plan["requests"])
    assert outs[0] >= 32 and outs[-1] <= 1024 and plan["loop"] == "open"
    assert 165 <= outs[len(outs) // 2] <= 220
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 600.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws, engine = traffic["warm_shapes"], load("configs", "laguna-xs.2")["engine"]
    assert max(ws["background_prompts"]) + ws["background_output"] <= engine["max_seq_len"]
    assert 32768 + 1024 <= engine["max_seq_len"]
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.02)
    sweep = cell["sweep"]
    assert len(sweep["points"]) >= 3 and len(sweep["points_first_draw"]) >= 4
    assert {p["rate_per_s"] for p in sweep["points"]} >= {cell["knee_per_s"]}
    assert "coding agents" in traffic["users"] and "prefix cache" in traffic["users"]
    assert len(cell["why"]) <= 200


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
            'xllm_engine_kv_block_bytes{pool="full"}': 1048576.0,
            'xllm_engine_kv_block_bytes{pool="window"}': 1572864.0}


def window(with_program=True, config="laguna-xs.2"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 2048, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 1024 + 5, 6, 7
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%window_paged_attention_kernel.3 = ...": 0.1e6, "%paged_attention_kernel.5 = ...": 0.05e6} \
        if with_program else {"%paged_attention_kernel.5 = ...": 0.05e6}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [12e6, 12e6], "_decode_impl": [3e6]}}
    deltas = {"xllm_engine_moe_experts_touched_total": 700_000.0,
              "xllm_engine_moe_experts_held_total": 1000 * 4 * 256.0} if with_program else {}
    snaps = (gauges(900.0, 60.0), gauges(1100.0, 64.0)) if with_program else ({}, {})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0,
                      deltas=deltas, counters_start=snaps[0], counters_end=snaps[1])


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    # three decode rows: 512 tokens x 12,288 B each over the window layers in 0.1 ms of launches
    assert reader("window_attn_roofline.agent").compute(w) == pytest.approx(
        100 * 3 * 512 * 12288 / 819e9 / 0.1e-3)
    # ... and their whole contexts x 8,192 B over the full layers in 0.05 ms
    assert reader("full_attn_roofline.agent").compute(w) == pytest.approx(
        100 * (1029 + 1030 + 1031) * 8192 / 819e9 / 0.05e-3)
    model = cl.model_flops(m, [0, 512], 512, [1029, 1030, 1031])
    assert reader("step_mfu.agent").compute(w) == pytest.approx(100 * model / 197e12 / 0.027)
    for name in TRACED:
        assert 0 < reader(name).compute(w) < 100, name
    assert reader("experts_touched_share.agent").compute(w) == pytest.approx(100 * 700_000 / 1_024_000)
    share = lambda f, wn: 100 * wn * 1572864 / (wn * 1572864 + f * 1048576)
    assert reader(SHARED).compute(w) == pytest.approx(
        (share(900, 60) + share(1100, 64)) / 2)
    assert w.checks == {}  # three step programs: a ratio of so few says nothing


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 60 (no such counters, no launch of this family in
    a trace), and another family's window: every new reader returns None
    and raises nothing; without a trace every traced one does."""
    w = window(with_program=False)
    for name in ("window_attn_roofline.agent", SHARED) + COUNTED:
        assert reader(name).compute(w) is None, name
    other = window(config="mimo-v2-flash")
    for name in TRACED:
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in TRACED:
        assert reader(name).compute(w) is None, name
    # ... and the other window family's readers give nothing in this family's window
    for name in ("window_attn_roofline.longmix", "full_attn_roofline.longmix", "step_mfu.longmix"):
        assert reader(name).compute(window()) is None, name


def test_the_manifest_names_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "laguna-xs.2", "traffic": "agent-steady", "chips": 1,
                    "why": load("cells", CELL)["why"]}
    config = next(c for c in man["configs"] if c["name"] == "laguna-xs.2")
    assert config["reduced"] == ["num_hidden_layers"] and config["file"].endswith("laguna-xs.2.json")
    own = {e["name"]: e for e in man["per_layer"] if e.get("workloads") == [CELL]}
    assert set(own) == set(TRACED) | set(COUNTED)
    assert all(e["moves"] == "tpot_p90_ms" for e in own.values())
    for name in own:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py")), name
    listed = {e["name"] for e in man["per_layer"] if CELL in e.get("workloads", ())}
    assert {"host_gap_ms", "moe_pairs_per_expert.doc", "prefill_step_share.doc",
            "setup_cache_hit_share", "device_named_share", SHARED} <= listed


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-laguna-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"window-xla"' in p.stdout
    assert '"rotary": "yarn x16 / 8 lanes"' in p.stdout and '"query_group": 3' in p.stdout


def test_the_family_with_a_broken_sampler_is_not_correct():
    p = run("--workload", "rehearse-laguna-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and '"ok": false' in p.stdout


def control(mode):
    args = ["--config", "rehearse-laguna-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896", "--long-tokens", "64"]
    p = run(*args, script=("benchmarks", "tests", "control_laguna.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["w-int8", "no-gate", "gate-broadcast", "no-yarn", "no-attn-factor",
                                  "lanes-swapped", "tables-swapped", "window-128", "no-window",
                                  "stale-block", "no-shared", "no-scale", "softmax-scores",
                                  "wrong-expert"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)
    assert not any(low["verdicts"]) or mode == "softmax-scores"  # (near the chip's limit in float32)


def test_the_timed_sizes_stay_sound(sound):
    long = control("long")  # 7 chunks of 128, window blocks freed behind them, then 64 tokens
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 10 * max(sound["logprob_mse_max"], 1e-13)


def test_an_int8_cache_is_refused_by_name_not_served():
    """There is no `kv-int8` control: the build refuses the cache format."""
    code = ("from xllm_service_tpu.common.config import EngineConfig\n"
            "from xllm_service_tpu.runtime.executor import ModelExecutor\n"
            "ModelExecutor(EngineConfig(model='laguna-tiny', dtype='float32', num_blocks=64, block_size=8,\n"
            "    max_seq_len=64, max_running_requests=2, kv_cache_dtype='int8'))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT), timeout=300)
    assert p.returncode != 0 and "WindowFamilyUnsupported" in p.stderr and "kv_cache_dtype" in p.stderr
