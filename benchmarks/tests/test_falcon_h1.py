"""The Falcon-H1 family's additions (PR 53): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its five readers over a made-up window (and over a program or a
family that lacks what they read), a whole rehearsal on the CPU with
`correct` true, and its controls at a size the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_falcon_h1 as cf, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "falcon-h1-34b.dialog-steady"
READERS = ("ssm_update_roofline.dialog", "attn_decode_roofline.dialog", "step_mfu.dialog",
           "state_mixer_ms_per_step.dialog", "state_slots_live.dialog")


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
    m = load("configs", "falcon-h1-34b")
    c = cf.param_counts(m)
    # ISSUE 53's arithmetic: attention 31,457,280 + Mamba 68,351,072 + MLP 330,301,440 a block
    assert c["attention"] == 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128 == 31_457_280
    assert c["mamba"] == 5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120 + 96 + 4096 == 68_351_072
    assert c["mlp"] == 3 * 5120 * 21504 == 330_301_440 and c["block"] == 430_109_792
    assert c["embed"] == c["head"] == 32640 * 5120 == 167_116_800
    assert c["total"] == 9 * c["block"] + 2 * c["embed"] == 4_205_221_728  # 4,205 M: 8.41 GB
    assert cf.decode_weight_bytes(m) == 2 * (9 * c["block"] + c["head"])  # 8.08 GB: a floor of 9.9 ms
    assert 9.8e-3 < counts.hbm_time_s(cf.decode_weight_bytes(m), "TPU v5 lite") < 9.9e-3
    # a live row: 32 x 128 x 256 float32 a block, read AND written
    assert cf.state_bytes_per_row(m) == 9 * 32 * 128 * 256 * 4 == 37_748_736
    assert cf.slot_bytes(m) == 37_748_736 + 9 * 3 * 5120 * 4 == 38_301_696
    assert cf.update_kernel_bytes(m, 30) == 2 * 30 * 37_748_736  # 75.5 MB a live row a step
    assert cf.kv_bytes_per_token(m) == 2 * 9 * 4 * 128 * 2 == 18_432
    assert cf.decode_kv_bytes(m, [100, 2048]) == 2148 * 18_432
    # the two memories of a row are equal at about 2k tokens of context
    assert 2040 < cf.update_kernel_bytes(m, 1) / cf.kv_bytes_per_token(m) / 2 < 2056
    assert cf.scan_flops_per_token(m) == 6 * 32 * 128 * 256
    pairs = 256 * 257 // 2
    assert cf.chunk_flops(m, 256) == 2 * 2 * 256 * pairs + 2 * 4096 * pairs + 4 * 256 * 4096 * 256
    assert cf.attention_pair_flops(m) == 9 * 4 * 128 * 20 and cf.head_flops(m) == 2 * c["head"]
    assert cf.model_flops(m, [], 256, [1000]) == (
        cf.token_matrix_flops(m) + 9 * cf.scan_flops_per_token(m)
        + 1000 * cf.attention_pair_flops(m) + cf.head_flops(m))


def test_the_counts_hold_against_the_programs_parameter_tree():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import family as family_mod
    from xllm_service_tpu.models import granite

    m = load("configs", "falcon-h1-34b")
    cfg = family_mod.load(m).model_config(m["name"], m)
    tree = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    norms = size(tree["layers"]["attn_norm"]) + size(tree["layers"]["mlp_norm"]) + size(tree["final_norm"])
    assert size(tree) - norms == cf.param_counts(m)["total"]
    assert size(tree["mamba"]) == 9 * cf.param_counts(m)["mamba"]
    assert size(tree["attn"]) == 9 * cf.param_counts(m)["attention"]
    S, conv = granite.state_shapes(cfg, 1)
    assert int(np.prod(S)) * 4 == cf.state_bytes_per_row(m)
    assert (int(np.prod(S)) + int(np.prod(conv))) * 4 == cf.slot_bytes(m)


def test_the_configuration_is_the_published_one_cut_as_issue_53_says():
    m = load("configs", "falcon-h1-34b")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    cut = {"num_hidden_layers": 9, "vocab_size": 32640}
    assert {k: m[k] for k in row["config"]} == {**row["config"], **cut}  # every key, the lists whole
    assert m["source"] == row["source_url"] and m["reduced"] == list(cut)
    assert (m["num_hidden_layers_published"], m["vocab_size_published"]) == (72, 261120)
    assert m["family"] == "falcon_h1" and "eight pipeline stages of nine" in m["deployment"]
    assumed = " ".join(m["assumed"])
    for said in ("ssm_multipliers[0..4] scale the z", "key_multiplier scales k", "mamba_expand 2 is unused",
                 "mamba_chunk_size 128", "BEFORE the gated RMSNorm", "group h // 16",
                 "float32", "max_running_requests", "TWO step programs"):
        assert said in assumed, said
    e = m["engine"]
    assert e["max_prefill_tokens"] == 256 and e["prefill_buckets"] == [256] and e["block_size"] == 128
    assert e["max_seq_len"] == 4096 and e["hbm_utilization"] == 0.9 and e["tp_size"] == 1
    assert e["max_running_requests"] == 64


def test_the_mix_is_whole_chunks_on_both_sides_of_two_thousand_tokens():
    cell, traffic = load("cells", CELL), load("traffic", "dialog-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 600.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 256 and lens[-1] <= 3072 and all(n % 256 == 0 for n in lens)
    assert lens[len(lens) // 2] in (768, 1024)  # the median prompt: three or four chunks
    assert 950 < sum(lens) / len(lens) < 1250
    assert 0.08 < sum(n >= 2048 for n in lens) / len(lens) < 0.25  # rows on the far side of 2k
    outs = sorted(r["out_len"] for r in plan["requests"])
    assert outs[0] >= 32 and outs[-1] <= 768 and plan["loop"] == "open"
    assert 200 <= outs[len(outs) // 2] <= 290
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 600.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    ws = traffic["warm_shapes"]
    assert max(ws["background_prompts"]) + ws["background_output"] <= 4096
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"], rel=0.02)
    sweep = cell["sweep"]
    assert len(sweep["points"]) >= 4
    assert {p["rate_per_s"] for p in sweep["points"]} >= {cell["knee_per_s"]}
    assert "75.5 MB" in traffic["users"] and "no shared prefix" in traffic["users"]


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


def window(with_program=True, config="falcon-h1-34b"):
    m = load("configs", config)
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 1024, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 1024 + 5, 6, 7
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%mamba_update_kernel.3 = ...": 0.4e6, "%paged_attention_kernel.5 = ...": 0.1e6} \
        if with_program else {"%fusion.5 = ...": 0.05e6}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [25e6, 25e6], "_decode_impl": [15e6]}}
    deltas = {"xllm_engine_decode_steps_total": 1000.0, "xllm_engine_prefill_chunks_total": 400.0}
    hist = {"xllm_engine_state_slots_in_use_sum": 30000.0, "xllm_engine_state_slots_in_use_count": 1000.0}
    snaps = ({k: 0.0 for k in hist}, hist) if with_program else ({}, {})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0,
                      deltas=deltas, counters_start=snaps[0], counters_end=snaps[1])


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    # three decode rows: 2 x 37.7 MB of state each in 0.4 ms of launches
    assert reader("ssm_update_roofline.dialog").compute(w) == pytest.approx(
        100 * 3 * 2 * 37_748_736 / 819e9 / 0.4e-3)
    # ... and their whole contexts x 18,432 B in 0.1 ms
    assert reader("attn_decode_roofline.dialog").compute(w) == pytest.approx(
        100 * (1029 + 1030 + 1031) * 18_432 / 819e9 / 0.1e-3)
    model = cf.model_flops(m, [0, 256], 256, [1029, 1030, 1031])
    assert reader("step_mfu.dialog").compute(w) == pytest.approx(100 * model / 197e12 / 0.065)
    for name in READERS[:3]:
        assert 0 < reader(name).compute(w) < 100, name


def test_a_program_or_a_family_without_what_they_read_reads_as_nothing():
    """The parent of PR 53 cannot build the configuration at all; a
    program without the kernels' names in its trace, another family's
    window, and a run without a trace all read as nothing and raise
    nothing."""
    w = window(with_program=False)
    for name in READERS[:2]:
        assert reader(name).compute(w) is None, name
    other = window(config="granite-4.0-h-small")
    for name in READERS:
        assert reader(name).compute(other) is None, name
    w.trace = None
    for name in READERS[:4]:
        assert reader(name).compute(w) is None, name
    # ... and the other hybrids' readers give nothing in this family's window
    for name in ("kda_update_roofline.think", "step_mfu.think", "ssm_update_roofline.assist",
                 "step_mfu.assist", "state_slots_live.assist", "full_attn_roofline.longmix"):
        assert reader(name).compute(window()) is None, name


def test_rehearsal_of_the_family_end_to_end():
    p = run("--workload", "rehearse-falcon-h1-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"mamba-xla"' in p.stdout


def control(mode):
    args = ["--config", "rehearse-falcon-h1-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896", "--long-tokens", "64"]
    p = run(*args, script=("benchmarks", "tests", "control_falcon_h1.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return last_json(p.stdout)


@pytest.fixture(scope="module")
def sound():
    return control("sound")


@pytest.mark.parametrize("mode", ["w-int8", "state-bf16", "zero-carry", "no-conv-carry", "no-attn", "no-state",
                                  "ssm-swap", "mlp-swap", "group-swap", "stale-block"])
def test_controls_read_far_from_the_sound_runs(sound, mode):
    """On the CPU in float32 a sound run reads rounding alone; every
    control reads orders above it (the limits are the chip's: there a
    sound run is bfloat16's, PERF.md section 2)."""
    low = control(mode)
    assert all(sound["verdicts"]), sound
    assert not any(low["verdicts"]), low
    assert low["logprob_mse_min"] >= 1e3 * sound["logprob_mse_max"], (sound, low)


def test_the_timed_sizes_stay_sound(sound):
    # 7 chunks of 128 through both pools, then 64 tokens. In float32 the chunk form and the
    # recurrence part by rounding that grows with the tokens a state has integrated (the draw
    # makes the state branch the largest: 1.8e-10 here beside a sound 5e-12), eight orders
    # under the limit; on the chip in bfloat16 the long run reads under the sound band
    long = control("long")
    assert all(long["verdicts"]) and long["logprob_mse_max"] <= 100 * max(sound["logprob_mse_max"], 1e-12)


def test_the_draws_gains_are_read_by_the_reference():
    p = run("--config", "rehearse-falcon-h1-tiny", "--mode", "shares", "--seeds", "21", "--rehearse",
            script=("benchmarks", "tests", "control_falcon_h1.py"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    rms = last_json(p.stdout)["rms_by_block"]
    assert 0.7 < rms["stream"][0] < 1.3  # h0 has unit RMS
    for branch in ("attention", "state", "mlp"):  # no branch vanishes from the stream
        assert all(0.05 < r / s < 3.0 for r, s in zip(rms[branch], rms["stream"])), (branch, rms)
