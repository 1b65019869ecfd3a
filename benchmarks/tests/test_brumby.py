"""The Brumby family's additions (PR 31) beside the Llama ones: its counts
by hand, its traffic mix through the generator, a whole rehearsal
with `correct` true and with the broken sampler false, and its controls at
a size the CPU holds."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts_brumby, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def run(cwd, *args, script=("benchmarks", "run.py"), timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(cwd, *script), *args],
                          cwd=cwd, env=e, capture_output=True, text=True, timeout=timeout)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_counts_by_hand():
    m = load("configs", "brumby-14b")
    assert counts_brumby.true_features(m) == 128 * 129 // 2 == 8256
    # S of one sequence: 8 layers x 8 KV heads x 8256 x 128 float32
    assert counts_brumby.state_bytes_per_row(m) == 8 * 8 * 8256 * 128 * 4 == 270_532_608
    # a layer: q and o 5120 x 5120 each, k and v 5120 x 1024 each, the gate
    # 5120 x 8, SwiGLU 3 x 5120 x 17408; the head 151936 x 5120 once
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    norms = 8 * (2 * 5120 + 2 * 128 + 8) + 5120
    assert counts_brumby.decode_weight_bytes(m) == (8 * layer + 151936 * 5120) * 2 + norms * 4
    assert 6.8e9 < counts_brumby.decode_weight_bytes(m) < 6.9e9  # ISSUE 31: 6.84 GB
    # one 256-token chunk: read for 40 query heads, update for 8 KV heads
    assert counts_brumby.chunk_kernel_flops(m, 256) == 8 * 256 * 2 * 8256 * 129 * 48
    assert 0.20e12 < counts_brumby.chunk_kernel_flops(m, 256) < 0.22e12  # ISSUE 31: 0.21 TFLOP


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    m = load("configs", "brumby-14b")
    published = {"hidden_size": 5120, "intermediate_size": 17408, "num_attention_heads": 40,
                 "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936,
                 "max_position_embeddings": 32768, "rope_theta": 1000000,
                 "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "model_type": "brumby"}
    assert {k: m[k] for k in published} == published
    assert m["reduced"] == ["num_hidden_layers"] and m["num_hidden_layers"] == 8
    assert m["num_hidden_layers_published"] == 40 and m["family"] == "brumby"
    said = " ".join(m["assumed"])
    for word in ("retention_degree", "gate", "normaliser", "eps", "dtype", "max_running_requests"):
        assert word in said, word


def test_reason_batch_mix():
    t, cell = load("traffic", "reason-batch"), load("cells", "brumby-14b.reason-batch")
    m = load("configs", "brumby-14b")
    assert cell["clients"] == m["engine"]["max_running_requests"] == 24  # one client a slot
    plan = loadgen.build_plan(t, cell, 2**31 + 9, 45)
    reqs = plan["requests"]
    assert len(reqs) == 24 * 14 and plan["loop"] == "closed"
    lens = sorted(r["prompt_len"] for r in reqs)
    assert lens[0] == 256 and lens[-1] == 4096 and all(n % 256 == 0 for n in lens)
    assert lens[len(lens) // 2] == 1024 and 1250 <= sum(lens) / len(lens) <= 1450
    outs = [r["out_len"] for r in reqs if r["due"] is None]
    assert 512 <= min(outs) and max(outs) <= 1024
    assert max(lens) + 1024 <= 5120 < m["engine"]["max_seq_len"]
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(t, cell, 8, 45)["requests"]
    assert sorted(r["prompt_len"] for r in other) == lens  # the same work for every seed


def test_rehearsal_of_the_family_end_to_end():
    p = run(ROOT, "--workload", "rehearse-brumby-tiny.rehearse", "--rehearse", "--seed",
            str(2**31 + 78), "--seconds", "5", "--trace", "1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 15
    assert '"ok": true' in p.stdout and '"retention-xla"' in p.stdout
    assert res["metrics"]["state_slots_live.reason"]["value"] > 0
    # the accepted reader of decode rows a step has a number for this family
    # too (the cell is appended to it), and mid-prefill slots are not rows
    rows = res["metrics"]["decode_batch_mean.batch"]["value"]
    assert 0 < rows <= res["metrics"]["state_slots_live.reason"]["value"]
    for name in ("retention_update_roofline.reason", "decode_hbm_share.reason"):
        assert name not in res["metrics"]  # device metrics: nothing to read on the CPU


def test_the_family_with_a_broken_sampler_is_not_correct():
    p = run(ROOT, "--workload", "rehearse-brumby-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and '"ok": false' in p.stdout


def control(mode):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_brumby.py"), "--config",
         "rehearse-brumby-tiny", "--mode", mode, "--seeds", "21", "22", "--rehearse"],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["w-int8", "state-bf16", "zero-carry"])
def test_controls_read_far_from_the_sound_runs(mode):
    sound, low = control("sound"), control(mode)
    assert all(sound["verdicts"]), sound
    assert low["logprob_mse_min"] >= 3 * sound["logprob_mse_max"], (sound, low)
