"""run.py end to end at tiny size on the CPU, its refusal without a chip,
and a later PR's additions (a configuration, a family) as new files only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def run(cwd, *args, env=None, timeout=600, script=("benchmarks", "run.py")):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run([sys.executable, os.path.join(cwd, *script), *args],
                          cwd=cwd, env=e, capture_output=True, text=True, timeout=timeout)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_without_a_chip_it_fails_and_prints_no_result():
    p = run(ROOT, "--workload", "qwen2.5-3b.chat-steady", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no accelerator" in p.stderr or "only the CPU" in p.stderr


def test_rehearsal_end_to_end():
    p = run(ROOT, "--workload", "rehearse-tiny.rehearse", "--rehearse", "--seed", str(2**31 + 77),
            "--seconds", "5", "--trace", "0")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] == 15 and res["failed"] == 0
    assert '"ok": true' in p.stdout  # the served sample is within the reference's limits
    assert p.stdout.index("correct: ") < p.stdout.index("bridge: decoding") < p.stdout.index("window: ")
    assert {"setup_s", "ttft_p50_ms", "tpot_p90_ms", "out_tokens_per_s"} <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert "correct: " in p.stdout and '"logprob_mse_limit"' in p.stdout


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The whole of a rehearsal with the program's sampler broken
    underneath (broken_sampler.py): the run ends, and `correct` is false."""
    p = run(ROOT, "--workload", "rehearse-tiny.rehearse", "--rehearse", "--seed", "5",
            "--seconds", "3", "--trace", "0", script=("benchmarks", "tests", "broken_sampler.py"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is False and res["failed"] == 0
    assert '"ok": false' in p.stdout
    assert "not correct: the served sample misses the reference's limits" in p.stderr


def copy_of_the_benchmark(tmp_path):
    """benchmarks/ copied under tmp_path, and every copied file's bytes."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for d, _, fs in os.walk(os.path.join(root, "benchmarks")):
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    return root, os.path.join(root, "benchmarks"), before


def dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def llama_config_of_its_own(b):
    """A second configuration of a family the benchmark has, under a mix
    of ragged prompts (no warm_shapes: `correct` is not asked of it)."""
    with open(os.path.join(b, "configs", "rehearse-tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="throwaway-1l", num_hidden_layers=1, tie_word_embeddings=False,
               attention_bias=False)
    mix = {"loop": "open",
           "arrivals": {"process": "onoff", "period_s": 2, "on_s": 0.5, "on_factor": 3},
           "prompt_tokens": {"dist": "uniform", "min": 20, "max": 120},
           "output_tokens": {"dist": "constant", "value": 6, "min": 6, "max": 6},
           "sampling": {"temperature": 0.0}, "warmup_seconds": 1}
    return cfg, mix, {"seed": "3", "requests": 12.0, "correct": None}


def mla_family_of_its_own(b):
    """A family file the benchmark does not have (MLA at deepseek-tiny's
    sizes: the program's other parameter tree, one latent cache), with its
    configuration; the rehearsal's mix, so every shape is warmed."""
    shutil.copy(os.path.join(HERE, "fixture_mla_family.py"),
                os.path.join(b, "families", "throwaway-mla.py"))
    with open(os.path.join(b, "configs", "rehearse-tiny.json")) as f:
        engine = json.load(f)["engine"]
    cfg = {"name": "throwaway-1l", "source": "none", "family": "throwaway-mla",
           "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
           "num_attention_heads": 4, "kv_lora_rank": 40, "q_lora_rank": 48,
           "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 24,
           "vocab_size": 512, "max_position_embeddings": 1024, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
           "reduced": [], "assumed": [], "deployment": "none", "chips": 1, "engine": engine}
    with open(os.path.join(b, "traffic", "rehearse.json")) as f:
        mix = json.load(f)
    return cfg, mix, {"seed": str(2**31 + 5), "requests": 12.0, "correct": True}


@pytest.mark.parametrize("additions", [llama_config_of_its_own, mla_family_of_its_own])
def test_a_later_pr_adds_files_and_entries_only(tmp_path, additions):
    """A throwaway configuration (of a family the benchmark has, or with a
    family file of its own), mix, cell and per-layer metric, created
    beside copies of the committed files; no committed file is edited."""
    root, b, before = copy_of_the_benchmark(tmp_path)
    cfg, mix, want = additions(b)
    dump(cfg, b, "configs", "throwaway-1l.json")
    dump(mix, b, "traffic", "throwaway-burst.json")
    dump({"name": "throwaway-1l.burst", "config": "throwaway-1l", "traffic": "throwaway-burst",
          "chips": 1, "rate_per_s": 3.0, "why": "test"}, b, "cells", "throwaway-1l.burst.json")
    with open(os.path.join(b, "layer_metrics", "throwaway_requests.py"), "w") as f:
        f.write("def compute(w):\n    return float(len(w.measured()))\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        committed_manifest = f.read()
    man = json.loads(committed_manifest)
    man["configs"].append({"name": "throwaway-1l", "source": "none",
                           "file": "benchmarks/configs/throwaway-1l.json", "reduced": [], "why": "t"})
    man["workloads"].append({"name": "throwaway-1l.burst", "config": "throwaway-1l",
                             "traffic": "throwaway-burst", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "throwaway_requests", "unit": "requests", "better": "higher",
                             "source": "program_counter", "layer": "load generator",
                             "moves": "tpot_p90_ms", "workloads": ["throwaway-1l.burst"]})
    dump(man, root, "BENCHMARK.json")
    p = run(root, "--workload", "throwaway-1l.burst", "--rehearse", "--seed", want["seed"],
            "--seconds", "4", "--trace", "1", env={"PYTHONPATH": ROOT})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["metrics"]["throwaway_requests"] == {"value": want["requests"], "unit": "requests"}
    assert "gen_lag_p99_ms" not in res["metrics"]  # listed for other cells only
    assert res["failed"] == 0 and res["device"]["platform"] == "cpu"
    if want["correct"] is not None:
        assert res["correct"] is want["correct"], p.stdout[-3000:]
        assert '"ok": true' in p.stdout  # against the fixture's own reference
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert f.read() == committed_manifest


def without_the_family_key(b, cfg):
    del cfg["family"]
    return '"family"'


def naming_a_family_without_a_file(b, cfg):
    cfg["family"] = "nowhere"
    return "'nowhere' has no file"


def whose_family_file_lacks_a_name(b, cfg):
    with open(os.path.join(b, "families", "llama.py")) as f:
        src = f.read().replace("def forward_logits(", "def forward(")
    with open(os.path.join(b, "families", "throwaway-short.py"), "w") as f:
        f.write(src)
    cfg["family"] = "throwaway-short"
    return "lacks forward_logits"


@pytest.mark.parametrize("fault", [without_the_family_key, naming_a_family_without_a_file,
                                   whose_family_file_lacks_a_name])
def test_a_configuration_is_refused_before_anything_is_built(tmp_path, fault):
    root, b, _ = copy_of_the_benchmark(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(b, "configs", "rehearse-tiny.json")) as f:
        cfg = json.load(f)
    said = fault(b, cfg)
    dump(cfg, b, "configs", "rehearse-tiny.json")
    p = run(root, "--workload", "rehearse-tiny.rehearse", "--rehearse", "--seed", "1",
            "--seconds", "2", "--trace", "0", env={"PYTHONPATH": ROOT}, timeout=120)
    assert p.returncode != 0 and said in p.stderr, p.stderr[-2000:]
    assert "device:" not in p.stdout and not any(l.startswith("{") for l in p.stdout.splitlines())
