"""run.py end to end at tiny size on the CPU, its refusal without a chip,
and a later PR's additions as new files only."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def run(cwd, *args, env=None, timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run([sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
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


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A throwaway configuration, mix, cell and per-layer metric, created
    beside copies of the committed files; no committed file is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for d, _, fs in os.walk(os.path.join(root, "benchmarks")):
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "rehearse-tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="throwaway-1l", num_hidden_layers=1, tie_word_embeddings=False,
               attention_bias=False)
    with open(os.path.join(b, "configs", "throwaway-1l.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "throwaway-burst.json"), "w") as f:
        json.dump({"loop": "open",
                   "arrivals": {"process": "onoff", "period_s": 2, "on_s": 0.5, "on_factor": 3},
                   "prompt_tokens": {"dist": "uniform", "min": 20, "max": 120},
                   "output_tokens": {"dist": "constant", "value": 6, "min": 6, "max": 6},
                   "sampling": {"temperature": 0.0}, "warmup_seconds": 1}, f)
    with open(os.path.join(b, "cells", "throwaway-1l.burst.json"), "w") as f:
        json.dump({"name": "throwaway-1l.burst", "config": "throwaway-1l",
                   "traffic": "throwaway-burst", "chips": 1, "rate_per_s": 3.0, "why": "test"}, f)
    with open(os.path.join(b, "layer_metrics", "throwaway_requests.py"), "w") as f:
        f.write("def compute(w):\n    return float(len(w.measured()))\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "throwaway-1l", "source": "none",
                           "file": "benchmarks/configs/throwaway-1l.json", "reduced": [], "why": "t"})
    man["workloads"].append({"name": "throwaway-1l.burst", "config": "throwaway-1l",
                             "traffic": "throwaway-burst", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "throwaway_requests", "unit": "requests", "better": "higher",
                             "source": "program_counter", "layer": "load generator",
                             "moves": "tpot_p90_ms", "workloads": ["throwaway-1l.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    p = run(root, "--workload", "throwaway-1l.burst", "--rehearse", "--seed", "3",
            "--seconds", "4", "--trace", "1", env={"PYTHONPATH": ROOT})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["metrics"]["throwaway_requests"] == {"value": 12.0, "unit": "requests"}
    assert "gen_lag_p99_ms" not in res["metrics"]  # listed for other cells only
    assert res["failed"] == 0 and res["device"]["platform"] == "cpu"
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"
