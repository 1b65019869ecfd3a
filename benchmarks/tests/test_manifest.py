"""Every name in BENCHMARK.json resolves to a file and is legal."""
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and m["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" and "workloads" not in e for e in m["end_to_end"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_name_resolves_to_a_file():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for c in m["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"] and len(c["source"]) <= 200
        assert data["reduced"] == c["reduced"] and "assumed" in data and "deployment" in data
        assert os.path.exists(os.path.join(BENCH, "families", data["family"] + ".py"))
        assert any(w["config"] == c["name"] for w in m["workloads"])
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(BENCH, "cells", w["name"] + ".json")) as f:
            cell = json.load(f)
        for k in ("config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "configs", w["config"] + ".json"))
    for e in m["end_to_end"]:
        assert os.path.exists(os.path.join(BENCH, "end_to_end", e["name"] + ".py"))
        assert set(e.get("workloads", cells)) <= cells
    layers = set()
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", e["name"] + ".py"))
        assert e["moves"] in e2e and set(e.get("workloads", cells)) <= cells
        layers.add(e["layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layer list lacks {layer!r}"
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    # every cell reports setup_s, one more end-to-end metric, one per-layer
    for c in cells:
        assert sum(c in e.get("workloads", cells) for e in m["end_to_end"]) >= 2
        assert any(c in e.get("workloads", cells) for e in m["per_layer"])


def test_files_under_paths_have_legal_names():
    import subprocess

    out = subprocess.run(["git", "ls-files", "-co", "--exclude-standard", "benchmarks"],
                         cwd=ROOT, capture_output=True, text=True)
    files = out.stdout.split() if out.returncode == 0 else []
    for f in files:
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", f), f
