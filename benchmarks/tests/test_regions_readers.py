"""The five readers of the device regions (PR 44) over hand-made windows:
a trace with ops and program durations joined against a map, no trace,
an empty map, and a program without `obs.regions`."""
import importlib.util
import json
import os
import sys
import weakref

import pytest

from benchmarks.harness import regions as harness_regions
from xllm_service_tpu.obs import regions as obs_regions

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
READERS = ("device_named_share", "ffn_ms_per_step", "attn_proj_ms_per_step",
           "head_sample_ms_per_step", "stack_slice_share")


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Window:
    def __init__(self, trace=None):
        self.trace = trace


class Executor:
    """What `obs.regions.register` takes: the maps of its step programs."""

    def __init__(self, programs):
        self.programs, self.calls = programs, 0

    def program_regions(self, budget_s=None):
        self.calls += 1
        return self.programs


@pytest.fixture
def executors(monkeypatch):
    """The process's registry, emptied for the test: `add(programs)`
    registers an executor with those maps and returns it."""
    monkeypatch.setattr(obs_regions, "_EXECUTORS", weakref.WeakSet())
    alive = []

    def add(programs):
        alive.append(Executor(programs))
        obs_regions.register(alive[-1])
        return alive[-1]

    return add


def op(name, shape, opcode="fusion"):
    """An op as a v5e profile names it: the whole HLO line, operand shapes in."""
    return f"%{name} = {shape}{{1,0:T(8,128)}} {opcode}(f32[8,64]{{1,0}} %p.1), kind=kLoop"


DECODE = {
    "fusion.1 bf16[32,2048]": "ffn", "fusion.2 bf16[32,2560]": "attn_proj",
    "fusion.3 f32[32,151936]": "head", "iota_reduce_fusion f32[32]": "sample",
    "constant_dynamic-slice_fusion bf16[2048,11008]": "stack_slice",
    "paged_attention_kernel bf16[32,2,8,128]": "attn", "moe.4 f32[32,8]": "moe_route",
    "moe_grouped_kernel bf16[64,2048]": "moe_experts",
}
MIXED = dict(DECODE, **{"fusion.1 bf16[32,2048]": "attn_proj", "fusion.9 bf16[1,512,2048]": "ffn"})


def trace():
    ops = {
        op("fusion.1", "bf16[32,2048]"): 4e6,       # ffn in decode, attn_proj in mixed: ambiguous
        op("fusion.9", "bf16[1,512,2048]"): 6e6,    # ffn
        op("fusion.2", "bf16[32,2560]"): 2e6,       # attn_proj
        op("fusion.3", "f32[32,151936]"): 1e6,      # head
        op("iota_reduce_fusion", "f32[32]"): 3e6,   # sample
        op("constant_dynamic-slice_fusion", "bf16[2048,11008]"): 1e6,  # stack_slice
        op("paged_attention_kernel", "bf16[32,2,8,128]", "custom-call"): 5e6,  # attn
        op("moe.4", "f32[32,8]"): 1e6,              # moe_route
        op("moe_grouped_kernel", "bf16[64,2048]", "custom-call"): 1e6,  # moe_experts
        op("copy.77", "bf16[958,2,128,128]", "copy"): 1e6,  # in no map: unnamed
    }
    return {
        "ops": ops, "chips": 1,
        "program_durations_ns": {"_decode_impl": [1.0] * 6, "_mixed_impl": [1.0] * 4,
                                 "_import_impl": [1.0] * 90},  # no map: not a step
    }


def test_the_five_readers_over_a_trace_and_a_map(executors, capsys):
    ex = executors({"_decode_impl": [DECODE], "_mixed_impl": [MIXED]})
    w = Window(trace())
    got = {name: reader(name).compute(w) for name in READERS}
    total = 25e6
    assert got["device_named_share"] == pytest.approx(100.0 * (total - 4e6 - 1e6) / total)
    assert got["ffn_ms_per_step"] == pytest.approx((6.0 + 1.0 + 1.0) / 10)  # ffn + moe_route + moe_experts
    assert got["attn_proj_ms_per_step"] == pytest.approx(2.0 / 10)
    assert got["head_sample_ms_per_step"] == pytest.approx((1.0 + 3.0) / 10)
    assert got["stack_slice_share"] == pytest.approx(100.0 * 1e6 / total)
    assert ex.calls == 1  # computed once a window, whichever reader comes first
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("regions: ")]
    assert len(lines) == 1  # ONE log line a traced run
    logged = json.loads(lines[0][len("regions: "):])
    assert logged["steps"] == 10 and logged["programs_mapped"] == {"_decode_impl": 1, "_mixed_impl": 1}
    by_region = logged["by_region"]
    assert by_region["ambiguous"] == [0.004, {"fusion": 0.004}]
    assert by_region["unnamed"] == [0.001, {"copy": 0.001}]
    assert by_region["attn"] == [0.005, {"paged_attention_kernel": 0.005}]
    assert sum(v[0] for v in by_region.values()) == pytest.approx(logged["total_s"])
    r = harness_regions.window_regions(w)
    assert sum(r["ns"].values()) == pytest.approx(r["total_ns"])  # nothing lost in the join


def test_four_chips_count_a_step_once(executors):
    executors({"_decode_impl": [DECODE]})
    t = trace()
    t["chips"] = 4
    t["program_durations_ns"] = {"_decode_impl": [1.0] * 40}  # ten steps, an event a chip
    assert reader("attn_proj_ms_per_step").compute(Window(t)) == pytest.approx(2.0 / 10)


@pytest.mark.parametrize("name", READERS)
def test_no_trace_reads_nothing_and_an_empty_map_reads_zero(executors, name):
    assert reader(name).compute(Window(None)) is None  # --rehearse, --trace 0
    executors({})  # an executor whose scopes name nothing (or none alive)
    assert reader(name).compute(Window(trace())) == 0.0  # a listed metric has to be on the line
    ex = executors({"_decode_impl": [{}]})
    assert reader(name).compute(Window(trace())) == 0.0
    assert ex.calls == 1


def test_a_program_that_cannot_be_mapped_reads_zero_and_says_why(executors, capsys):
    class Broken:
        def program_regions(self, budget_s=None):
            raise RuntimeError("the compiler refused")

    broken = Broken()
    obs_regions.register(broken)
    assert reader("device_named_share").compute(Window(trace())) == 0.0
    assert "could not be mapped: RuntimeError('the compiler refused')" in capsys.readouterr().out


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_obs_regions_reads_nothing(monkeypatch, name):
    # the parent of PR 44: the import fails, the metric is left out, nothing raises
    import xllm_service_tpu.obs

    monkeypatch.setitem(sys.modules, "xllm_service_tpu.obs.regions", None)
    monkeypatch.delattr(xllm_service_tpu.obs, "regions")
    assert reader(name).compute(Window(trace())) is None
