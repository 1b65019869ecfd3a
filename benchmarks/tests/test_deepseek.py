"""The DeepSeek-V2 family's additions (PR 39): its counts by hand, its
configuration against the published row, its traffic mix through the
generator, its readers over a made-up window, and its controls at a size
the CPU holds."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import counts, counts_deepseek as cd, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_counts_by_hand():
    m = load("configs", "deepseek-v2")
    c = cd.param_counts(m)
    # ISSUE 39's arithmetic: w_dq 7.86, w_uq 37.75, w_dkv 2.95, w_uk + w_uv 16.78, wo 83.89 (M)
    assert c["attention"] == 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 128 * 512 * 256 + 128 * 128 * 5120
    assert round(c["attention"] / 1e6, 1) == 149.2 and c["expert"] == 3 * 5120 * 1536 == 23_592_960
    assert c["shared"] == 2 * c["expert"] and c["router"] == 5120 * 160
    assert round(c["dense_layer"] / 1e6, 1) == 338.0 and round(c["expert_layer"] / 1e6) == 1141
    assert round(c["total"] / 1e6) == 5164 and c["lm_head"] == 25600 * 5120
    assert cd.decode_weight_bytes(m) == (c["total"] - c["embed"]) * 2  # 10.07 GB: a floor of 12.3 ms
    assert 12.2e-3 < counts.hbm_time_s(cd.decode_weight_bytes(m), "TPU v5 lite") < 12.4e-3
    assert cd.fixed_weight_bytes(m) == cd.decode_weight_bytes(m) - 4 * 40 * cd.expert_bytes(m)
    assert cd.latent_bytes_per_token(m) == 5 * 576 * 2 == 5760  # stored: 5 x 640 x 2 = 6,400
    assert cd.attention_pair_flops(m, absorbed=True) == 2 * (576 + 512) * 128 == 278_528
    assert cd.attention_pair_flops(m, absorbed=False) == 2 * 320 * 128
    assert cd.chunk_pairs(0, 512) == 512 * 513 // 2 and cd.chunk_pairs(7168, 512) == 512 * 7168 + 512 * 513 // 2
    assert cd.chunk_attention_flops(m, 7168, 512) == 5 * 278_528 * cd.chunk_pairs(7168, 512)
    assert cd.decode_attention_flops(m, 4000) == 5 * 278_528 * 4000
    assert cd.expert_pair_flops(m) == 6 * 5120 * 1536
    assert cd.token_matrix_flops(m) == 2 * (5 * c["attention"] + c["dense_mlp"] + 4 * (c["shared"] + c["router"]))


def test_the_configuration_is_the_published_one_cut_as_issue_39_says():
    m = load("configs", "deepseek-v2")
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v2", "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 128,
        "num_experts_per_tok": 6, "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "scoring_func": "softmax", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 3, "topk_method": "group_limited_greedy",
        "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                         "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"},
    }
    assert {k: m[k] for k in published} == published
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"]) == (5, 40, 25600)
    assert (m["num_hidden_layers_published"], m["n_routed_experts_published"], m["vocab_size_published"]) \
        == (60, 160, 102400)
    assert m["experts_held"] == [0, 40] and m["family"] == "deepseek" and "four chips" in m["deployment"]
    assert m["engine"]["max_prefill_tokens"] == 512 and m["engine"]["prefill_buckets"] == [512]


def test_the_mix_is_whole_chunks_under_the_sliced_vocabulary():
    cell, traffic = load("cells", "deepseek-v2.doc-steady"), load("traffic", "doc-steady")
    plan = loadgen.build_plan(traffic, cell, 2**31 + 5, 45.0)
    lens = sorted(r["prompt_len"] for r in plan["requests"])
    assert lens[0] >= 1024 and lens[-1] <= 7680 and all(n % 512 == 0 for n in lens)
    outs = [r["out_len"] for r in plan["requests"]]
    assert min(outs) >= 16 and max(outs) <= 384 and plan["loop"] == "open"
    assert plan["sampling"] == {"temperature": 0.7}
    other = loadgen.build_plan(traffic, cell, 7, 45.0)  # schedule_seed pins arrivals and prompts
    assert [(r["due"], r["prompt_len"]) for r in plan["requests"]] == \
        [(r["due"], r["prompt_len"]) for r in other["requests"]]
    assert max(traffic["warm_shapes"]["background_prompts"]) + traffic["warm_shapes"]["background_output"] <= 8192
    assert traffic["warm_shapes"]["probe_prompt"] == 7680


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


def window(with_program=True):
    m = load("configs", "deepseek-v2")
    taps = {
        # prefilling through the span: 4 chunks done at t=10, 11, 12, 13; the first two fall in [10, 12)
        "a": {"prompt_len": 2048, "t_add": 9.0, "times": [13.0], "counts": [1]},
        # decoding through it: 3 tokens inside, contexts 1024 + 5, 6, 7
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0, 5.0, 10.5, 11.0, 11.5, 12.5],
              "counts": [1, 4, 1, 1, 1, 1]},
    }
    ops = {"%mla_prefill_kernel.3 = ...": 40e6, "%mla_paged_attention_kernel.1 = ...": 2e6,
           "%moe_grouped_kernel.7 = ...": 30e6, "%moe_grouped_down_kernel.8 = ...": 15e6} if with_program else {}
    trace = {"ops": ops, "program_durations_ns": {"_mixed_impl": [40e6, 40e6], "_decode_impl": [15e6]}}
    deltas = {"xllm_engine_decode_steps_total": 1000.0, "xllm_engine_prefill_chunks_total": 600.0}
    if with_program:
        deltas.update({"xllm_engine_moe_pairs_per_expert_sum": 10000.0,
                       "xllm_engine_moe_pairs_per_expert_count": 40000.0})
    return FakeWindow(config=m, taps=taps, trace=trace, trace_span=(10.0, 12.0), t_zero=0.0, deltas=deltas)


def test_the_readers_count_what_the_traced_steps_held():
    w = window()
    m = w.config
    assert cd.traced_decode_contexts(w) == [1029, 1030, 1031]
    assert cd.traced_chunk_starts(w, 512) == [0, 512]
    flops = sum(cd.chunk_attention_flops(m, s, 512) for s in (0, 512))
    assert reader("mla_prefill_mfu.doc").compute(w) == pytest.approx(100 * flops / 197e12 / 0.040)
    hbm = (1029 + 1030 + 1031) * 5760 / 819e9
    mxu = (1029 + 1030 + 1031) * 5 * 278_528 / 197e12
    assert hbm < mxu * 240 / 218 * 1.2  # neither side bounds it alone
    assert reader("mla_decode_roofline.doc").compute(w) == pytest.approx(100 * max(hbm, mxu) / 0.002)
    # model FLOPs: 1027 tokens through the matrices and 4 layers x 6 x 40 / 160 pairs each, 5 heads,
    # attention in the materialised count
    assert cd.routed_pairs_per_token(m) == 4 * 1.5
    model = (1027 * (cd.token_matrix_flops(m) + 6 * cd.expert_pair_flops(m)) + 5 * cd.head_flops(m)
             + sum(cd.chunk_attention_flops(m, s, 512, absorbed=False) for s in (0, 512))
             + 3090 * 5 * cd.attention_pair_flops(m, absorbed=False))
    assert reader("step_mfu.doc").compute(w) == pytest.approx(100 * model / 197e12 / 0.095)
    assert reader("moe_pairs_per_expert.doc").compute(w) == pytest.approx(0.25)
    assert reader("prefill_step_share.doc").compute(w) == pytest.approx(60.0)
    assert w.checks == {}  # three step programs: a ratio of so few says nothing


def test_prompts_prefill_one_after_another():
    """A prompt added while another prefills starts where that one ended,
    not where it was added: its chunks are not spread over its queue wait."""
    w = window()
    w.taps["c"] = {"prompt_len": 1024, "t_add": 9.5, "times": [15.0], "counts": [1]}
    w.trace_span = (10.0, 14.5)
    # a: chunks end at 10, 11, 12, 13; c waits for a: 13 -> 15, chunks end at 14, 15
    assert cd.traced_chunk_starts(w, 512) == [0, 512, 1024, 1536, 0]


def test_the_taps_counts_are_held_against_the_traces_own():
    """PR 33's check holds the tap's rows against the WINDOW's mean rows a
    step, which an open loop's 3 s leave by a factor of two; these hold the
    tap's steps and chunks against the step programs the trace itself has."""
    w = window()
    steps = [10.0 + 0.01 * i for i in range(40)]  # a step every 10 ms, two rows each, booked 0.3 ms apart
    w.taps = {
        "b": {"prompt_len": 1024, "t_add": 0.0, "times": [1.0] + steps, "counts": [1] * 41},
        "d": {"prompt_len": 2048, "t_add": 0.0, "times": [2.0] + [t + 3e-4 for t in steps], "counts": [1] * 41},
        "a": {"prompt_len": 7680, "t_add": 9.9, "times": [10.3], "counts": [1]},  # 15 chunks, the last at 10.3
    }
    w.trace_span = (10.0, 10.4)
    w.trace["program_durations_ns"] = {"_mixed_impl": [20e6] * 12, "_decode_impl": [8e6] * 28}
    assert len(cd.traced_decode_contexts(w)) == 80
    assert w.checks["traced_steps_ratio.doc"]["value"] == pytest.approx(1.0)  # a's first token is a step's
    assert len(cd.traced_chunk_starts(w, 512)) == 12  # 15 from 9.9 to 10.3: those that end at 10.007 .. 10.3
    assert w.checks["traced_chunks_ratio.doc"]["value"] == pytest.approx(1.0)
    from benchmarks.run import checks_off

    assert checks_off(w.checks) == []
    w.trace_span = (10.0, 10.2)  # the span's end taken wrong (PR 31's fault was the profile's write time)
    cd.traced_decode_contexts(w), cd.traced_chunk_starts(w, 512)
    assert checks_off(w.checks) == ["traced_steps_ratio.doc", "traced_chunks_ratio.doc"]


def test_a_program_without_the_kernels_and_counters_reads_as_nothing():
    """The parent of PR 39: no such kernel names in a trace, no such
    series on /metrics. Every reader of a kernel or a series returns None
    and raises nothing; without a trace every traced one does."""
    w = window(with_program=False)
    for name in ("mla_prefill_mfu.doc", "mla_decode_roofline.doc", "moe_pairs_per_expert.doc"):
        assert reader(name).compute(w) is None, name
    w.trace = None
    for name in ("mla_prefill_mfu.doc", "mla_decode_roofline.doc", "step_mfu.doc"):
        assert reader(name).compute(w) is None, name


@pytest.mark.parametrize("mode,ok", [("sound", True), ("w-int8", False), ("kv-int8", False),
                                     ("no-shared", False), ("unscaled", False), ("wrong-expert", False),
                                     ("long", True)])
def test_controls_on_the_cpu(mode, ok):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    args = ["--config", "rehearse-deepseek-tiny", "--mode", mode, "--seeds", "3", "--rehearse"]
    if mode == "long":
        args += ["--long-prompt", "896"]
    out = subprocess.run([sys.executable, os.path.join(HERE, "control_deepseek.py"), *args],
                         cwd=ROOT, env=e, capture_output=True, text=True, timeout=900)
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["verdicts"] == [ok], summary
