"""bench.py clean-load CPU decode regression guard (VERDICT r5 #2).

Pure-logic tests over _cpu_regression_guard — no model, no timing. The
guard must (a) fail loudly on a clean-load >5% CPU regression, (b) abstain
on hot hosts (the r3 precedent) and on hosts smaller than the anchor's
class, and (c) never touch TPU results or unparseable lines.
"""

import json

import pytest

import bench


@pytest.fixture(autouse=True)
def _anchor(monkeypatch):
    # Pin the knobs so the assertions don't depend on env or host size.
    monkeypatch.setattr(bench, "_BEST_CPU_DECODE_TOK_S", 4262.9)
    monkeypatch.setattr(bench, "_GUARD_LOADAVG_CEILING", 1.0)
    monkeypatch.setattr(bench, "_GUARD_MIN_CPUS", 1)
    monkeypatch.setattr(bench, "_OVERLAP_MIN_RATIO", 0.92)
    monkeypatch.setattr(bench, "_RAGGED_MIN_RATIO", 0.95)


def _line(**kw):
    d = {"backend": "cpu", "value": 4262.9,
         "loadavg_1m": 0.2, "loadavg_1m_start": 0.2}
    d.update(kw)
    return json.dumps(d)


def test_clean_load_regression_fails():
    out, rc = bench._cpu_regression_guard(_line(value=3901.8))  # the r5 drop
    assert rc == 3
    assert json.loads(out)["cpu_regression_guard"].startswith("FAIL")


def test_within_five_percent_passes():
    out, rc = bench._cpu_regression_guard(_line(value=4060.0))  # -4.8%
    assert rc == 0
    assert json.loads(out)["cpu_regression_guard"] == "ok"


def test_hot_host_abstains():
    out, rc = bench._cpu_regression_guard(
        _line(value=100.0, loadavg_1m=3.0)
    )
    assert rc == 0
    assert "loadavg" in json.loads(out)["cpu_regression_guard"]


def test_small_host_abstains(monkeypatch):
    monkeypatch.setattr(bench, "_GUARD_MIN_CPUS", 10_000)
    out, rc = bench._cpu_regression_guard(_line(value=100.0))
    assert rc == 0
    assert "host below" in json.loads(out)["cpu_regression_guard"]


def test_tpu_result_untouched():
    line = json.dumps({"backend": "tpu", "value": 1.0})
    out, rc = bench._cpu_regression_guard(line)
    assert rc == 0
    assert "cpu_regression_guard" not in json.loads(out)


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv("XLLM_BENCH_NO_REGRESSION_GUARD", "1")
    out, rc = bench._cpu_regression_guard(_line(value=10.0))
    assert rc == 0
    assert out == _line(value=10.0)


def test_non_json_line_passes_through():
    out, rc = bench._cpu_regression_guard("not json")
    assert (out, rc) == ("not json", 0)


# ---- overlapped-engine A/B guard (runs against the overlapped default
# mode; docs/ENGINE_PIPELINE.md) ----


def _eb(sync_tok, overlap_tok):
    return {
        "sync": {"mode": "sync", "tok_s": sync_tok},
        "overlap": {"mode": "overlap", "tok_s": overlap_tok},
    }


def test_overlap_at_parity_passes():
    out, rc = bench._cpu_regression_guard(
        _line(engine_bench=_eb(100.0, 99.0))
    )
    assert rc == 0
    assert json.loads(out)["engine_overlap_guard"] == "ok"


def test_overlap_regression_fails():
    out, rc = bench._cpu_regression_guard(
        _line(engine_bench=_eb(100.0, 80.0))
    )
    assert rc == 3
    assert json.loads(out)["engine_overlap_guard"].startswith("FAIL")


def test_overlap_guard_needs_both_modes():
    # --engine-mode sync|overlap runs one mode: nothing to A/B.
    out, rc = bench._cpu_regression_guard(
        _line(engine_bench={"overlap": {"tok_s": 50.0}})
    )
    assert rc == 0
    assert "engine_overlap_guard" not in json.loads(out)


def test_overlap_guard_abstains_on_hot_host():
    out, rc = bench._cpu_regression_guard(
        _line(value=100.0, loadavg_1m=3.0, engine_bench=_eb(100.0, 10.0))
    )
    assert rc == 0
    assert "engine_overlap_guard" not in json.loads(out)


# ---- mixed-vs-split attention A/B guard (--attention-mode both; one
# ragged dispatch per engine step vs the split-step escape hatch,
# docs/KERNELS.md) ----


def _ab(split_tok, ragged_tok):
    return {
        "split": {"step_builder": "split", "tok_s": split_tok},
        "ragged": {"step_builder": "ragged", "tok_s": ragged_tok},
    }


def test_ragged_at_parity_passes():
    out, rc = bench._cpu_regression_guard(
        _line(attention_bench=_ab(100.0, 96.0))
    )
    assert rc == 0
    assert json.loads(out)["engine_ragged_guard"] == "ok"


def test_ragged_regression_fails():
    out, rc = bench._cpu_regression_guard(
        _line(attention_bench=_ab(100.0, 90.0))
    )
    assert rc == 3
    assert json.loads(out)["engine_ragged_guard"].startswith("FAIL")


def test_ragged_guard_needs_both_modes():
    # --attention-mode split|ragged runs one mode: nothing to A/B.
    out, rc = bench._cpu_regression_guard(
        _line(attention_bench={"ragged": {"tok_s": 50.0}})
    )
    assert rc == 0
    assert "engine_ragged_guard" not in json.loads(out)


def test_ragged_guard_abstains_on_hot_host():
    out, rc = bench._cpu_regression_guard(
        _line(value=100.0, loadavg_1m=3.0, attention_bench=_ab(100.0, 10.0))
    )
    assert rc == 0
    assert "engine_ragged_guard" not in json.loads(out)


def test_ragged_guard_abstains_on_builder_mismatch():
    # A family without a mixed step runs split whatever the per-run
    # config asks: both rows ran split, so a passing ratio would be
    # vacuous — the guard must abstain loudly rather than stamp "ok" on
    # split-vs-split.
    ab = _ab(100.0, 96.0)
    ab["ragged"]["step_builder"] = "split"
    out, rc = bench._cpu_regression_guard(_line(attention_bench=ab))
    assert rc == 0
    assert json.loads(out)["engine_ragged_guard"].startswith("abstained")


# ---- combined-path A/B guard (--spec-mode both; speculative decode on
# the composed overlap+mixed pipeline vs the sync+split verify engine,
# ISSUE 13 / docs/ENGINE_PIPELINE.md) ----


def _sb(sync_tok, composed_tok):
    return {
        "composed": {
            "step_builder": "spec-overlap+mixed", "tok_s": composed_tok,
        },
        "sync_split": {
            "step_builder": "spec-sync+split", "tok_s": sync_tok,
        },
    }


def test_spec_at_parity_passes():
    out, rc = bench._cpu_regression_guard(
        _line(spec_bench=_sb(100.0, 96.0))
    )
    assert rc == 0
    assert json.loads(out)["engine_spec_guard"] == "ok"


def test_spec_regression_fails():
    out, rc = bench._cpu_regression_guard(
        _line(spec_bench=_sb(100.0, 90.0))
    )
    assert rc == 3
    assert json.loads(out)["engine_spec_guard"].startswith("FAIL")


def test_spec_guard_needs_both_modes():
    # --spec-mode composed|sync runs one mode: nothing to A/B.
    out, rc = bench._cpu_regression_guard(
        _line(spec_bench={"composed": {"tok_s": 50.0}})
    )
    assert rc == 0
    assert "engine_spec_guard" not in json.loads(out)


def test_spec_guard_abstains_on_hot_host():
    out, rc = bench._cpu_regression_guard(
        _line(value=100.0, loadavg_1m=3.0, spec_bench=_sb(100.0, 10.0))
    )
    assert rc == 0
    assert "engine_spec_guard" not in json.loads(out)


def test_spec_guard_abstains_on_builder_mismatch():
    # The "composed" row's engine resolved depth 0: it actually ran the
    # sync verify steps, so a passing ratio would be vacuous — abstain
    # loudly rather than stamp "ok" on sync-vs-sync.
    sb = _sb(100.0, 96.0)
    sb["composed"]["step_builder"] = "spec-sync+split"
    out, rc = bench._cpu_regression_guard(_line(spec_bench=sb))
    assert rc == 0
    assert json.loads(out)["engine_spec_guard"].startswith("abstained")


# ---- the grouped-MoE A/B guard went with XLLM_MOE_KERNEL (PR 39): there
# is one expert product and no dense row to hold it against ----


def test_bench_has_no_moe_ab_left():
    """bench.py reports the expert model's row and offers no switch and
    no guard that would need a dense all-experts run."""
    src = open(bench.__file__).read()
    assert not hasattr(bench, "_moe_guard")
    assert "XLLM_MOE_KERNEL" not in src and '"--moe"' not in src


# ------------------------------------------------- mesh guard (--mesh)


def _mesh_line(**kw):
    d = {
        "backend": "tpu", "value": 1000.0,
        "mesh": {"dp": 1, "tp": 8, "ep": 1},
        "decode_roofline": {"expected_tok_s": 1500.0},
    }
    d.update(kw)
    return json.dumps(d)


def test_mesh_guard_skips_unsharded_rows():
    out, rc = bench._mesh_guard(_line())
    assert rc == 0
    assert "engine_mesh_guard" not in json.loads(out)


def test_mesh_guard_abstains_off_tpu():
    # The CPU virtual mesh proves parity in tier-1, not performance —
    # the guard must say so loudly instead of comparing meaningless
    # CPU numbers against a v5e roofline.
    out, rc = bench._mesh_guard(_mesh_line(backend="cpu"))
    assert rc == 0
    g = json.loads(out)["engine_mesh_guard"]
    assert g.startswith("abstained") and "tier-1" in g


def test_mesh_guard_above_floor_passes():
    out, rc = bench._mesh_guard(_mesh_line(value=800.0))  # 53% of 1500
    assert rc == 0
    assert json.loads(out)["engine_mesh_guard"] == "ok"


def test_mesh_guard_below_floor_fails():
    # A GSPMD-replicated kernel / silent gather fallback is ~tp× off the
    # per-shard roofline: exit 3, with the diagnosis in the message.
    out, rc = bench._mesh_guard(_mesh_line(value=100.0))
    assert rc == 3
    assert json.loads(out)["engine_mesh_guard"].startswith("FAIL")


# ---------------------------------------------------------------------------
# bench_serving --pd-adapt goodput guard (ISSUE 16)
# ---------------------------------------------------------------------------

import bench_serving


@pytest.fixture(autouse=True)
def _adapt_env(monkeypatch):
    # The guard reads these at call time; pin them off so assertions
    # don't depend on the invoking shell.
    monkeypatch.delenv("XLLM_BENCH_NO_REGRESSION_GUARD", raising=False)
    monkeypatch.delenv("XLLM_BENCH_PD_ADAPT_MIN_RATIO", raising=False)


def _adapt_line(a=2500.0, s=500.0, m=1500.0, acted=40, **kw):
    d = {
        "metric": "pd_adapt",
        "goodput": {
            "adaptive": {"goodput_tok_s": a, "acted": acted},
            "static_pd": {"goodput_tok_s": s},
            "all_mix": {"goodput_tok_s": m},
        },
    }
    d.update(kw)
    return json.dumps(d)


def test_pd_adapt_guard_win_passes():
    out, rc = bench_serving._pd_adapt_guard(_adapt_line())
    assert rc == 0
    assert json.loads(out)["pd_adapt_guard"] == "ok"


def test_pd_adapt_guard_loss_to_all_mix_fails():
    # Adaptive under the best static baseline: the controller routed
    # against its own goodput model — exit 3, both baselines named.
    out, rc = bench_serving._pd_adapt_guard(_adapt_line(a=1200.0))
    assert rc == 3
    g = json.loads(out)["pd_adapt_guard"]
    assert g.startswith("FAIL") and "1500.0" in g and "static" in g


def test_pd_adapt_guard_loss_to_static_pd_fails():
    out, rc = bench_serving._pd_adapt_guard(
        _adapt_line(a=400.0, s=500.0, m=300.0)
    )
    assert rc == 3
    assert json.loads(out)["pd_adapt_guard"].startswith("FAIL")


def test_pd_adapt_guard_inert_controller_fails():
    # Tied goodput but zero actionable decisions: an inert controller
    # (XLLM_GOODPUT_CONTROLLER=0, cold EWMAs) must not pass its own A/B.
    out, rc = bench_serving._pd_adapt_guard(_adapt_line(acted=0))
    assert rc == 3
    assert "0 actionable decisions" in json.loads(out)["pd_adapt_guard"]


def test_pd_adapt_guard_min_ratio_env(monkeypatch):
    # 2500 vs best 1500 is a 1.67x win; demanding 2x must fail it.
    monkeypatch.setenv("XLLM_BENCH_PD_ADAPT_MIN_RATIO", "2.0")
    out, rc = bench_serving._pd_adapt_guard(_adapt_line())
    assert rc == 3
    assert "200%" in json.loads(out)["pd_adapt_guard"]


def test_pd_adapt_guard_all_zero_abstains():
    # No mode met any SLO: the host is too noisy for the --adapt-slo-*
    # constants to mean anything — loud abstain, not a fail.
    out, rc = bench_serving._pd_adapt_guard(
        _adapt_line(a=0.0, s=0.0, m=0.0)
    )
    assert rc == 0
    assert json.loads(out)["pd_adapt_guard"].startswith("abstained")


def test_pd_adapt_guard_unparseable_goodput_abstains():
    line = json.dumps({
        "metric": "pd_adapt",
        "goodput": {
            "adaptive": {"goodput_tok_s": None, "acted": 40},
            "static_pd": {"goodput_tok_s": 1.0},
            "all_mix": {"goodput_tok_s": 1.0},
        },
    })
    out, rc = bench_serving._pd_adapt_guard(line)
    assert rc == 0
    assert "unparseable" in json.loads(out)["pd_adapt_guard"]


def test_pd_adapt_guard_other_rows_untouched():
    line = json.dumps({"metric": "pd", "value": 1.0})
    out, rc = bench_serving._pd_adapt_guard(line)
    assert rc == 0 and out == line


def test_pd_adapt_guard_non_json_untouched():
    out, rc = bench_serving._pd_adapt_guard("plain text line")
    assert rc == 0 and out == "plain text line"


def test_pd_adapt_guard_kill_switch(monkeypatch):
    monkeypatch.setenv("XLLM_BENCH_NO_REGRESSION_GUARD", "1")
    out, rc = bench_serving._pd_adapt_guard(_adapt_line(a=0.0, acted=0))
    assert rc == 0
    assert "pd_adapt_guard" not in json.loads(out)


# ---- latency-hiding collectives A/B guard + warm-start host-gap
# ceiling (--overlap both, ISSUE 18 / docs/SHARDING.md) ----


def _ob(off_tok, on_tok, on_routed=True, off_routed=False):
    return {
        "on": {"tok_s": on_tok, "overlap_collectives": on_routed},
        "off": {"tok_s": off_tok, "overlap_collectives": off_routed},
    }


def _ovl_line(**kw):
    d = {"backend": "cpu", "value": 100.0,
         "loadavg_1m": 0.2, "loadavg_1m_start": 0.2}
    d.update(kw)
    return json.dumps(d)


def test_overlap_coll_at_parity_passes(monkeypatch):
    monkeypatch.setattr(bench, "_OVERLAP_COLL_MIN_RATIO", 0.97)
    out, rc = bench._overlap_guard(
        _ovl_line(backend="tpu", overlap_bench=_ob(100.0, 98.0))
    )
    assert rc == 0
    assert json.loads(out)["engine_overlap_collectives_guard"] == "ok"


def test_overlap_coll_regression_fails(monkeypatch):
    monkeypatch.setattr(bench, "_OVERLAP_COLL_MIN_RATIO", 0.97)
    out, rc = bench._overlap_guard(
        _ovl_line(backend="tpu", overlap_bench=_ob(100.0, 80.0))
    )
    assert rc == 3
    assert json.loads(out)[
        "engine_overlap_collectives_guard"
    ].startswith("FAIL")


def test_overlap_coll_abstains_on_cpu_virtual_mesh():
    # The mesh-guard precedent: a CPU virtual mesh routes the ring (the
    # rows carry True/False) but every ppermute hop is a same-host
    # memcpy — the floor would grade pure overhead and flake. Off-TPU
    # the guard abstains and points at the tier-1 parity suite.
    out, rc = bench._overlap_guard(
        _ovl_line(overlap_bench=_ob(100.0, 80.0))
    )
    assert rc == 0
    g = json.loads(out)["engine_overlap_collectives_guard"]
    assert g.startswith("abstained")
    assert "TPU" in g and "test_overlap_collectives" in g


def test_overlap_coll_guard_needs_both_modes():
    out, rc = bench._overlap_guard(
        _ovl_line(overlap_bench={"on": {"tok_s": 50.0}})
    )
    assert rc == 0
    assert "engine_overlap_collectives_guard" not in json.loads(out)


def test_overlap_coll_abstains_on_single_device_mesh():
    # The DOCUMENTED abstention: tp=1/ep=1 means the ring schedule was
    # ineligible on both rows — an einsum-vs-einsum floor would stamp
    # "ok" on nothing. The message points at the differential suite.
    out, rc = bench._overlap_guard(
        _ovl_line(overlap_bench=_ob(100.0, 80.0, on_routed=False))
    )
    assert rc == 0
    g = json.loads(out)["engine_overlap_collectives_guard"]
    assert g.startswith("abstained")
    assert "test_overlap_collectives" in g


def test_overlap_coll_abstains_on_env_pinned_hatch():
    # XLLM_OVERLAP_COLLECTIVES pinned in the env flips BOTH rows onto
    # the ring schedule — on-vs-on stamping "ok" would be vacuous.
    out, rc = bench._overlap_guard(
        _ovl_line(overlap_bench=_ob(100.0, 98.0, off_routed=True))
    )
    assert rc == 0
    g = json.loads(out)["engine_overlap_collectives_guard"]
    assert g.startswith("abstained")
    assert "XLLM_OVERLAP_COLLECTIVES" in g


def test_overlap_coll_abstains_on_hot_host():
    out, rc = bench._overlap_guard(
        _ovl_line(backend="tpu", overlap_bench=_ob(100.0, 80.0),
                  loadavg_1m=3.0)
    )
    assert rc == 0
    assert "loadavg" in json.loads(out)["engine_overlap_collectives_guard"]


def test_overlap_coll_abstains_loudly_on_bad_tok_s():
    ob = _ob(100.0, 98.0)
    ob["on"]["tok_s"] = None
    out, rc = bench._overlap_guard(_ovl_line(backend="tpu", overlap_bench=ob))
    assert rc == 0
    assert json.loads(out)[
        "engine_overlap_collectives_guard"
    ].startswith("abstained")


def test_overlap_guard_kill_switch(monkeypatch):
    monkeypatch.setenv("XLLM_BENCH_NO_REGRESSION_GUARD", "1")
    out, rc = bench._overlap_guard(_ovl_line(overlap_bench=_ob(100.0, 10.0)))
    assert rc == 0
    assert "engine_overlap_collectives_guard" not in json.loads(out)


def test_overlap_guard_non_json_passes_through():
    assert bench._overlap_guard("not json") == ("not json", 0)


def test_host_gap_under_ceiling_passes(monkeypatch):
    monkeypatch.setattr(bench, "_HOST_GAP_MAX_MS", 25.0)
    out, rc = bench._overlap_guard(_ovl_line(
        engine_bench={"overlap": {"tok_s": 300.0, "host_gap_ms_mean": 0.6}}
    ))
    assert rc == 0
    assert json.loads(out)["engine_host_gap_guard"] == "ok"


def test_host_gap_recompile_ambush_fails(monkeypatch):
    # The PR 11 ambush class: a fresh XLA compile inside the serving
    # loop shows up as a multi-second mean host gap on the warm rows.
    monkeypatch.setattr(bench, "_HOST_GAP_MAX_MS", 25.0)
    out, rc = bench._overlap_guard(_ovl_line(
        engine_bench={"overlap": {"tok_s": 300.0,
                                  "host_gap_ms_mean": 2700.0}}
    ))
    assert rc == 3
    g = json.loads(out)["engine_host_gap_guard"]
    assert g.startswith("FAIL") and "compiling inside" in g


def test_host_gap_abstains_on_hot_host(monkeypatch):
    monkeypatch.setattr(bench, "_HOST_GAP_MAX_MS", 25.0)
    out, rc = bench._overlap_guard(_ovl_line(
        engine_bench={"overlap": {"tok_s": 300.0,
                                  "host_gap_ms_mean": 2700.0}},
        loadavg_1m=3.0,
    ))
    assert rc == 0
    assert "loadavg" in json.loads(out)["engine_host_gap_guard"]


def test_host_gap_abstains_on_small_host(monkeypatch):
    monkeypatch.setattr(bench, "_GUARD_MIN_CPUS", 10_000)
    monkeypatch.setattr(bench, "_HOST_GAP_MAX_MS", 25.0)
    out, rc = bench._overlap_guard(_ovl_line(
        engine_bench={"overlap": {"tok_s": 300.0,
                                  "host_gap_ms_mean": 2700.0}}
    ))
    assert rc == 0
    assert "host below" in json.loads(out)["engine_host_gap_guard"]


def test_host_gap_guard_skips_sync_only_runs(monkeypatch):
    monkeypatch.setattr(bench, "_HOST_GAP_MAX_MS", 25.0)
    out, rc = bench._overlap_guard(_ovl_line(
        engine_bench={"sync": {"tok_s": 300.0,
                               "host_gap_ms_mean": 2700.0}}
    ))
    assert rc == 0
    assert "engine_host_gap_guard" not in json.loads(out)
