import json
import os

import numpy as np
import pytest

from benchmarks.harness import counts, counts_brumby, loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
# whose decode_weight_bytes gives a family's streaming floor
FAMILY_COUNTS = {"llama": counts, "brumby": counts_brumby}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def mix(name):
    return load("traffic", name)


def manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def closed_loop_cells():
    cells = [w["name"] for w in manifest()["workloads"]]
    return [c for c in cells if mix(load("cells", c)["traffic"])["loop"] == "closed"]


def test_plan_is_a_function_of_the_seed():
    t, cell = mix("chat-steady"), {"rate_per_s": 4.0}
    t.pop("schedule_seed")
    a = loadgen.build_plan(t, cell, 2**31 + 12345, 45)
    b = loadgen.build_plan(t, cell, 2**31 + 12345, 45)
    c = loadgen.build_plan(t, cell, 7, 45)
    assert a == b and a != c
    assert loadgen.prompt_ids(7, c["requests"][3], 1000) == loadgen.prompt_ids(7, c["requests"][3], 1000)
    assert loadgen.prompt_ids(7, c["requests"][3], 1000) != loadgen.prompt_ids(8, c["requests"][3], 1000)


def test_a_pinned_schedule_moves_only_answers_and_content():
    t, cell = mix("chat-steady"), {"rate_per_s": 3.2}
    assert "schedule_seed" in t
    a, b = (loadgen.build_plan(t, cell, s, 45)["requests"] for s in (1, 2))
    assert [(r["due"], r["prompt_len"]) for r in a] == [(r["due"], r["prompt_len"]) for r in b]
    assert [r["out_len"] for r in a] != [r["out_len"] for r in b]
    assert sorted(r["out_len"] for r in a) == sorted(r["out_len"] for r in b)
    assert loadgen.prompt_ids(1, a[0], 1000) != loadgen.prompt_ids(2, b[0], 1000)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    t, cell = mix("chat-steady"), {"rate_per_s": 4.0}
    t.pop("schedule_seed")
    plans = [loadgen.build_plan(t, cell, s, 45) for s in (1, 2)]
    win = [[r for r in p["requests"] if r["measured"]] for p in plans]
    assert len(win[0]) == len(win[1]) == 180  # rate x seconds
    for key in ("prompt_len", "out_len"):
        assert sorted(r[key] for r in win[0]) == sorted(r[key] for r in win[1])
        assert [r[key] for r in win[0]] != [r[key] for r in win[1]]
    for w in win:
        due = sorted(r["due"] for r in w)
        assert 0.0 <= due[0] and due[-1] < 45.0
    warm = [r for r in plans[0]["requests"] if not r["measured"]]
    assert len(warm) == 60 and all(-15.0 <= r["due"] < 0.0 for r in warm)
    lens = sorted(r["prompt_len"] for r in win[0])
    assert lens[0] == 256 and lens[-1] == 1536 and all(n % 256 == 0 for n in lens)
    assert 480 <= sum(lens) / len(lens) <= 560  # lognormal(5.8, 1.0) in whole chunks
    outs = sorted(r["out_len"] for r in win[0])
    assert outs[0] >= 8 and outs[-1] <= 512 and 135 <= outs[90] <= 165  # median e^5


def test_onoff_arrivals_keep_the_mean_rate_and_bunch_up():
    t = dict(mix("chat-steady"), arrivals={"process": "onoff", "period_s": 8, "on_s": 2, "on_factor": 3})
    plan = loadgen.build_plan(t, {"rate_per_s": 4.0}, 3, 48)
    due = np.array([r["due"] for r in plan["requests"] if r["measured"]])
    assert len(due) == 192
    on = ((due % 8) < 2).mean()
    assert 0.65 < on < 0.85  # 3x the mean rate for a quarter of the time


def test_closed_loop_and_shared_documents():
    t = mix("decode-batch")
    plan = loadgen.build_plan(t, {"clients": 4}, 5, 10)
    firsts = [r for r in plan["requests"] if r["due"] is not None]
    assert len(firsts) == 4 and len(plan["requests"]) == 4 * t["requests_per_client"]
    assert sorted(r["out_len"] for r in firsts)[0] < 256  # staggered first answers
    shared = dict(mix("chat-steady"), shared_prefix={
        "documents": 5, "doc_tokens": {"dist": "uniform", "min": 100, "max": 200}})
    plan = loadgen.build_plan(shared, {"rate_per_s": 2.0}, 5, 10)
    a, b = [r for r in plan["requests"] if r["doc"] == 0][:2]
    pa, pb = loadgen.prompt_ids(5, a, 5000), loadgen.prompt_ids(5, b, 5000)
    assert pa[: a["doc_len"]] == pb[: b["doc_len"]] and pa != pb


def test_generator_reports_lateness_and_failures():
    """Against a dead port every request fails and says so; send times are
    recorded against due times."""
    import asyncio
    import socket
    import time

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    t = dict(mix("rehearse"), warmup_seconds=0)
    plan = loadgen.build_plan(t, {"rate_per_s": 20.0}, 1, 0.5)
    recs = asyncio.run(loadgen.drive(f"127.0.0.1:{port}", "m", plan, 100, time.monotonic() + 0.1))
    assert len(recs) == 10
    for r in recs:
        assert r["error"] and not r["done"]
        assert -0.001 <= r["t_send"] - r["due"] < 0.25


def room(cell_name, seed, **override):
    """(fewest tokens before a client's last request, tokens the rule asks
    for): a token takes one engine step, and a decode step cannot be
    shorter than the time to stream the configuration's weights once."""
    cell = load("cells", cell_name)
    t = dict(mix(cell["traffic"]), **override)
    m = load("configs", cell["config"])
    floor_s = FAMILY_COUNTS[m["family"]].decode_weight_bytes(
        m, m["engine"].get("dtype", "bfloat16")) / counts.PEAKS["TPU v5e"]["hbm_bytes_per_s"]
    seconds = manifest()["run_seconds"]
    plan = loadgen.build_plan(t, cell, seed, seconds)
    return loadgen.closed_loop_room(plan), (t["warmup_seconds"] + seconds) / floor_s


def test_the_cells_that_can_run_out_are_the_two_closed_loops():
    assert closed_loop_cells() == ["qwen2.5-3b.decode-batch", "brumby-14b.reason-batch"]
    assert loadgen.closed_loop_room(loadgen.build_plan(mix("chat-steady"), {"rate_per_s": 3.2}, 1, 45)) is None


@pytest.mark.parametrize("seed", list(range(1, 21)) + [2**31 + 33])
@pytest.mark.parametrize("cell", closed_loop_cells())
def test_no_client_runs_out_above_the_weights_streaming_floor(cell, seed):
    fewest, needed = room(cell, seed)
    assert fewest > needed, (cell, seed, fewest, needed)


@pytest.mark.parametrize("seed", [1, 7, 20])
def test_the_rule_sees_the_fault_it_guards(seed):
    """The parent's six requests a client: the first client is out at a
    mean TPOT of 49.6 ms, where the cell read 49.1-49.9."""
    fewest, needed = room("qwen2.5-3b.decode-batch", seed, requests_per_client=6)
    assert fewest < needed / 5
    assert 40.0 < 57.0 / fewest * 1e3 < 52.0


def exhausted(cell_name, seed, tpot_s, ttft_s=0.15, **override):
    """Clients that would run out: every request served at one time per
    token, back to back. As in loadgen.drive, a client is out once it has
    SENT its last request before the window closes."""
    cell = load("cells", cell_name)
    t = dict(mix(cell["traffic"]), **override)
    plan = loadgen.build_plan(t, cell, seed, 45)
    sent, free = {}, {}
    for r in plan["requests"]:
        sent[r["client"]] = free.get(r["client"], -plan["warmup_seconds"])
        free[r["client"]] = sent[r["client"]] + ttft_s + tpot_s * r["out_len"]
    return sum(1 for last_sent in sent.values() if last_sent < plan["seconds"])


@pytest.mark.parametrize("seed", [3000000131, 7, 3200000001])
def test_an_engine_twice_as_fast_exhausts_the_parents_clients_and_none_of_these(seed):
    cell = "qwen2.5-3b.decode-batch"
    assert exhausted(cell, seed, 0.050, requests_per_client=6) == 0  # where the cell stood
    assert exhausted(cell, seed, 0.027, requests_per_client=6) > 100  # device-bound
    for tpot in (0.050, 0.027, 0.0135, 0.0076):
        assert exhausted(cell, seed, tpot) == 0
    assert exhausted("brumby-14b.reason-batch", seed, 0.0084) == 0
