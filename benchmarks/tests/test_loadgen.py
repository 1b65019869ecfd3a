import json
import os

import numpy as np

from benchmarks.harness import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


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
