"""Traffic generation and the load generator. Imports no JAX: run.py
starts this file as a child process, so the clients never touch the chip
and the process that holds it never runs the clients' Python.

One general generator reads a traffic mix (benchmarks/traffic/<mix>.json)
and a cell's rate or client count and returns a plan: for every seed the
SAME multiset of prompt lengths, output lengths and arrival gaps
(stratified quantiles of the mix's distributions), in an order the seed
permutes. The amount of work in a window therefore does not vary with the
seed; which request meets which does. A mix may pin the order of arrivals
and prompt lengths (`schedule_seed`): a tail over some 150 requests
follows the particular arrival order more than anything a PR changes.

Schedule arithmetic after bench_serving.py's open-loop scheduler
(requests are timed from when they were DUE, lateness is reported);
prompts are seeded token ids, not text, and no two share a prefix unless
the mix asks for shared documents."""

from __future__ import annotations

import asyncio
import json
import math
import statistics
import sys
import time
from typing import Dict, List, Mapping, Optional

import numpy as np

KEY_TOKENS = 8  # a request is recognised by its first prompt token ids


def request_key(prompt_ids) -> str:
    return ",".join(str(int(t)) for t in prompt_ids[:KEY_TOKENS])


# ------------------------------------------------------------------ plan


def quantile_lengths(spec: Mapping, n: int) -> List[int]:
    """n stratified quantiles ((k + 0.5) / n) of a length distribution,
    rounded to `multiple_of` (default 1) and clipped to [min, max]."""
    if n <= 0:
        return []
    us = [(k + 0.5) / n for k in range(n)]
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    if dist == "lognormal":
        nd = statistics.NormalDist()
        vals = [math.exp(spec["mu"] + spec["sigma"] * nd.inv_cdf(u)) for u in us]
    elif dist == "uniform":
        vals = [lo + (hi - lo) * u for u in us]
    elif dist == "constant":
        vals = [float(spec["value"])] * n
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    step = int(spec.get("multiple_of", 1))
    return [int(min(max(round(v / step) * step, lo), hi)) for v in vals]


def unit_gaps(n: int) -> np.ndarray:
    """n stratified quantiles of Exp(1), scaled to sum to n."""
    us = (np.arange(n) + 0.5) / n
    g = -np.log1p(-us)
    return g * (n / g.sum())


def intensity_inverse(arrivals: Mapping, rate: float, span: float):
    """Returns f: operational time s in [0, rate*span] -> clock time in
    [0, span] for the arrival process's intensity (mean `rate`)."""
    proc = arrivals.get("process", "poisson")
    if proc == "poisson":
        return lambda s: s / rate
    if proc == "onoff":
        period, on_s, factor = (
            float(arrivals["period_s"]), float(arrivals["on_s"]),
            float(arrivals["on_factor"]),
        )
        on_rate = factor * rate
        off_rate = (rate * period - on_rate * on_s) / (period - on_s)
        if off_rate < 0:
            raise ValueError("onoff arrivals: on_factor * on_s exceeds the period")
        t = np.linspace(0.0, span, int(span * 1000) + 1)
        lam = np.where((t % period) < on_s, on_rate, off_rate)
        cum = np.concatenate([[0.0], np.cumsum((lam[1:] + lam[:-1]) * 0.5 * np.diff(t))])
        cum *= rate * span / cum[-1]
        return lambda s: float(np.interp(s, cum, t))
    raise ValueError(f"unknown arrival process {proc!r}")


def _perm(rng: np.random.Generator, values) -> list:
    values = list(values)
    return [values[i] for i in rng.permutation(len(values))]


def _phase(traffic: Mapping, sched, rng, n: int, rate: float, span: float, t_off: float):
    """n open-loop requests over [t_off, t_off + span): arrival gaps and
    prompt lengths in the order `sched` draws, answer lengths in the
    order `rng` (the run's seed) draws."""
    gaps = np.asarray(_perm(sched, unit_gaps(n)))
    s = np.cumsum(gaps) - 0.5 * gaps
    inv = intensity_inverse(traffic.get("arrivals", {}), rate, span)
    due = [t_off + inv(x * (rate * span / n)) for x in s]
    plens = _perm(sched, quantile_lengths(traffic["prompt_tokens"], n))
    olens = _perm(rng, quantile_lengths(traffic["output_tokens"], n))
    return list(zip(due, plens, olens))


def build_plan(traffic: Mapping, cell: Mapping, seed: int, seconds: float) -> Dict:
    """The whole run's requests. Times are relative to the window start;
    warm-up requests are due before 0 and are not measured."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    # A mix with a `schedule_seed` keeps its arrival times and prompt
    # lengths the same for every run seed (the seed then moves the
    # prompts' content, which answer length meets which request, and the
    # sampling seeds); without one the run seed permutes those too.
    sched = rng
    if "schedule_seed" in traffic:
        sched = np.random.default_rng(np.random.SeedSequence([int(traffic["schedule_seed"]), 4]))
    warm = float(traffic.get("warmup_seconds", 0.0))
    reqs: List[dict] = []
    if traffic["loop"] == "open":
        rate = float(cell["rate_per_s"])
        n_win = max(1, int(round(rate * seconds)))
        n_warm = int(round(rate * warm))
        phases = []
        if n_warm:
            phases += [(x, False) for x in _phase(traffic, sched, rng, n_warm, rate, warm, -warm)]
        phases += [(x, True) for x in _phase(traffic, sched, rng, n_win, rate, seconds, 0.0)]
        for (due, pl, ol), measured in phases:
            reqs.append({"due": due, "prompt_len": pl, "out_len": ol,
                         "measured": measured, "client": None})
    elif traffic["loop"] == "closed":
        clients = int(cell["clients"])
        per = int(traffic.get("requests_per_client", 8))
        n = clients * per
        plens = _perm(rng, quantile_lengths(traffic["prompt_tokens"], n))
        olens = _perm(rng, quantile_lengths(traffic["output_tokens"], n))
        for c in range(clients):
            for j in range(per):
                k = c * per + j
                ol = olens[k]
                if j == 0 and traffic.get("stagger_first", False):
                    # as if client c were already part-way through a
                    # request: the batch starts desynchronised, as a
                    # steady closed loop is
                    ol = max(2, int(round(ol * (c + 0.5) / clients)))
                reqs.append({"due": -warm if j == 0 else None, "prompt_len": plens[k],
                             "out_len": ol, "measured": None, "client": c})
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    shared = traffic.get("shared_prefix")
    if shared:
        docs = int(shared["documents"])
        dlens = quantile_lengths(shared["doc_tokens"], docs)
        order = _perm(rng, range(len(reqs)))
        for pos, i in enumerate(order):
            reqs[i]["doc"] = pos % docs
            reqs[i]["doc_len"] = dlens[pos % docs]
    for i, r in enumerate(reqs):
        r["i"] = i
    return {"requests": reqs, "warmup_seconds": warm, "seconds": float(seconds),
            "loop": traffic["loop"], "seed": int(seed),
            "sampling": dict(traffic.get("sampling", {})),
            "stream": bool(traffic.get("stream", True))}


def closed_loop_room(plan: Mapping) -> Optional[int]:
    """The fewest output tokens any client of a closed loop must receive
    before it sends its LAST request; None for an open loop. A token takes
    one engine step, so a client runs out before the window closes only
    if its requests' mean time per token is under (warm-up + window) /
    this. The mixes choose `requests_per_client` so that this time lies
    under the time a step needs to stream the configuration's weights
    once (benchmarks/tests/test_loadgen.py holds every closed-loop cell of
    BENCHMARK.json to that): no engine that does the configuration's work
    exhausts a client, at any speed."""
    if plan["loop"] != "closed":
        return None
    answers: Dict[int, List[int]] = {}
    for r in plan["requests"]:  # a client's requests in the order it sends them
        answers.setdefault(r["client"], []).append(int(r["out_len"]))
    return min(sum(lens[:-1]) for lens in answers.values())


def prompt_ids(seed: int, req: Mapping, vocab: int) -> List[int]:
    """Token ids of one request, uniform over the vocabulary, from the
    seed and the request's index (and its document's, if shared)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2, int(req["i"])]))
    own = rng.integers(0, vocab, size=int(req["prompt_len"])).tolist()
    if "doc" in req:
        drng = np.random.default_rng(np.random.SeedSequence([int(seed), 3, int(req["doc"])]))
        return drng.integers(0, vocab, size=int(req["doc_len"])).tolist() + own
    return own


# --------------------------------------------------------------- clients


async def _one_request(addr: str, model: str, plan: Mapping, req: dict,
                       vocab: int, t_zero: float, rec: dict) -> None:
    ids = prompt_ids(plan["seed"], req, vocab)
    rec["key"] = request_key(ids)
    rec["prompt_tokens_sent"] = len(ids)
    body = {
        "model": model, "prompt": ids, "max_tokens": int(req["out_len"]),
        "ignore_eos": True, "stream": True,
        "stream_options": {"include_usage": True},
        "seed": int((plan["seed"] * 1000003 + req["i"]) % (2 ** 31 - 1)),
    }
    body.update(plan["sampling"])
    payload = json.dumps(body).encode()
    host, _, port = addr.partition(":")
    rec["t_send"] = time.monotonic() - t_zero
    reader, writer = await asyncio.open_connection(host, int(port))
    try:
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: " + host.encode()
            + b"\r\nContent-Type: application/json\r\nConnection: close\r\n"
            + b"Content-Length: " + str(len(payload)).encode() + b"\r\n\r\n" + payload
        )
        await writer.drain()
        status = await reader.readline()
        parts = status.split()
        rec["status"] = int(parts[1]) if len(parts) > 1 and parts[1].isdigit() else -1
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            now = time.monotonic() - t_zero
            if data == b"[DONE]":
                rec["done"] = True
                break
            ev = json.loads(data)
            if ev.get("usage"):
                rec["completion_tokens"] = int(ev["usage"].get("completion_tokens", 0))
            for ch in ev.get("choices") or ():
                lp = (ch.get("logprobs") or {}).get("token_logprobs")
                rec["chunk_times"].append(now)
                rec["chunk_tokens"].append(len(lp) if lp else 1)
                if ch.get("finish_reason"):
                    rec["finish_reason"] = ch["finish_reason"]
    finally:
        writer.close()
    rec["t_end"] = time.monotonic() - t_zero


def _new_rec(req: Mapping) -> dict:
    return {
        "i": req["i"], "due": req["due"], "measured": req["measured"],
        "client": req["client"], "prompt_len": req["prompt_len"]
        + int(req.get("doc_len", 0)), "out_len": req["out_len"],
        "status": None, "done": False, "chunk_times": [], "chunk_tokens": [],
        "completion_tokens": None, "error": None, "t_send": None, "t_end": None,
    }


async def _guarded(addr, model, plan, req, vocab, t_zero, rec, deadline):
    try:
        await asyncio.wait_for(
            _one_request(addr, model, plan, req, vocab, t_zero, rec),
            timeout=max(1.0, deadline - (time.monotonic() - t_zero)),
        )
    except asyncio.TimeoutError:
        rec["error"] = "not finished by the deadline"
    except Exception as e:  # a failed request is a result, not a crash
        rec["error"] = repr(e)


async def drive(addr: str, model: str, plan: Mapping, vocab: int,
                t_zero: float) -> List[dict]:
    """Run the plan against the master at `addr`. t_zero is the window
    start on time.monotonic() (shared by every process of one machine)."""
    seconds = plan["seconds"]
    deadline = seconds + 2.0 * seconds  # a request not finished by then failed
    recs: List[dict] = []
    tasks = []
    if plan["loop"] == "open":
        async def at(req, rec):
            delay = req["due"] - (time.monotonic() - t_zero)
            if delay > 0:
                await asyncio.sleep(delay)
            await _guarded(addr, model, plan, req, vocab, t_zero, rec, deadline)

        for req in plan["requests"]:
            rec = _new_rec(req)
            recs.append(rec)
            tasks.append(asyncio.ensure_future(at(req, rec)))
    else:
        by_client: Dict[int, list] = {}
        for req in plan["requests"]:
            by_client.setdefault(req["client"], []).append(req)

        async def client(reqs):
            delay = reqs[0]["due"] - (time.monotonic() - t_zero)
            if delay > 0:
                await asyncio.sleep(delay)
            for req in reqs:
                now = time.monotonic() - t_zero
                if now >= seconds:
                    return  # the window is closed: send nothing new
                rec = _new_rec(req)
                rec["due"] = now
                rec["measured"] = 0.0 <= now < seconds
                recs.append(rec)
                await _guarded(addr, model, plan, req, vocab, t_zero, rec, deadline)
            recs.append({"exhausted_client": reqs[0]["client"]})

        tasks = [asyncio.ensure_future(client(r)) for r in by_client.values()]
    await asyncio.gather(*tasks)
    return recs


def main() -> int:
    job = json.loads(sys.stdin.read())
    plan = build_plan(job["traffic"], job["cell"], job["seed"], job["seconds"])
    recs = asyncio.run(
        drive(job["addr"], job["model"], plan, job["vocab"], job["t_zero"])
    )
    json.dump({"records": recs}, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
