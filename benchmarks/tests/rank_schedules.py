#!/usr/bin/env python3
"""How `schedule_seed` of benchmarks/traffic/think-steady.json was chosen
(PR 46), kept so that the choice can be made again: a step-time model of
the cell `solar-open2-250b.think-steady` on the CPU, no engine and no chip.

A mix's `schedule_seed` pins arrival times and prompt lengths; the run
seed still moves which answer length meets which arrival
(harness/loadgen.py `build_plan`). Over some 216 requests a window,
`tpot_p90_ms` follows that pairing more than anything a PR changes, and
some arrival orders are far more sensitive to it than others: the first
committed schedule, 29, read spreads (IQR / median) of 4.90 and 4.67 % in
`tpot_p90_ms` over two sets of six runs on the chip, over half the bound.

The model: one engine step takes `STEP_MS + ROW_MS * decode rows`
milliseconds, `CHUNK_MS` more when it carries a prefill chunk (one chunk
of 512 tokens a step, first come first served, at most `SLOTS` sequences
in flight), and `HOST_MS` between steps; the constants are the chip's
(PERF.md section 5: a decode step of 13.7-15.0 ms at 45-50 rows, a mixed
step of 39.5 ms). It serves the loadgen's own plan for a schedule and a
run seed and takes `tpot_p90_ms` and `ttft_p50_ms` as the harness does.
For each schedule it prints the spread of both over `--runs` run seeds;
the cell takes a schedule from the steadiest few. A model of the
scheduler, not a measurement: what the chosen schedule reads on the chip
is in PERF.md section 6.

    python3 benchmarks/tests/rank_schedules.py [--schedules 64] [--runs 30] [--only 13 29]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loadgen  # noqa: E402

CELL = "solar-open2-250b.think-steady"
STEP_MS, ROW_MS, CHUNK_MS, HOST_MS = 10.3, 0.094, 24.5, 1.0
CHUNK, SLOTS = 512, 96


def _files():
    with open(os.path.join(ROOT, "benchmarks", "cells", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")) as f:
        return cell, json.load(f)


def serve(traffic, cell, schedule: int, seed: int, seconds: float = 45.0):
    """(tpot_p90_ms, ttft_p50_ms) of the measured requests of one modelled run."""
    plan = loadgen.build_plan(dict(traffic, schedule_seed=schedule), cell, seed, seconds)
    reqs = sorted(plan["requests"], key=lambda r: r["due"])
    now, i, pf, dec, done = reqs[0]["due"], 0, [], [], []
    while i < len(reqs) or pf or dec:
        while i < len(reqs) and reqs[i]["due"] <= now and len(pf) + len(dec) < SLOTS:
            r = reqs[i]
            pf.append({"chunks": r["prompt_len"] // CHUNK, "left": r["out_len"], "n": r["out_len"],
                       "due": r["due"], "measured": r["measured"]})
            i += 1
        if not pf and not dec:
            now = reqs[i]["due"]
            continue
        step, first = STEP_MS + ROW_MS * len(dec), None
        if pf:
            step += CHUNK_MS
            pf[0]["chunks"] -= 1
            if pf[0]["chunks"] == 0:
                first = pf.pop(0)
        now += (step + HOST_MS) * 1e-3
        if first is not None:
            first["first"] = now
            dec.append(first)
        for d in dec:
            d["left"] -= 1
            d["last"] = now
        done += [d for d in dec if d["left"] <= 0]
        dec = [d for d in dec if d["left"] > 0]
    m = [d for d in done if d["measured"] and d["n"] > 1]
    tpot = [(d["last"] - d["first"]) / (d["n"] - 1) * 1e3 for d in m]
    ttft = [(d["first"] - d["due"]) * 1e3 for d in m]
    return float(np.percentile(tpot, 90)), float(np.median(ttft))


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def rank(schedules, runs: int):
    """[(tpot_p90 spread, ttft_p50 spread, schedule)], steadiest first."""
    cell, traffic = _files()
    rows = []
    for s in schedules:
        r = [serve(traffic, cell, s, 4600000600 + k) for k in range(runs)]
        rows.append((spread([x[0] for x in r]), spread([x[1] for x in r]), s))
    return sorted(rows)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedules", type=int, default=64)
    ap.add_argument("--runs", type=int, default=30)
    ap.add_argument("--only", type=int, nargs="*")
    args = ap.parse_args()
    rows = rank(args.only or range(args.schedules), args.runs)
    for place, (tp, tt, s) in enumerate(rows, 1):
        print(f"{place:3d}  schedule {s:3d}  tpot_p90 spread {tp:.4f}  ttft_p50 spread {tt:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
