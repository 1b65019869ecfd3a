"""Device time by region of a step program, for the five readers of
benchmarks/layer_metrics that report it (device_named_share,
ffn_ms_per_step, attn_proj_ms_per_step, head_sample_ms_per_step,
stack_slice_share).

The third place the harness depends on the program (PERF.md section 3):
`xllm_service_tpu.obs.regions` gives, for the step programs of the
executors alive in this process, `{op key: region}` parsed out of each
compiled program's text (the models name their regions with
`obs.spans.region`), and joins a trace's own nanoseconds by op name
(`w.trace["ops"]`) against them. A program that has no `obs.regions` (a
parent of PR 44) gives nothing to read; one that has it but named no op
reads 0.0.

Computed once a window, on the first reader's call: it COMPILES (each
step program's kept signature, ahead of time, out of the persistent
cache where that holds it), after the window and after the stack has
stopped, never inside what is measured. One log line a traced run:
seconds by region and, inside each, by `trace_reduce.op_family`."""

from __future__ import annotations

import json
import time
import traceback

from benchmarks.harness import trace_reduce

OTHER = ("unnamed", "ambiguous")
FAMILIES_SHOWN = 6
# seconds after which no further step program is compiled for its map (one
# the call's own executable serves costs a second or less; a program left
# out reads `unnamed`): a traced run must end inside run.py's HARD_EXIT_S
MAP_BUDGET_S = 60.0


def window_regions(w):
    """{"ns": {region: ns}, "total_ns", "steps", "families": {region:
    {op family: ns}}, "programs": {program: maps}, "map_s"} of a traced
    window, cached on it; None without a trace or without `obs.regions`
    in the program."""
    if getattr(w, "trace", None) is None:
        return None
    if not hasattr(w, "_regions"):
        w._regions = _compute(w.trace)
    return w._regions


def _compute(trace):
    try:
        from xllm_service_tpu.obs import regions
    except ImportError:
        print("regions: the program has no obs.regions: nothing to read", flush=True)
        return None
    t0 = time.monotonic()
    try:
        programs = regions.program_maps(MAP_BUDGET_S)
    except Exception as e:  # a per-layer reading made after the window: no map, and the log says why
        print(f"regions: the step programs could not be mapped: {e!r}\n{traceback.format_exc()}",
              flush=True)
        programs = {}
    map_s = time.monotonic() - t0
    ops = trace["ops"]
    assigned = regions.assign(ops, regions.all_maps(programs))
    ns, families = {}, {}
    for name, region in assigned.items():
        ns[region] = ns.get(region, 0.0) + ops[name]
        fam = families.setdefault(region, {})
        family = trace_reduce.op_family(name)
        fam[family] = fam.get(family, 0.0) + ops[name]
    runs = trace["program_durations_ns"]
    steps = sum(len(runs.get(p, ())) for p, maps in programs.items() if maps)
    out = {
        "ns": ns, "total_ns": sum(ops.values()), "families": families,
        "steps": steps / max(1, int(trace.get("chips", 1))),
        "programs": {p: len(maps) for p, maps in programs.items()}, "map_s": map_s,
    }
    print("regions: " + json.dumps(_log_line(out)), flush=True)
    return out


def _log_line(r) -> dict:
    def top(d):
        return {k: round(v / 1e9, 4) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:FAMILIES_SHOWN]}

    return {
        "total_s": round(r["total_ns"] / 1e9, 4), "steps": r["steps"],
        "map_s": round(r["map_s"], 2), "map_budget_s": MAP_BUDGET_S,
        "programs_mapped": r["programs"],
        "by_region": {
            region: [round(v / 1e9, 4), top(r["families"][region])]
            for region, v in sorted(r["ns"].items(), key=lambda kv: -kv[1])
        },
    }


def share(w, names):
    """Own time of the ops in the regions `names` / own time of all ops of
    the trace, in percent; None without a trace (or without obs.regions)."""
    r = window_regions(w)
    if r is None:
        return None
    if not r["total_ns"]:
        return 0.0
    return 100.0 * sum(r["ns"].get(n, 0.0) for n in names) / r["total_ns"]


def named_share(w):
    r = window_regions(w)
    if r is None:
        return None
    return share(w, [n for n in r["ns"] if n not in OTHER])


def ms_per_step(w, names):
    """Milliseconds of the regions `names` per execution of a step program
    that has a map (mixed or decode); None without a trace."""
    r = window_regions(w)
    if r is None:
        return None
    if not r["steps"]:
        return 0.0
    return sum(r["ns"].get(n, 0.0) for n in names) / 1e6 / r["steps"]
