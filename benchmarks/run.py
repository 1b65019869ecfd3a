#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves --workload to benchmarks/cells/<cell>.json, that to its
configuration (configs/) and traffic mix (traffic/), the configuration's
"family" to families/<family>.py (key mapping, weights, plain reference,
limits), and the metric names of BENCHMARK.json to end_to_end/<name>.py
and layer_metrics/<name>.py: all by name, so a later PR adds cells, mixes,
configurations, families and metrics as new files plus entries in
BENCHMARK.json and edits nothing here.

One run: set-up (build master + instance + engine in this process; the
benchmark's weights made on the device from --seed; the correctness
check against the plain float32 reference; a bridge request that keeps
the engine busy until the traffic starts; warm-up traffic from the load
generator, a child process that imports no JAX), then a window of
--seconds in which nothing may compile, then the drain. The last line of
stdout is the result object of the contract. Without an accelerator (or
with fewer chips than the cell asks for) it exits non-zero and prints no
result. --rehearse (explicit, never a fallback) runs the cell on the CPU
backend, to find wrong paths before chip time is spent: give it the cell
rehearse-tiny.rehearse. Its line says platform cpu and carries no device
time.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 3.0
HARD_EXIT_S = 1150.0  # a first run in a checkout may take 1200 s: it compiles


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_metric(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest_metrics(cell: str) -> tuple:
    """(end_to_end, per_layer) metric entries that apply to `cell`; a cell
    the manifest does not list (a rehearsal) is offered every metric."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    listed = any(w["name"] == cell for w in man["workloads"])

    def applies(entry) -> bool:
        return not listed or "workloads" not in entry or cell in entry["workloads"]

    return (
        [e for e in man["end_to_end"] if applies(e)],
        [e for e in man["per_layer"] if applies(e)],
    )


class Window:
    """What one run measured; the input of every metric reader."""

    def __init__(self, **kw):
        self.checks = {}  # name -> {"value", "low", "high"}: a reader's check of itself
        self.__dict__.update(kw)

    @property
    def model(self) -> dict:
        """The configuration as parsed: what a family's counts take."""
        return self.config

    @property
    def engine(self) -> dict:
        return self.config["engine"]

    def measured(self) -> list:
        return [r for r in self.records if r.get("measured")]

    def ok(self, r) -> bool:
        return (
            r.get("status") == 200 and r.get("done") and not r.get("error")
            and len(r["chunk_times"]) > 0
            and r.get("completion_tokens") == r["out_len"]
        )

    def counter_delta(self, name: str):
        a, b = self.counters_start.get(name), self.counters_end.get(name)
        return None if a is None or b is None else b - a


def checks_off(checks: dict) -> list:
    """Names of the readers' checks of themselves that left their range."""
    return [n for n, c in checks.items() if not c["low"] <= c["value"] <= c["high"]]


def device_memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU backend (never reports a TPU)")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="override the cell's rate (the knee sweep; never the driver)")
    ap.add_argument("--dump-records", default="",
                    help="write every request's client record to this JSON file")
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's .xplane.pb into this directory")
    return ap.parse_args()


def open_devices(chips: int, rehearse: bool):
    """JAX's devices, or None (after saying why) where the cell cannot be
    measured: no accelerator, or fewer chips than it asks for."""
    import jax

    if rehearse:
        # XLA:CPU reads the engine's host arrays in place, after the engine
        # has moved them on; a third of rehearsals then serve wrong tokens
        # (PERF.md section 6). The chip copies them: nothing is set there.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run.py: no accelerator: {str(e).splitlines()[0]}", file=sys.stderr)
        return None
    if not rehearse and devices[0].platform == "cpu":
        print("run.py: JAX reports only the CPU; this benchmark measures the "
              "chip (use --rehearse for a CPU rehearsal)", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chip(s), JAX reports {len(devices)}",
              file=sys.stderr)
        return None
    return devices


def log_room(w, fewest: int, reach_s: float) -> None:
    """A closed loop's room, for the next reader of this log: a client
    runs out of requests when the answers to all but its last have ended
    before the window does, `fewest` tokens at least (loadgen.closed_loop_room)."""
    tpots = [
        (r["chunk_times"][-1] - r["chunk_times"][0]) * 1e3 / (r["completion_tokens"] - 1)
        for r in w.records if w.ok(r) and r["completion_tokens"] >= 2
    ]
    mean = f"{sum(tpots) / len(tpots):.2f} ms" if tpots else "not read"
    log(f"room: fewest tokens before a client's last request {fewest}; the first client "
        f"runs out under a mean TPOT of {reach_s / fewest * 1e3:.2f} ms "
        f"({reach_s:g} s of warm-up and window); this run's mean TPOT {mean} "
        f"over {len(tpots)} requests")


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def trace_span(trace_dir: str, t_zero: float, seconds: float) -> tuple:
    """Profile TRACE_SECONDS in the middle of the window, from this
    process (only the one that holds the chip can). The Python tracer
    stays off: it slows the host it is measuring. Returns the seconds of
    the window in which the profiler was recording: from when
    `start_trace` returned to when `stop_trace` was CALLED. That call
    writes the profile and returns seconds later; what the engine emits
    meanwhile is in no traced step (readers.traced_emissions)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    sleep_until(t_zero + max(0.0, 0.5 * seconds - 0.5 * TRACE_SECONDS))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ta = time.monotonic()
    time.sleep(min(TRACE_SECONDS, 0.5 * seconds))
    tb = time.monotonic()
    jax.profiler.stop_trace()
    log(f"trace: recording {tb - ta:.3f}s from t={ta - t_zero:+.3f}s, "
        f"profile written {time.monotonic() - tb:.3f}s after the stop was called")
    return ta - t_zero, tb - t_zero


def drive(stack, job: dict, trace_dir: str) -> dict:
    """Warm-up traffic and the window: the child process sends, this one
    watches (counters at window start and end, fresh lowerings, the
    profiler). Returns what the window measured."""
    t_zero, seconds = job["t_zero"], job["seconds"]
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
    )
    try:
        child.stdin.write(json.dumps(job).encode())
        child.stdin.close()
        out: list = []
        reader = threading.Thread(target=lambda: out.append(child.stdout.read()), daemon=True)
        reader.start()
        watching = threading.Event()

        def watch_lowerings() -> None:
            seen = stack.lowerings()
            while not watching.wait(1.0):
                n = stack.lowerings()
                if n != seen:
                    log(f"lowerings: {seen} -> {n} at t={time.monotonic() - t_zero:+.1f}s")
                    seen = n

        watcher = threading.Thread(target=watch_lowerings, daemon=True)
        watcher.start()
        sleep_until(t_zero)
        got = {"setup_s": time.monotonic() - T_PROCESS_START,
               "counters_start": stack.counters(), "lowerings_start": stack.lowerings(),
               "trace_span": trace_span(trace_dir, t_zero, seconds) if trace_dir else None}
        sleep_until(t_zero + seconds)
        got["counters_end"], got["lowerings_end"] = stack.counters(), stack.lowerings()
        reader.join(timeout=max(5.0, 3.0 * seconds + 30.0))
        watching.set()
        watcher.join(timeout=5.0)
        if reader.is_alive() or not out:
            raise RuntimeError("the load generator did not finish")
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)
    records = json.loads(out[0])["records"]
    got["exhausted"] = [r for r in records if "exhausted_client" in r]
    got["records"] = [r for r in records if "exhausted_client" not in r]
    return got


def read_trace(trace_dir: str, chips: int, keep: str):
    from benchmarks.harness import trace_reduce

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    trace = trace_reduce.reduce_file(files[0], chips) if files else None
    if files and keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(files[0], keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace


def main() -> int:
    args = parse_args()
    cell = load_json("cells", args.workload + ".json")
    if args.rate_per_s is not None:
        cell["rate_per_s"] = args.rate_per_s
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    chips = int(cell["chips"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = float(args.seconds if args.seconds is not None else json.load(f)["run_seconds"])
    e2e_entries, layer_entries = manifest_metrics(args.workload)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks.harness import family as family_mod

    try:  # before any device or engine: a missing name costs no set-up
        family = family_mod.load(config)
    except family_mod.FamilyError as e:
        raise SystemExit(f"run.py: {e}") from None
    threading.Thread(
        target=lambda: (time.sleep(HARD_EXIT_S), os._exit(124)), daemon=True
    ).start()
    devices = open_devices(chips, args.rehearse)
    if devices is None:
        return 2
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"used={chips}")

    from benchmarks.harness import check as check_mod, counts, loadgen, stack as stack_mod, stats

    if not args.rehearse:
        counts.peaks(dev.device_kind)  # an unknown device is an error now
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax-compile-cache"
    )
    log(f"compile cache: {cache_dir}")

    stack = stack_mod.Stack(cell["config"], family, config, args.seed, cache_dir)
    try:
        log(f"built: executor {stack.build_s:.2f}s, weights {stack.weights_s:.2f}s, "
            f"num_blocks={stack.executor.num_blocks} kernels={json.dumps(stack.kernel_report())}")
        t0 = time.monotonic()
        warm_threads = stack_mod.warm_shapes(
            stack, traffic.get("warm_shapes") or {"background_prompts": []},
            config["vocab_size"], args.seed,
        )
        log(f"warm shapes: {time.monotonic() - t0:.2f}s, {stack.lowerings()} step programs")
        check = check_mod.check_correct(stack, args.seed)
        log("correct: " + json.dumps(check))
        t0 = time.monotonic()
        warm_threads += stack_mod.start_bridge(
            stack, traffic.get("warm_shapes") or {}, config["vocab_size"], args.seed
        )
        log(f"bridge: decoding after {time.monotonic() - t0:.2f}s, "
            f"{stack.lowerings()} step programs")
        job = {
            "addr": stack.master_addr, "model": cell["config"], "traffic": traffic,
            "cell": cell, "seed": args.seed, "seconds": seconds, "vocab": config["vocab_size"],
            "t_zero": time.monotonic() + float(traffic.get("warmup_seconds", 0.0)) + 1.0,
        }
        trace_dir = os.path.join(ROOT, ".bench-trace") if args.trace else ""
        got = drive(stack, job, trace_dir)
        for t in warm_threads:
            t.join(timeout=60.0)
        trace = read_trace(trace_dir, chips, args.keep_trace) if args.trace else None
        memory_peak = device_memory_peak(devices[:chips])
        taps = stack.taps
    finally:
        stack.stop()

    records = got["records"]
    if args.dump_records:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump_records)), exist_ok=True)
        with open(args.dump_records, "w") as f:
            json.dump({"records": records, "seconds": seconds, "cell": cell}, f)
    w = Window(
        cell=cell, config=config, traffic=traffic, seconds=seconds, records=records, taps=taps,
        t_zero=job["t_zero"], counters_start=got["counters_start"],
        counters_end=got["counters_end"], trace=trace, trace_span=got["trace_span"],
        setup_s=got["setup_s"], device_kind=dev.device_kind, chips=chips, counts=counts,
    )
    measured = w.measured()
    failed = [r for r in measured if not w.ok(r)]
    compiles = got["lowerings_end"] - got["lowerings_start"]
    late = [r["t_send"] - r["due"] for r in measured if r.get("t_send") is not None]
    log(f"window: {seconds:g}s, attempted {len(measured)}, failed {len(failed)}, "
        f"warm-up requests {len(records) - len(measured)}, lowerings in window "
        f"{compiles} (limit 0), xllm_engine_compile_cache_misses_total delta "
        f"{w.counter_delta('xllm_engine_compile_cache_misses_total')}, "
        f"generator lateness max {max(late, default=0.0) * 1e3:.2f} ms, "
        f"exhausted clients {len(got['exhausted'])}")
    log("in flight, mean over each third of the window: "
        + ", ".join(f"{x:.1f}" for x in stats.in_flight_thirds(records, seconds)))
    shown = ("i", "client", "due", "status", "done", "error", "completion_tokens", "out_len")
    for r in failed[:5]:
        log("failed request: " + json.dumps({k: r[k] for k in shown}))
    if traffic["loop"] == "closed":
        plan = loadgen.build_plan(traffic, cell, args.seed, seconds)
        log_room(w, loadgen.closed_loop_room(plan), plan["warmup_seconds"] + seconds)
    for c in [x["exhausted_client"] for x in got["exhausted"]][:3]:
        log(f"client {c} ran out of requests: " + json.dumps(
            [{k: r[k] for k in shown} for r in records if r.get("client") == c]))

    kind, entries = ("layer_metrics", layer_entries) if args.trace else ("end_to_end", e2e_entries)
    metrics, finite = {}, True
    for e in entries:
        value = load_metric(kind, e["name"]).compute(w)
        if value is None:
            log(f"metric {e['name']}: nothing to read")
        elif value != value or value in (float("inf"), float("-inf")):
            log(f"metric {e['name']} = {value!r}: not a number a result line can carry")
            finite = False
        else:
            metrics[e["name"]] = {"value": float(value), "unit": e["unit"]}
            log(f"metric {e['name']} = {value!r} {e['unit']}")

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    # every number compared, beside its limit(s): the served sample's, then
    # what a reader checked of itself (readers.check_traced_tokens)
    compared = {k: {"value": check[k], "limit": check[k + "_limit"]}
                for k in ("logprob_mse", "deficit_max") if k + "_limit" in check}
    for name, c in w.checks.items():
        compared[name] = {"value": c["value"], "low": c["low"], "high": c["high"]}
        log(f"self-check {name}: " + json.dumps(c))
    off = checks_off(w.checks)
    faults = [why for why, bad in (
        ("the served sample misses the reference's limits", not check["ok"]),
        (f"a reader's check of itself is outside its range: {', '.join(off)}", off),
        (f"{compiles} step program(s) lowered inside the window", compiles != 0),
        (f"{len(got['exhausted'])} closed-loop client(s) ran out of requests", got["exhausted"]),
        ("a metric is not a finite number", not finite),
    ) if bad]
    for why in faults:  # on both streams: a refusal quotes the end of stderr
        log("not correct: " + why)
        print("run.py: not correct: " + why, file=sys.stderr, flush=True)
    result = {
        "correct": not faults,
        "attempted": len(measured), "failed": len(failed),
        "metrics": metrics, "device": device,
    }
    if args.trace and args.rehearse and trace is None:
        log("trace: the CPU backend has no device plane; no busy_s")
    elif args.trace:
        if trace is None or trace["busy_s"] <= 0:
            raise RuntimeError("the traced window holds no device operation")
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
        log("trace: programs " + json.dumps(trace["programs"][:12]))
    result["compared"] = compared  # last in the line
    for name, c in compared.items():  # the last lines on standard error
        print(f"run.py: compared {name}: " + json.dumps(c), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
