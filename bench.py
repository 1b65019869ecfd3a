"""Benchmark driver: continuous-batching decode throughput on the flagship
model (single chip). Prints ONE JSON line.

`vs_baseline` is measured against the only quantitative anchor the reference
publishes (BASELINE.md): its SLO defaults — 50 ms TPOT ⇒ 20 output tok/s per
running request, times the decode batch. >1.0 means every slot in the batch
beats the reference's per-request latency SLO.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

def _backend() -> str:
    """The backend is what JAX_PLATFORMS says: unset (or `tpu`) means the
    chip, `cpu` means the CPU; of a list (`tpu,cpu`, as the chip machine
    sets it) the first name is jax's default backend. The parent never
    touches JAX — a chip belongs to one process at a time, and that is
    the child which runs the config — and the child exits non-zero when
    the backend it finds is not the one named here: no chip is a
    failure, not a CPU run."""
    plat = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if plat in ("", "tpu"):
        return "tpu"
    if plat == "cpu":
        return "cpu"
    raise SystemExit(
        f"bench.py: JAX_PLATFORMS={plat!r}: expected unset, 'tpu' or 'cpu'"
    )


# Wall-clock cap on the child: a config that compiles but then wedges the
# device must end the bench with an error, not hang it — exceptions
# already propagate; a hang needs the process boundary.
_ATTEMPT_TIMEOUT_S = float(os.environ.get("XLLM_BENCH_ATTEMPT_TIMEOUT", 780))


def _run_attempt_subprocess(child_cfg: dict) -> "tuple[int, str, str]":
    """The config in its own PROCESS GROUP: a wedged child (or any
    helper process it forked holding the pipe FDs) is killed as a group,
    so the parent's pipe reads always terminate. Returns (rc, out, err);
    rc < 0 means timeout-killed."""
    import signal

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--attempt-json", json.dumps(child_cfg)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=_ATTEMPT_TIMEOUT_S)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        out, err = proc.communicate()
        return -1, out or "", err or ""


# Best recorded clean-load CPU decode figure (BENCH_r04: 4,262.9 tok/s at
# loadavg 0.2). The guard fails the bench loudly — annotated JSON + exit 3
# AFTER the number prints, so the record survives — when a clean-load CPU
# run lands >5% below it, instead of letting a regression ride silently
# into the record as r5's 4,263 -> 3,902 did (VERDICT r5 #2). Raise this
# anchor whenever a faster clean-load CPU figure is recorded.
_BEST_CPU_DECODE_TOK_S = float(os.environ.get("XLLM_BENCH_CPU_BEST", 4262.9))
# r3 precedent: host load masquerades as regression. Above this 1-min
# loadavg (before or after the timed runs) the guard abstains.
_GUARD_LOADAVG_CEILING = float(os.environ.get("XLLM_BENCH_GUARD_LOAD", 1.0))
# Host-class gate: a 2-CPU dev container lands ~1,400 tok/s at loadavg 0.0
# on the SAME tree that does 4,263 on the r4 driver host (r3's 1,600 was
# the same effect) — an absolute anchor only means anything on hosts of
# the class that recorded it, so the guard abstains below this CPU count.
_GUARD_MIN_CPUS = int(os.environ.get("XLLM_BENCH_GUARD_MIN_CPUS", 4))


# Overlapped-engine A/B guard: the overlapped (default) engine must not
# land below this fraction of the sync escape hatch's throughput in the
# same run — the pipeline paying MORE than it hides is a regression.
_OVERLAP_MIN_RATIO = float(os.environ.get("XLLM_BENCH_OVERLAP_MIN_RATIO", 0.92))

# Mixed-vs-split attention A/B guard (--attention-mode both): the fused
# mixed-step engine (one ragged dispatch per iteration, docs/KERNELS.md)
# must hold at least this fraction of split-step throughput — fusing the
# hot loop can never be allowed to regress silently (ISSUE 9).
_RAGGED_MIN_RATIO = float(os.environ.get("XLLM_BENCH_RAGGED_MIN_RATIO", 0.95))

# Combined-path A/B guard (--spec-mode both, ISSUE 13): with speculative
# decoding ON, the composed engine (overlap pipeline + mixed verify
# batch) must hold at least this fraction of the sync+split verify
# engine's throughput — composing the fast paths must never pay more
# than it hides (the real win is on TPU; CPU arms the floor).
_SPEC_MIN_RATIO = float(os.environ.get("XLLM_BENCH_SPEC_MIN_RATIO", 0.95))

# Latency-hiding collectives A/B guard (--overlap both, ISSUE 18): with
# the ring collective-matmul schedule ON (XLLM_OVERLAP_COLLECTIVES=1,
# docs/SHARDING.md "Hiding the mesh"), the sharded engine must hold at
# least this fraction of the plain-psum row's throughput — decomposing
# the combines buys overlap headroom and must never pay more than it
# hides. Distinct env from XLLM_BENCH_OVERLAP_MIN_RATIO, which floors
# the engine PIPELINE overlap (sync-vs-overlap stepping), not the
# collective schedule.
_OVERLAP_COLL_MIN_RATIO = float(
    os.environ.get("XLLM_BENCH_OVERLAP_COLL_MIN_RATIO", 0.97)
)

# Warm-start host-gap ceiling (ms) for the default engine row: after the
# compile-cache prewarm the first post-idle dispatch must NOT pay a
# fresh XLA compile (PR 11 measured that ambush at 2.7-4 s; steady-state
# host gap on the record is <1 ms) — a mean above this ceiling on a
# clean-load host means programs are compiling inside the serving loop.
_HOST_GAP_MAX_MS = float(os.environ.get("XLLM_BENCH_HOST_GAP_MAX_MS", 25.0))


def _cpu_regression_guard(line: str) -> "tuple[str, int]":
    """Apply the >5% clean-load CPU decode regression guard — and the
    overlap-vs-sync engine A/B guard — to the result line. Returns
    (annotated line, exit code); nonzero means regression."""
    if os.environ.get("XLLM_BENCH_NO_REGRESSION_GUARD"):
        return line, 0
    try:
        res = json.loads(line)
    except ValueError:
        return line, 0
    if res.get("backend") != "cpu" or _BEST_CPU_DECODE_TOK_S <= 0:
        return line, 0
    load = max(
        float(res.get("loadavg_1m_start") or 0.0),
        float(res.get("loadavg_1m") or 0.0),
    )
    value = float(res.get("value") or 0.0)
    ncpu = os.cpu_count() or 1
    if ncpu < _GUARD_MIN_CPUS:
        res["cpu_regression_guard"] = (
            f"abstained: {ncpu}-CPU host below the anchor's class "
            f"(set XLLM_BENCH_CPU_BEST for a local anchor)"
        )
        return json.dumps(res), 0
    if load > _GUARD_LOADAVG_CEILING:
        res["cpu_regression_guard"] = f"abstained: loadavg {load:.1f}"
        return json.dumps(res), 0
    rc = 0
    if value >= 0.95 * _BEST_CPU_DECODE_TOK_S:
        res["cpu_regression_guard"] = "ok"
    else:
        res["cpu_regression_guard"] = (
            f"FAIL: {value:.1f} tok/s is "
            f"{100.0 * (1.0 - value / _BEST_CPU_DECODE_TOK_S):.1f}% below "
            f"the best recorded clean-load CPU figure "
            f"{_BEST_CPU_DECODE_TOK_S:.1f}"
        )
        rc = 3
    # Engine-level A/B (runs against the overlapped DEFAULT mode): present
    # only when the run measured both modes.
    eb = res.get("engine_bench") or {}
    if isinstance(eb, dict) and "sync" in eb and "overlap" in eb:
        try:
            s = float(eb["sync"]["tok_s"])
            o = float(eb["overlap"]["tok_s"])
        except (KeyError, TypeError, ValueError):
            s = o = 0.0
        if s <= 0:
            pass
        elif o >= _OVERLAP_MIN_RATIO * s:
            res["engine_overlap_guard"] = "ok"
        else:
            res["engine_overlap_guard"] = (
                f"FAIL: overlapped engine {o:.1f} tok/s is below "
                f"{100 * _OVERLAP_MIN_RATIO:.0f}% of sync mode {s:.1f}"
            )
            rc = rc or 3
    # Attention-mode A/B (--attention-mode both): the mixed (ragged) step
    # builder vs the split-step escape hatch.
    ab = res.get("attention_bench") or {}
    if isinstance(ab, dict) and "split" in ab and "ragged" in ab:
        try:
            s = float(ab["split"]["tok_s"])
            g = float(ab["ragged"]["tok_s"])
        except (KeyError, TypeError, ValueError):
            s = g = 0.0
        # The rows must have RUN the builders they are labeled as — a
        # family without a mixed step runs split whatever the per-run
        # config says, and a split-vs-split comparison stamping "ok"
        # would defeat the guard's whole purpose.
        builders = (
            ab["split"].get("step_builder"),
            ab["ragged"].get("step_builder"),
        )
        if builders != ("split", "ragged"):
            res["engine_ragged_guard"] = (
                f"abstained: step_builder {builders[0]}/{builders[1]} — "
                f"the engine resolved another builder than the row's"
            )
        elif s <= 0:
            pass
        elif g >= _RAGGED_MIN_RATIO * s:
            res["engine_ragged_guard"] = "ok"
        else:
            res["engine_ragged_guard"] = (
                f"FAIL: mixed (ragged) engine {g:.1f} tok/s is below "
                f"{100 * _RAGGED_MIN_RATIO:.0f}% of split mode {s:.1f}"
            )
            rc = rc or 3
    # Combined-path A/B (--spec-mode both): speculative decode through
    # the composed overlap+mixed pipeline vs the sync+split verify
    # engine (ISSUE 13).
    sb = res.get("spec_bench") or {}
    if isinstance(sb, dict) and "composed" in sb and "sync_split" in sb:
        try:
            s = float(sb["sync_split"]["tok_s"])
            c = float(sb["composed"]["tok_s"])
        except (KeyError, TypeError, ValueError):
            s = c = 0.0
        # The rows must have RUN the builders they are labeled as: a
        # sync-vs-sync comparison stamping "ok" would defeat the guard —
        # abstain loudly on a builder mismatch, like engine_ragged_guard.
        builders = (
            sb["composed"].get("step_builder"),
            sb["sync_split"].get("step_builder"),
        )
        if builders != ("spec-overlap+mixed", "spec-sync+split"):
            # "spec-overlap+split" for the composed row is also a
            # legitimate label: the model family has no
            # mixed_verify_step (MLA), so verify rows pipelined without
            # prefill fusion — name that cause where it is the one.
            cause = (
                "the family lacks mixed_verify_step (no spec+mixed "
                "fusion)"
                if builders[0] == "spec-overlap+split"
                and builders[1] == "spec-sync+split"
                else "the engine resolved another builder than the row's"
            )
            res["engine_spec_guard"] = (
                f"abstained: step_builder {builders[0]}/{builders[1]} — "
                f"{cause}"
            )
        elif s <= 0:
            pass
        elif c >= _SPEC_MIN_RATIO * s:
            res["engine_spec_guard"] = "ok"
        else:
            res["engine_spec_guard"] = (
                f"FAIL: composed spec engine {c:.1f} tok/s is below "
                f"{100 * _SPEC_MIN_RATIO:.0f}% of sync+split {s:.1f}"
            )
            rc = rc or 3
    return json.dumps(res), rc


def _overlap_guard(line: str) -> "tuple[str, int]":
    """Exit-3 guards for the --overlap A/B rows and the warm-start host
    gap (ISSUE 18). `engine_overlap_collectives_guard` floors the ring
    collective-matmul row against plain psum and abstains LOUDLY when
    the labeled rows did not actually route the schedule (the
    engine_mesh_guard dispatch-mismatch pattern); `engine_host_gap_guard`
    ceilings the default engine row's mean host gap so an
    in-serving-loop recompile can never ride into the record as a tok/s
    blip."""
    if os.environ.get("XLLM_BENCH_NO_REGRESSION_GUARD"):
        return line, 0
    try:
        res = json.loads(line)
    except ValueError:
        return line, 0
    rc = 0
    load = max(
        float(res.get("loadavg_1m_start") or 0.0),
        float(res.get("loadavg_1m") or 0.0),
    )
    ob = res.get("overlap_bench") or {}
    if isinstance(ob, dict) and "on" in ob and "off" in ob:
        routed = (
            ob["on"].get("overlap_collectives"),
            ob["off"].get("overlap_collectives"),
        )
        try:
            on = float(ob["on"]["tok_s"])
            off = float(ob["off"]["tok_s"])
        except (KeyError, TypeError, ValueError):
            on = off = 0.0
        if routed != (True, False):
            # The documented abstention: on a single-device mesh
            # (tp=1, ep=1) the ring schedule is ineligible by design —
            # both rows ran the original einsum and a floor over them
            # would stamp "ok" on nothing. Also covers an env override
            # pinning the hatch under both labels.
            cause = (
                "the ring schedule never engaged (single-device mesh — "
                "run --mesh 1,N,1; parity/eligibility is tier-1's "
                "tests/test_overlap_collectives.py)"
                if routed == (False, False)
                else "an env override pinned the hatch "
                "(XLLM_OVERLAP_COLLECTIVES?)"
            )
            res["engine_overlap_collectives_guard"] = (
                f"abstained: overlap_collectives {routed[0]}/{routed[1]}"
                f" — {cause}"
            )
        elif res.get("backend") != "tpu":
            # The mesh-guard precedent: a CPU virtual mesh proves
            # routing (the rows above carry overlap_collectives
            # True/False) but not performance — every ppermute hop is a
            # same-host memcpy with no ICI to hide it behind, so the
            # ring reads as pure overhead and the floor would flake.
            res["engine_overlap_collectives_guard"] = (
                "abstained: virtual CPU mesh — ppermute hops have no "
                "ICI to hide behind off-TPU; the floor arms on TPU "
                "(bit-parity is tier-1's tests/test_overlap_collectives"
                ".py)"
            )
        elif load > _GUARD_LOADAVG_CEILING:
            res["engine_overlap_collectives_guard"] = (
                f"abstained: loadavg {load:.1f}"
            )
        elif on <= 0 or off <= 0:
            res["engine_overlap_collectives_guard"] = (
                f"abstained: unparseable tok_s (on={on}, off={off})"
            )
        elif on >= _OVERLAP_COLL_MIN_RATIO * off:
            res["engine_overlap_collectives_guard"] = "ok"
        else:
            res["engine_overlap_collectives_guard"] = (
                f"FAIL: collective-matmul engine {on:.1f} tok/s is "
                f"below {100 * _OVERLAP_COLL_MIN_RATIO:.0f}% of the "
                f"psum row {off:.1f}"
            )
            rc = 3
    # Warm-start host-gap ceiling on the default (overlapped) engine
    # row: the timed repeats run after the warm passes, so a mean above
    # the ceiling means a program compiled INSIDE the serving loop —
    # exactly the post-idle ambush the compile-cache prewarm exists to
    # kill. Timing-based absolute ceiling, so it inherits the CPU
    # guard's host-class and load abstentions.
    eb = res.get("engine_bench") or {}
    row = eb.get("overlap") if isinstance(eb, dict) else None
    if isinstance(row, dict) and row.get("host_gap_ms_mean") is not None:
        gap = float(row["host_gap_ms_mean"])
        ncpu = os.cpu_count() or 1
        if ncpu < _GUARD_MIN_CPUS:
            res["engine_host_gap_guard"] = (
                f"abstained: {ncpu}-CPU host below the ceiling's class"
            )
        elif load > _GUARD_LOADAVG_CEILING:
            res["engine_host_gap_guard"] = f"abstained: loadavg {load:.1f}"
        elif gap <= _HOST_GAP_MAX_MS:
            res["engine_host_gap_guard"] = "ok"
        else:
            res["engine_host_gap_guard"] = (
                f"FAIL: warm-start host gap {gap:.3f} ms exceeds the "
                f"{_HOST_GAP_MAX_MS:.0f} ms ceiling — a program is "
                f"compiling inside the serving loop (compile-cache "
                f"prewarm missed a variant? see compile_cache_bench)"
            )
            rc = rc or 3
    return json.dumps(res), rc


# Sharded-decode roofline guard (--mesh, ROADMAP item 3): on TPU a
# tp-sharded decode must land at least this fraction of its analytic
# per-shard roofline expectation — a GSPMD-replicated kernel or a silent
# gather fallback is ~tp× off, which this catches loudly (exit 3)
# instead of letting a degraded multi-chip round into the record.
_MESH_MIN_ROOFLINE_RATIO = float(
    os.environ.get("XLLM_BENCH_MESH_MIN_RATIO", 0.5)
)


def _mesh_guard(line: str) -> "tuple[str, int]":
    """Exit-3 guard for --mesh rows. Abstains LOUDLY off-TPU (the same
    pattern as engine_spec_guard): a CPU virtual mesh proves parity in
    tier-1, not performance — the floor arms only where the roofline
    means something."""
    try:
        res = json.loads(line)
    except ValueError:
        return line, 0
    m = res.get("mesh") or {}
    if not isinstance(m, dict) or m.get("dp", 1) * m.get("tp", 1) * m.get(
        "ep", 1
    ) <= 1:
        return line, 0
    if res.get("backend") != "tpu":
        res["engine_mesh_guard"] = (
            "abstained: virtual CPU mesh — shard parity is tier-1's "
            "differential suite (tests/test_sharded_engine.py); the "
            "per-shard roofline floor arms on TPU"
        )
        return json.dumps(res), 0
    try:
        value = float(res.get("value") or 0.0)
        expect = float(res["decode_roofline"]["expected_tok_s"])
    except (KeyError, TypeError, ValueError):
        return line, 0
    if expect <= 0:
        return line, 0
    if value >= _MESH_MIN_ROOFLINE_RATIO * expect:
        res["engine_mesh_guard"] = "ok"
        return json.dumps(res), 0
    res["engine_mesh_guard"] = (
        f"FAIL: sharded decode {value:.1f} tok/s is below "
        f"{100 * _MESH_MIN_ROOFLINE_RATIO:.0f}% of the per-shard "
        f"roofline expectation {expect:.1f} — GSPMD-replicated kernel "
        f"or gather fallback? (see kernel_shards / attention_kernel)"
    )
    return json.dumps(res), 3


def main() -> None:
    if "--attempt-json" in sys.argv:
        # child mode: run exactly one config in THIS process
        cfg = json.loads(sys.argv[sys.argv.index("--attempt-json") + 1])
        on_tpu = cfg.pop("_on_tpu")
        if not on_tpu:
            from __graft_entry__ import _force_cpu_platform

            # CPU mesh runs need that many VIRTUAL host devices — the
            # same --xla_force_host_platform_device_count trick the
            # tier-1 differential suite runs on (docs/SHARDING.md).
            dp, tp, ep = cfg.get("mesh", (1, 1, 1))
            _force_cpu_platform(max(1, dp * tp * ep))
        import jax

        found = jax.devices()[0].platform
        if found != ("tpu" if on_tpu else "cpu"):
            raise SystemExit(
                f"bench.py: JAX_PLATFORMS names "
                f"{'the chip' if on_tpu else 'the CPU'} but jax found "
                f"{found!r} — not benching on another backend"
            )
        _run(on_tpu, **cfg)
        return

    # --mesh dp,tp,ep: bench a SHARDED engine (ROADMAP item 3). On TPU
    # this is the real multi-chip GSPMD tier (tp-sharded 70B-class
    # decode, per-shard Pallas dispatch); on CPU it runs the same code
    # on the virtual host mesh so MULTICHIP/BENCH rounds get comparable
    # shard-aware rows before a chip window opens. Default 1,1,1.
    mesh = (1, 1, 1)
    if "--mesh" in sys.argv:
        raw = sys.argv[sys.argv.index("--mesh") + 1]
        try:
            parts = [int(x) for x in raw.split(",")]
        except ValueError:
            parts = []
        if len(parts) != 3 or any(p < 1 for p in parts):
            raise SystemExit(f"--mesh must be dp,tp,ep integers, got {raw!r}")
        mesh = tuple(parts)

    # --engine-mode {sync,overlap,both}: which InferenceEngine stepping
    # mode(s) the engine-level A/B section measures (docs/ENGINE_PIPELINE.md).
    # Default "both" reports the A/B pair and arms the overlap guard.
    engine_mode = "both"
    if "--engine-mode" in sys.argv:
        engine_mode = sys.argv[sys.argv.index("--engine-mode") + 1]
        if engine_mode not in ("sync", "overlap", "both"):
            raise SystemExit(
                f"--engine-mode must be sync|overlap|both, got {engine_mode!r}"
            )

    # --attention-mode {split,ragged,both}: mixed (ragged) stepping vs the
    # split-step escape hatch (docs/KERNELS.md), mirroring --engine-mode.
    # Default "both" reports the A/B pair and arms the ragged guard.
    attention_mode = "both"
    if "--attention-mode" in sys.argv:
        attention_mode = sys.argv[sys.argv.index("--attention-mode") + 1]
        if attention_mode not in ("split", "ragged", "both"):
            raise SystemExit(
                f"--attention-mode must be split|ragged|both, "
                f"got {attention_mode!r}"
            )

    # --spec-mode {composed,sync,both}: the combined-path A/B (ISSUE 13)
    # — speculative decoding through the composed overlap+mixed pipeline
    # vs the sync+split verify engine. Default "both" reports the pair
    # and arms the engine_spec_guard.
    spec_mode = "both"
    if "--spec-mode" in sys.argv:
        spec_mode = sys.argv[sys.argv.index("--spec-mode") + 1]
        if spec_mode not in ("composed", "sync", "both"):
            raise SystemExit(
                f"--spec-mode must be composed|sync|both, got {spec_mode!r}"
            )

    # --overlap {on,off,both}: the latency-hiding collectives A/B
    # (ISSUE 18) — the ring collective-matmul schedule
    # (XLLM_OVERLAP_COLLECTIVES=1, docs/SHARDING.md) vs the plain
    # psum/einsum combines, on the tp-sharded engine. Default "both"
    # reports the pair and arms engine_overlap_collectives_guard.
    overlap_mode = "both"
    if "--overlap" in sys.argv:
        idx = sys.argv.index("--overlap") + 1
        nxt = sys.argv[idx] if idx < len(sys.argv) else ""
        if nxt in ("on", "off", "both"):
            overlap_mode = nxt
        elif nxt and not nxt.startswith("-"):
            raise SystemExit(f"--overlap takes on|off|both, got {nxt!r}")
        # bare `--overlap` (or followed by another flag) = "both"

    on_tpu = _backend() == "tpu"
    # One config — the engine's defaults, the path chip_smoke.py checks on
    # the chip. Failure is failure: no ladder of slower configs behind it.
    rc, out, err = _run_attempt_subprocess(
        dict(kv_cache_dtype="auto", engine_mode=engine_mode,
             attention_mode=attention_mode, spec_mode=spec_mode,
             overlap_mode=overlap_mode,
             mesh=list(mesh), _on_tpu=on_tpu)
    )
    line = ""
    for ln in out.splitlines():
        if ln.startswith("{"):
            line = ln
    if rc != 0 or not line:
        sys.stderr.write(err[-4000:])
        raise SystemExit(
            f"bench failed: timed out after {_ATTEMPT_TIMEOUT_S:.0f}s"
            if rc < 0
            else f"bench failed: rc={rc}"
        )
    line, guard_rc = _cpu_regression_guard(line)
    line, mesh_rc = _mesh_guard(line)
    line, ovl_rc = _overlap_guard(line)
    guard_rc = guard_rc or mesh_rc or ovl_rc
    print(line)
    if guard_rc:
        print(
            "# CPU decode regression guard tripped — see the "
            "cpu_regression_guard field", file=sys.stderr,
        )
        sys.exit(guard_rc)


def _engine_bench(sync: bool, mixed: bool = True, spec: int = 0,
                  model: str = "llama3-tiny",
                  overlap: "str | None" = None,
                  tp: int = 1) -> dict:
    """Full-InferenceEngine decode throughput (llama3-tiny, R=8) in one
    stepping mode: R seeded requests driven to completion through the real
    admission/decode/emit path. Reports tokens/s plus the pipeline
    instruments — mean host_gap_ms (host bookkeeping between steps), the
    fraction of decode steps dispatched with another step in flight, the
    fraction of dispatches that fused prefill rows with the decode batch
    (`mixed` stepping, docs/KERNELS.md), and the RESOLVED attention
    kernel the engine's dispatches actually route to. `spec` > 0 runs
    the same harness under speculative decoding (the ISSUE 13 combined
    path: sync/mixed then select composed vs sync+split verify).
    `overlap` pins the collective-matmul schedule for the
    --overlap A/B (ISSUE 18): "on" sets XLLM_OVERLAP_COLLECTIVES=1,
    "off" =0 — the row reports `overlap_collectives`, whether the ring
    schedule was actually ELIGIBLE (tp>1/ep>1), which the guard keys
    on. `tp` runs the engine tp-sharded (needs that many devices)."""
    import numpy as np

    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.runtime.executor import ModelExecutor

    if overlap is not None:
        # Pin around the WHOLE run: the hatch is
        # read at trace time and later bucket shapes retrace mid-run,
        # so a leaky override would split one row across schedules.
        prev_ovl_env = os.environ.get("XLLM_OVERLAP_COLLECTIVES")
        os.environ["XLLM_OVERLAP_COLLECTIVES"] = (
            "1" if overlap == "on" else "0"
        )
        try:
            row = _engine_bench(
                sync, mixed=mixed, spec=spec, model=model, tp=tp
            )
            row["overlap_mode"] = overlap
            return row
        finally:
            if prev_ovl_env is None:
                os.environ.pop("XLLM_OVERLAP_COLLECTIVES", None)
            else:
                os.environ["XLLM_OVERLAP_COLLECTIVES"] = prev_ovl_env

    R, prompt_len, new_tokens = 8, 32, 48
    cfg = EngineConfig(
        model=model,
        dtype="float32",
        block_size=16,
        num_blocks=64,
        max_running_requests=R,
        max_seq_len=128 if tp > 1 else 256,
        prefill_buckets=[32, 64, 128] if tp > 1 else [32, 64, 128, 256],
        tp_size=tp,
        sync_engine=sync,
        enable_mixed_step=mixed,
        # sync=True + spec steps verify at depth 0: the sync+split row.
        speculative_tokens=spec,
    )
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, eng.executor.cfg.vocab_size, (prompt_len,)).tolist()
        for _ in range(R)
    ]

    def run_once(tag):
        emitted = [0]

        def cb(out):
            for so in out.outputs:
                emitted[0] += len(so.token_ids)
            return True

        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.add_request(EngineRequest(
                request_id=f"{tag}-{i}",
                prompt_token_ids=list(p),
                sampling=SamplingParams(
                    temperature=0.7, seed=i + 1, max_new_tokens=new_tokens,
                ),
                callback=cb,
            ))
        while eng.has_work():
            eng.step()
        return emitted[0], time.perf_counter() - t0

    run_once("warm")  # compile every shape outside the timing
    if spec and not sync:
        # Second warm pass for the pipelined verify: a first post-idle
        # dispatch sees device-provenance prev/cache arrays that the
        # cold boot's numpy-fed shapes didn't cover — one more full
        # cycle compiles those variants outside the timed window too.
        run_once("warm2")
    repeats = int(os.environ.get("XLLM_BENCH_ENGINE_REPEATS", 3))
    gap0, gsteps0 = eng.host_gap_ms_sum, eng.host_gap_steps
    ov0, disp0 = eng.overlap_steps, eng.decode_dispatches
    disc0, mix0 = eng.late_stop_discards, eng.mixed_steps
    emit0, sstep0 = eng.spec_tokens_emitted, eng.spec_slot_steps
    pipe0, spec0 = eng.spec_pipeline_steps, eng.spec_steps
    coll0 = eng.collective_overlap_steps
    dts, toks = [], 0
    for r in range(repeats):
        n, dt = run_once(f"t{r}")
        toks = n
        dts.append(dt)
    dt = float(np.median(dts))
    gap_steps = max(eng.host_gap_steps - gsteps0, 1)
    dispatches = max(eng.decode_dispatches - disp0, 1)
    # The builder the engine actually RAN, not the config knob: depth 0
    # forces the split path even with mixed enabled, and so does a
    # family without the fused step — the guards abstain on a label
    # mismatch.
    pipelined = not eng._force_sync
    mixed_ran = eng.mixed_step_enabled  # the engine's own live decision
    if spec:
        builder = (
            "spec-overlap+mixed" if mixed_ran
            else "spec-overlap+split" if pipelined
            else "spec-sync+split"
        )
    else:
        builder = "ragged" if mixed_ran else "split"
    row = {
        "mode": "sync" if sync else "overlap",
        "step_builder": builder,
        # The dispatch decision the engine RESOLVED for the step builder
        # it actually ran — the fused step's pair of launches
        # (<decode>+<prefill>), or the split builder's separate pair —
        # not the raw env var (ISSUE 9 satellite).
        "kernel": (
            eng._kernel_names["mixed"] if mixed_ran
            else f"split[{eng._kernel_names['decode']}+"
            f"{eng._kernel_names['prefill']}]"
        ),
        "tok_s": round(toks / dt, 1),
        "host_gap_ms_mean": round(
            (eng.host_gap_ms_sum - gap0) / gap_steps, 3
        ),
        "overlap_step_frac": round(
            (eng.overlap_steps - ov0) / dispatches, 3
        ),
        "mixed_step_frac": round(
            (eng.mixed_steps - mix0) / dispatches, 3
        ),
        "late_stop_discards": eng.late_stop_discards - disc0,
        "requests": R,
        "new_tokens": new_tokens,
        # Whether the ring collective-matmul schedule was ELIGIBLE for
        # this geometry (hatch on AND tp>1/ep>1) plus the steps that
        # dispatched through it — engine_overlap_collectives_guard keys
        # on the flag, never the raw env var (ISSUE 18).
        "overlap_collectives": bool(
            getattr(eng.executor, "overlap_collectives_active", False)
        ),
        "collective_overlap_steps": eng.collective_overlap_steps - coll0,
    }
    if getattr(eng.executor.cfg, "is_moe", False):
        # Resolved MoE dispatch + the expert-load signal (ISSUE 15):
        # the guard keys on moe_dispatch, not the env var — and on the
        # interpret hook, whose rows measure the interpreter.
        rep = eng.executor.kernel_report()
        row["moe_dispatch"] = rep.get("moe")
        row["moe_shards"] = rep.get("moe_shards", 1)
        row["moe_interpret"] = (
            os.environ.get("XLLM_MOE_INTERPRET") == "1"
        )
        stats = eng.executor.moe_stats(drain=True)
        row["moe_hot_expert_frac"] = round(stats["hot_expert_frac"], 3)
        row["moe_dropped_assignments"] = stats["dropped"]
    if spec:
        # Realized speculative speedup + how the verify steps routed —
        # deltas over the timed repeats only, like the other counters
        # (the warm passes must not fold into the A/B rows).
        row["spec_tokens"] = spec
        row["accepted_len_mean"] = round(
            (eng.spec_tokens_emitted - emit0)
            / max(eng.spec_slot_steps - sstep0, 1), 3
        )
        row["spec_pipeline_step_frac"] = round(
            (eng.spec_pipeline_steps - pipe0)
            / max(eng.spec_steps - spec0, 1), 3
        )
    return row


def _compile_cache_bench() -> dict:
    """Cold-vs-warm persistent compile cache A/B (ISSUE 18 tentpole b):
    two fresh executors prewarmed against the process's on-disk cache
    dir (runtime/compile_cache.py: where JAX_COMPILATION_CACHE_DIR
    places it, else the fixed path in the checkout) — the first pass
    pays every XLA compile the directory does not hold yet
    (`cache_entries_before` says how cold it was), the second (new jit
    wrappers, so jaxpr lowering still runs) reloads the executables
    from disk, which is exactly what a restarted instance with the same
    geometry sees. Minimal geometry (one prefill bucket, mixed step
    off) keeps the section to seconds; the absolute delta scales with
    the real bucket-program family."""
    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.runtime import compile_cache as cc
    from xllm_service_tpu.runtime.executor import ModelExecutor

    if not cc.compile_cache_enabled():
        return {"skipped": "XLLM_COMPILE_CACHE=0"}
    base = cc.resolve_cache_dir(cc.DEFAULT_DIR)
    before = cc.cache_entries(base)
    prev_min = os.environ.get("XLLM_COMPILE_CACHE_MIN_COMPILE_S")
    # Everything in this tiny geometry compiles fast — persist it all,
    # or the warm pass would measure nothing but re-compiles.
    os.environ["XLLM_COMPILE_CACHE_MIN_COMPILE_S"] = "0"
    try:
        cfg = EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=16,
            num_blocks=32, max_running_requests=4, max_seq_len=64,
            prefill_buckets=[32], enable_mixed_step=False,
            compilation_cache_dir=base,
        )
        cold = ModelExecutor(cfg)
        cold.prewarm_programs()
        warm = ModelExecutor(cfg)
        warm.prewarm_programs()
        return {
            "programs": cold.prewarm_report["programs"],
            "compile_ms_cold": round(cold.prewarm_ms, 1),
            "compile_ms_warm": round(warm.prewarm_ms, 1),
            "cache_entries_before": before,
            "cache_entries": cc.cache_entries(base),
            "cache_dir": base,
        }
    finally:
        if prev_min is None:
            os.environ.pop("XLLM_COMPILE_CACHE_MIN_COMPILE_S", None)
        else:
            os.environ["XLLM_COMPILE_CACHE_MIN_COMPILE_S"] = prev_min


def _run(on_tpu: bool, kv_cache_dtype: str = "auto",
         use_kernel: bool | None = None,
         weight_dtype: str = "auto",
         engine_mode: str = "both",
         attention_mode: str = "both",
         spec_mode: str = "both",
         overlap_mode: str = "both",
         mesh=(1, 1, 1)) -> None:
    import jax

    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime import compile_cache
    from xllm_service_tpu.runtime.executor import ModelExecutor, SamplingBatch

    dp, tp, ep = (int(x) for x in mesh)
    n_dev = dp * tp * ep
    # llama3-3b: largest llama member fitting v5e HBM (6.4 GB bf16 params);
    # head_dim 128 engages the Pallas decode kernel (1b's 64 cannot).
    model = "llama3-3b" if on_tpu else "llama3-tiny"
    if n_dev > 1:
        # Sharded rounds (--mesh): the 70B-class serving layout the
        # BASELINE round-3 dress rehearsal proved fits v5e at tp=8 with
        # int8 W8+KV8; the CPU virtual mesh runs the tp-shardable tiny
        # geometry (Hkv=8 divides every tp; llama3-tiny's Hkv=2 caps at
        # tp=2) so shard-aware rows exist before a chip window opens.
        # An ep axis (--mesh d,t,e with e>1) selects the MoE workload —
        # the `ep` axis is only real when experts shard over it
        # (ISSUE 15, docs/MOE.md).
        if ep > 1:
            default_model = (
                "qwen3-30b-a3b" if on_tpu else "moe-shard-tiny"
            )
        else:
            default_model = "llama3-70b" if on_tpu else "llama3-shard-tiny"
        model = os.environ.get("XLLM_BENCH_MESH_MODEL", default_model)
    # 32 slots on the chip: the auto-sized bf16 pool of llama3-3b on one
    # v5e is 299 blocks (PERF.md, PR 26) and each slot takes 5.
    R = 32 if on_tpu else 8
    prompt_len = 512 if on_tpu else 32
    decode_steps = 128 if on_tpu else 8

    cfg = EngineConfig(
        model=model,
        max_running_requests=R,
        max_seq_len=2048 if on_tpu else 256,
        # On the chip the executor sizes the pool from the HBM the
        # params leave (executor._decide_num_blocks): an explicit 512
        # bf16 blocks is refused by the compiler (PERF.md, PR 26).
        num_blocks=0 if on_tpu else 64,
        block_size=128 if on_tpu else 16,
        # int8 KV: halves the decode attention HBM traffic (validated
        # kernel + e2e parity in tests/test_kv_quant.py).
        kv_cache_dtype=kv_cache_dtype,
        weight_dtype=weight_dtype,
        dp_size=dp, tp_size=tp, ep_size=ep,
        # Persistent jit cache: re-runs skip the ~20 s-per-program TPU
        # compiles (runtime/compile_cache.py places the directory).
        compilation_cache_dir=(
            compile_cache.DEFAULT_DIR if on_tpu else ""
        ),
    )
    prev_prefill_env = os.environ.get("XLLM_PREFILL_ATTENTION_KERNEL")
    if use_kernel is False:
        # Conservative fallback config: force BOTH Pallas paths off so a
        # kernel-compile regression can never take the bench down.
        # Restored in the finally at the end — a later attempt in this
        # process must not inherit the override.
        os.environ["XLLM_PREFILL_ATTENTION_KERNEL"] = "0"
    try:
        ex = ModelExecutor(cfg)
        # The scan harness below calls llama.decode_step inside its OWN
        # jit (not the executor's step functions), so the per-shard
        # kernel dispatch context must be declared here for the trace.
        ex._set_shard_ctx()
        bs = ex.block_size
        # The dispatch decisions the serving paths RESOLVE for this
        # cache/geometry (ops.attention.attention_routes) — the
        # record gets which kernel actually runs, not the raw env var.
        kernel_rep = (
            ex.kernel_report() if hasattr(ex, "kernel_report") else {}
        )
        rng = np.random.default_rng(0)

        # Fill every slot with a prefilled context of prompt_len tokens via the
        # BATCHED prefill path (the serving admission path) — timed, so the
        # bench also reports prefill throughput.
        from xllm_service_tpu.runtime.executor import PrefillItem

        blocks_per_seq = (prompt_len + 1 + bs - 1) // bs
        assert ex.num_blocks > R * blocks_per_seq, "KV pool too small for bench"
        tables = np.zeros((R, ex.max_blocks_per_seq), np.int32)
        next_block = 1
        items = []
        for r in range(R):
            ids = list(range(next_block, next_block + blocks_per_seq))
            next_block += blocks_per_seq
            tables[r, : len(ids)] = ids
            items.append(
                PrefillItem(
                    token_ids=rng.integers(
                        0, ex.cfg.vocab_size, (prompt_len,), np.int32
                    ),
                    start_pos=0,
                    block_table=tables[r],
                )
            )
        # Median-of-N timing (r3 lesson: the round's only CPU number was
        # 2.6x off its r2 twin, most plausibly from host load at snapshot
        # time; a single sample can't tell load from regression).
        repeats = int(os.environ.get("XLLM_BENCH_REPEATS", 3 if on_tpu else 5))
        load_before = os.getloadavg()

        ex.prefill_batch(items)  # warmup/compile (idempotent: same blocks)
        prefill_dts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            ex.prefill_batch(items)
            prefill_dts.append(time.perf_counter() - t0)
        prefill_dt = float(np.median(prefill_dts))
        prefill_tok_s = R * prompt_len / prefill_dt

        token_ids = rng.integers(0, ex.cfg.vocab_size, (R,)).astype(np.int32)
        positions = np.full((R,), prompt_len, np.int32)
        active = np.ones((R,), bool)
        s = SamplingParams(temperature=0.7)
        batch = SamplingBatch(
            np.full((R,), s.temperature, np.float32),
            np.zeros((R,), np.int32),
            np.ones((R,), np.float32),
            rng.integers(0, 2**32, (R,)).astype(np.uint32),
            np.zeros((R,), np.int32),
        )

        # Timed loop runs ON DEVICE via lax.scan (autoregressive feedback, fused
        # sampling each step) so the number measures device decode throughput,
        # not per-dispatch host latency.
        import jax
        import jax.numpy as jnp

        from xllm_service_tpu.models import llama
        from xllm_service_tpu.ops import sampling as sampling_ops

        mcfg = ex.cfg

        def run_steps(k_cache, v_cache, params, tokens0, pos0, tables, active,
                      temps, top_ks, top_ps, seeds):
            def body(carry, step):
                k_cache, v_cache, toks, pos = carry
                logits, k_cache, v_cache = llama.decode_step(
                    params, mcfg, k_cache, v_cache, toks, pos, tables, active,
                    use_kernel=use_kernel)
                keys = sampling_ops.make_step_keys(seeds, step)
                toks, _, _ = sampling_ops.sample_tokens(
                    logits, temps, top_ks, top_ps, keys)
                return (k_cache, v_cache, toks, pos + 1), toks

            (k_cache, v_cache, toks, _), out = jax.lax.scan(
                body, (k_cache, v_cache, tokens0, pos0),
                jnp.arange(decode_steps, dtype=jnp.int32))
            return k_cache, v_cache, out

        run = jax.jit(run_steps, donate_argnums=(0, 1))
        args = (
            jnp.asarray(token_ids), jnp.asarray(positions), jnp.asarray(tables),
            jnp.asarray(active),
            jnp.asarray(batch.temperature), jnp.asarray(batch.top_k),
            jnp.asarray(batch.top_p), jnp.asarray(batch.seeds),
        )
        # Force a host fetch of the result: a device->host transfer of a
        # value that depends on every step drains the queue.
        ex.k_cache, ex.v_cache, out = run(ex.k_cache, ex.v_cache, ex.params, *args)
        int(jnp.sum(out))  # warmup/compile + drain
        dts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            ex.k_cache, ex.v_cache, out = run(
                ex.k_cache, ex.v_cache, ex.params, *args
            )
            int(jnp.sum(out))
            dts.append(time.perf_counter() - t0)
        dt = float(np.median(dts))

        tok_per_s = R * decode_steps / dt
        baseline = R * (1000.0 / 50.0)  # reference SLO: 50 ms TPOT per request

        # Roofline context: decode FLOPs/token ≈ 2·params (matmuls) plus
        # attention score/value FLOPs over the live context.
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(ex.params))
        ctx = prompt_len + decode_steps // 2
        attn_flops = 4 * mcfg.num_layers * mcfg.num_heads * mcfg.head_dim * ctx
        flops_per_tok = 2 * n_params + attn_flops
        achieved_flops = flops_per_tok * tok_per_s
        peak = _peak_flops(jax.devices()[0])
        # Prefill MFU: matmul FLOPs + causal attention (~L^2/2 per sequence).
        # Unembed runs ONCE per sequence (last token only) and the embedding
        # is a gather, so the per-token cost excludes lm_head — unlike decode,
        # which unembeds every token.
        lm_head_params = (
            0 if mcfg.tie_word_embeddings else mcfg.hidden_size * mcfg.vocab_size
        )
        body_params = n_params - lm_head_params - mcfg.vocab_size * mcfg.hidden_size
        prefill_flops = R * (
            prompt_len * 2 * body_params
            + 2 * mcfg.hidden_size * mcfg.vocab_size  # one unembed per seq
            + 4 * mcfg.num_layers * mcfg.num_heads * mcfg.head_dim
            * prompt_len * prompt_len // 2
        )
        prefill_mfu = (
            round(prefill_flops / prefill_dt / peak, 4) if peak else None
        )

        # Analytic roofline (VERDICT r4 #2): expected MFU / HBM-GB/s per
        # config, computable on ANY backend — on CPU the expectation is
        # referenced against the bench's TPU target (v5e) so a CPU
        # round still records where perf SHOULD land.
        # Weight bytes come from the LIVE param leaves (so W8/W4
        # quantized residency is counted as served); KV bytes from the
        # cache dtype. XLA's compiled-module cost_analysis is recorded
        # alongside for reference but NOT used for the expectation: it
        # counts lax.scan bodies once (verified: 17 GFLOP reported vs
        # 282 analytic on the 80-layer 70B decode), so it under-counts
        # scanned stacks ~num_layers-fold.
        peak_bw = _peak_hbm_bw(jax.devices()[0])
        roofline_ref = None
        peak_ref, bw_ref = peak, peak_bw
        if peak_ref is None or bw_ref is None:
            peak_ref, bw_ref, roofline_ref = 197e12, 819e9, "v5e"
        weight_bytes = sum(
            int(p.nbytes) for p in jax.tree.leaves(ex.params)
        )
        if cfg.kv_cache_dtype == "int8":
            kv_elem_bytes = 1
        else:
            kv_elem_bytes = 4 if cfg.dtype == "float32" else 2
        kv_row = mcfg.num_layers * mcfg.num_kv_heads * mcfg.head_dim
        # Decode step: whole weight set streams once per step (R
        # amortizes it), each slot reads its live context's K/V rows.
        # Sharded meshes: the roofline is PER DEVICE — params/FLOPs split
        # over tp*ep (dp replicates the weights), the KV stream over tp
        # (head-sharded pools) — ignoring collectives, i.e. the ideal
        # the engine_mesh_guard measures shortfall against.
        wshard = max(tp * ep, 1)
        dec_flops = R * flops_per_tok / wshard
        dec_bytes = (
            weight_bytes / wshard
            + R * ctx * kv_row * 2 * kv_elem_bytes / max(tp, 1)
        )
        decode_rl = _roofline(dec_flops, dec_bytes, peak_ref, bw_ref)
        decode_rl["expected_tok_s"] = round(
            R / decode_rl["expected_step_s"], 1
        )
        # Prefill: same weight stream + K/V writes for R*prompt_len rows;
        # FLOPs from the causal-attention-aware count above.
        pre_bytes = (
            weight_bytes / wshard
            + R * prompt_len * kv_row * 2 * kv_elem_bytes / max(tp, 1)
        )
        prefill_rl = _roofline(
            prefill_flops / wshard, pre_bytes, peak_ref, bw_ref
        )
        prefill_rl["expected_tok_s"] = round(
            R * prompt_len / prefill_rl["expected_step_s"], 1
        )
        # Opt-in: lowering again is a SECOND full XLA compile of the
        # decode scan (the jit dispatch cache is separate from the AOT
        # path) — not worth default bench time for a reference-only
        # field.
        # Engine-level A/B: the full InferenceEngine loop in sync vs
        # overlapped stepping (CPU tiny-model only; the chip rows are
        # ROADMAP A1's).
        engine_bench = None
        attention_bench = None
        spec_bench = None
        if (
            not on_tpu
            and n_dev == 1
            and not os.environ.get("XLLM_BENCH_SKIP_ENGINE_AB")
        ):
            engine_bench = {}
            modes = (
                ("sync", "overlap") if engine_mode == "both"
                else (engine_mode,)
            )
            for m in modes:
                engine_bench[m] = _engine_bench(sync=(m == "sync"))
            # Mixed-vs-split attention A/B (--attention-mode, ISSUE 9):
            # same full-engine harness, overlapped stepping, toggling
            # ONLY the step builder (ragged mixed batch vs alternating
            # prefill/decode). "ragged" reuses the engine_bench overlap
            # row when present — identical config, no second run.
            attention_bench = {}
            amodes = (
                ("split", "ragged") if attention_mode == "both"
                else (attention_mode,)
            )
            for m in amodes:
                if m == "ragged" and "overlap" in engine_bench:
                    attention_bench[m] = engine_bench["overlap"]
                else:
                    attention_bench[m] = _engine_bench(
                        sync=False, mixed=(m == "ragged")
                    )
            # Combined-path A/B (--spec-mode, ISSUE 13): speculative
            # decoding through the composed pipeline (overlap + mixed
            # verify batch + device-resident accepted-token feedback)
            # vs the sync+split verify engine — engine_spec_guard
            # (exit 3) enforces composed >= 95% of sync+split on CPU;
            # the real win lands in the TPU window.
            spec_bench = {}
            smodes = (
                ("composed", "sync_split") if spec_mode == "both"
                else ("composed",) if spec_mode == "composed"
                else ("sync_split",)
            )
            for m in smodes:
                spec_bench[m] = _engine_bench(
                    sync=(m == "sync_split"),
                    mixed=(m == "composed"),
                    spec=3,
                )

        # The expert model's row (moe-shard-tiny through the grouped
        # ragged expert product, the one path there is; docs/MOE.md):
        # reported, not guarded. On TPU it is the only row of this file
        # that runs the grouped kernels.
        moe_bench = None
        if (
            n_dev == 1
            and not os.environ.get("XLLM_BENCH_SKIP_ENGINE_AB")
        ):
            moe_bench = {
                "grouped": _engine_bench(sync=False, model="moe-shard-tiny")
            }

        # Latency-hiding collectives A/B (--overlap, ISSUE 18): the
        # ring collective-matmul schedule vs the plain psum/einsum
        # combines, full-engine harness. On a pure-tp mesh (--mesh
        # 1,N,1 — CPU virtual devices work) the schedule actually
        # engages on the tp-sharded tiny model; on a single-device run
        # the rows still print (original einsum both sides) and
        # engine_overlap_collectives_guard abstains loudly — the
        # documented single-device abstention.
        overlap_bench = None
        if (
            not on_tpu
            and dp == 1 and ep == 1
            and not os.environ.get("XLLM_BENCH_SKIP_ENGINE_AB")
        ):
            overlap_bench = {}
            omodes = (
                ("on", "off") if overlap_mode == "both"
                else (overlap_mode,)
            )
            omodel = "llama3-shard-tiny" if tp > 1 else "llama3-tiny"
            for m in omodes:
                overlap_bench[m] = _engine_bench(
                    sync=False, model=omodel, overlap=m, tp=tp,
                )

        xla_cost = None
        if os.environ.get("XLLM_BENCH_XLA_COST"):
            try:
                xla_cost = _cost_analysis(
                    run.lower(
                        ex.k_cache, ex.v_cache, ex.params, *args
                    ).compile()
                )
            except Exception:
                xla_cost = None

        # Cold-vs-warm compile cache row (ISSUE 18): LAST section — it
        # re-points jax's persistent cache at a throwaway keyed dir
        # (deleted on exit), so nothing may compile after it in this
        # process.
        compile_cache_bench = None
        if (
            not on_tpu
            and n_dev == 1
            and not os.environ.get("XLLM_BENCH_SKIP_ENGINE_AB")
        ):
            compile_cache_bench = _compile_cache_bench()
        print(json.dumps({
            "metric": f"decode_throughput_{model}_bs{R}",
            "value": round(tok_per_s, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tok_per_s / baseline, 3),
            "backend": jax.default_backend(),
            "tpot_ms": round(1000.0 * dt / decode_steps, 3),
            "mfu": round(achieved_flops / peak, 4) if peak else None,
            "prefill_tok_s": round(prefill_tok_s, 1),
            "prefill_mfu": prefill_mfu,
            "attention_kernel": (
                "gather (forced-off)" if use_kernel is False
                else kernel_rep.get("decode", "unknown")
            ),
            "prefill_kernel": (
                "blockwise (forced-off)" if use_kernel is False
                else kernel_rep.get("prefill", "unknown")
            ),
            "mixed_kernel": kernel_rep.get("mixed"),
            "mq_kernel": kernel_rep.get("mq"),
            # Shard-aware row (--mesh, docs/SHARDING.md): the mesh this
            # engine ran on and how many per-shard kernel launches one
            # attention dispatch fans into (1 = single-device or the
            # XLLM_SHARDED_KERNELS=0 GSPMD escape) — MULTICHIP/BENCH
            # rounds compare across mesh shapes on these columns.
            "mesh": {"dp": dp, "tp": tp, "ep": ep},
            "kernel_shards": kernel_rep.get("shards", 1),
            "kv_cache_dtype": cfg.kv_cache_dtype,
            "weight_dtype": cfg.weight_dtype,
            # Analytic roofline expectations ("roofline_ref" names the
            # referenced chip when the run itself is not on TPU). Decode
            # must be HBM-bound: weights + KV stream once per step.
            "expected_mfu": decode_rl["expected_mfu"],
            "expected_hbm_gbps": decode_rl["expected_hbm_gbps"],
            "decode_roofline": decode_rl,
            "prefill_roofline": prefill_rl,
            "roofline_ref": roofline_ref,
            # Raw XLA compiled-module numbers, for reference only (scan
            # bodies are counted once — see comment above).
            "xla_cost_analysis": (
                {"flops": xla_cost[0], "bytes": xla_cost[1]}
                if xla_cost else None
            ),
            # Full-engine stepping-mode A/B (llama3-tiny, R=8): decode
            # tokens/s, host_gap_ms, and overlap depth per mode — the
            # overlapped (default) engine must not lose to the sync
            # escape hatch (engine_overlap_guard enforces it).
            "engine_bench": engine_bench,
            "engine_mode": engine_mode,
            # Mixed-vs-split attention A/B (--attention-mode): one ragged
            # dispatch per iteration vs the alternating split-step escape
            # hatch — engine_ragged_guard (exit 3) enforces ragged ≥ 95%
            # of split (docs/KERNELS.md).
            "attention_bench": attention_bench,
            "attention_mode": attention_mode,
            # Combined-path A/B (--spec-mode): speculative decode on the
            # composed overlap+mixed pipeline vs sync+split verify —
            # engine_spec_guard (exit 3) enforces the floor (ISSUE 13,
            # docs/ENGINE_PIPELINE.md).
            "spec_bench": spec_bench,
            "spec_mode": spec_mode,
            # The expert model's row through the grouped ragged expert
            # product (docs/MOE.md): reported, not guarded.
            "moe_bench": moe_bench,
            # Latency-hiding collectives A/B (--overlap): ring
            # collective-matmul combines vs plain psum on the
            # tp-sharded engine — engine_overlap_collectives_guard
            # (exit 3) floors the pair when the schedule actually
            # engaged and abstains loudly on a single-device mesh
            # (ISSUE 18, docs/SHARDING.md "Hiding the mesh").
            "overlap_bench": overlap_bench,
            "overlap_mode": overlap_mode,
            # Cold-vs-warm persistent compile cache prewarm (ISSUE 18):
            # compile_ms_cold pays every XLA compile, compile_ms_warm
            # reloads the keyed on-disk cache — the restarted-instance
            # path. engine_host_gap_guard rides the engine rows above.
            "compile_cache_bench": compile_cache_bench,
            # The MoE dispatch THIS bench's main model resolved (None
            # for dense models).
            "moe_kernel": kernel_rep.get("moe"),
            "moe_shards": kernel_rep.get("moe_shards"),
            # Methodology markers: median of N repeats, the per-repeat
            # spread, and the host's 1-min load average around the run —
            # a hot host shows up here instead of masquerading as a
            # regression (r3 weak #1).
            "repeats": repeats,
            "cpu_count": os.cpu_count(),
            "decode_dt_spread_ms": [round(1000 * d, 1) for d in dts],
            "loadavg_1m": round(os.getloadavg()[0], 1),
            "loadavg_1m_start": round(load_before[0], 1),
        }))
    finally:
        if use_kernel is False:
            if prev_prefill_env is None:
                os.environ.pop("XLLM_PREFILL_ATTENTION_KERNEL", None)
            else:
                os.environ["XLLM_PREFILL_ATTENTION_KERNEL"] = (
                    prev_prefill_env
                )


def _peak_flops(device) -> float | None:
    """Peak bf16 FLOP/s by device kind; None on CPU (MFU meaningless)."""
    kind = getattr(device, "device_kind", "").lower()
    table = {
        "v6": 918e12, "v5p": 459e12, "v5e": 197e12, "v5 lite": 197e12,
        "v5": 459e12, "v4": 275e12,
    }
    for key, peak in table.items():
        if key in kind:
            return peak
    return None


def _peak_hbm_bw(device) -> float | None:
    """Peak HBM bandwidth (bytes/s) by device kind; None on CPU."""
    kind = getattr(device, "device_kind", "").lower()
    table = {
        "v6": 1640e9, "v5p": 2765e9, "v5e": 819e9, "v5 lite": 819e9,
        "v5": 2765e9, "v4": 1228e9,
    }
    for key, bw in table.items():
        if key in kind:
            return bw
    return None


def _cost_analysis(compiled) -> "tuple[float, float] | None":
    """(flops, bytes_accessed) from a compiled executable's XLA cost
    analysis, or None when the backend doesn't report it."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    flops = float(cost.get("flops", 0.0))
    bts = float(cost.get("bytes accessed", 0.0))
    if flops <= 0 or bts <= 0:
        return None
    return flops, bts


def _roofline(flops: float, bts: float, peak_flops: float,
              peak_bw: float) -> dict:
    """Analytic roofline for one compiled step: expected step time is
    max(compute time, HBM time); expected_mfu / expected_hbm_gbps are
    what the step achieves AT that bound (VERDICT r4 #2 — a perf
    expectation that exists even when no chip is reachable)."""
    t_compute = flops / peak_flops
    t_hbm = bts / peak_bw
    t = max(t_compute, t_hbm)
    return {
        "flops": flops,
        "bytes": bts,
        "expected_step_s": t,
        "expected_mfu": round(flops / (t * peak_flops), 4),
        "expected_hbm_gbps": round(bts / t / 1e9, 1),
        "bound": "hbm" if t_hbm >= t_compute else "compute",
        "arithmetic_intensity": round(flops / bts, 2),
        "ridge_intensity": round(peak_flops / peak_bw, 2),
    }


if __name__ == "__main__":
    main()
