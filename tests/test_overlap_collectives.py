"""Latency-hiding collectives + compile-cache differential suite
(ISSUE 18, docs/SHARDING.md "Hiding the mesh").

The contract under test: XLLM_OVERLAP_COLLECTIVES=1 decomposes the tp
o-proj / FFN down-projection combines into ring collective-matmul
schedules (ops/collective_matmul.py) and the ep expert combine into a
ring all-reduce — an IMPLEMENTATION DETAIL. Token streams must be
byte-identical to the hatch-off engine on every serving path: greedy,
seeded, penalized, staggered admission, guided decoding, and the
composed speculative pipeline, on tp ∈ {2, 4, 8} and ep ∈ {2} virtual
meshes (the conftest 8-device CPU platform).

The ep combine parity is EXACT by construction (per-slot expert values
are exact zeros off-shard, so the ring's += reproduces psum's bits);
the tp matmul parity is exact end-to-end because the engine's sampling
paths quantize through argmax/top-k before any f32 reduction-order
noise can reach a token boundary — asserted, not assumed, by the
stream equality below.

Also here: the persistent compile-cache contract (ISSUE 18 tentpole b)
— `prewarm_programs()` walks the full bucket/builder family, after
which a real workload must lower ZERO fresh programs (the engine's
compile_cache_{hits,misses} instruments count against exactly this
watermark), and a cold-vs-warm keyed on-disk cache changes timings,
never tokens.
"""

import threading

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

MODEL = "llama3-shard-tiny"
BS = 16


def _cfg(**kw) -> EngineConfig:
    base = dict(
        model=MODEL,
        dtype="float32",
        block_size=BS,
        num_blocks=48,
        max_running_requests=4,
        max_seq_len=128,
        prefill_buckets=[32, 64, 128],
    )
    base.update(kw)
    return EngineConfig(**base)


class C:
    def __init__(self):
        self.tokens = []
        self.done = threading.Event()

    def __call__(self, out):
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
        if out.finished:
            self.done.set()
        return True


def _drive(eng, max_steps=3000):
    for _ in range(max_steps):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()


def _mixed_workload(eng, tag=""):
    """Greedy + seeded + penalized requests with a staggered second wave
    (its chunks ride the fused mixed dispatch) — every step builder
    crosses the decomposed combines in one run."""
    rng = np.random.RandomState(3)
    cols = {}
    specs = [
        ("greedy", list(rng.randint(0, 500, size=11)),
         SamplingParams(temperature=0.0, max_new_tokens=8)),
        ("seeded", list(rng.randint(0, 500, size=14)),
         SamplingParams(temperature=0.9, top_k=20, seed=5,
                        max_new_tokens=8)),
        ("penal", list(rng.randint(0, 500, size=40)),
         SamplingParams(temperature=0.6, seed=11, max_new_tokens=7,
                        presence_penalty=0.4, frequency_penalty=0.2)),
    ]
    for name, prompt, sp in specs:
        c = C()
        cols[name] = c
        eng.add_request(EngineRequest(f"{tag}{name}", prompt, sp, c))
    for _ in range(2):  # deterministic mid-decode admission
        eng.step()
    c = C()
    cols["late"] = c
    eng.add_request(EngineRequest(
        f"{tag}late", list(rng.randint(0, 500, size=19)),
        SamplingParams(temperature=0.7, seed=2, max_new_tokens=6), c,
    ))
    return cols


def _run_workload(model_cfg=_cfg, **cfg_kw):
    cfg = model_cfg(**cfg_kw)
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    cols = _mixed_workload(eng)
    _drive(eng)
    assert all(c.done.is_set() for c in cols.values())
    return {k: c.tokens for k, c in cols.items()}, eng


@pytest.fixture(scope="module")
def ref_streams(cpu_devices):
    """Hatch-OFF tp=1 reference (the module's env never sets the hatch;
    overlap tests set it per-test via monkeypatch)."""
    streams, _ = _run_workload()
    return streams


# ------------------------------------------------ engine-stream parity


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_engine_tp_parity_overlap(cpu_devices, ref_streams, monkeypatch,
                                  tp):
    """Ring collective-matmul combines on a tp-sharded engine: greedy +
    seeded + penalized + staggered-admission streams match the hatch-off
    1-device engine byte for byte, and the ring schedule actually
    dispatched (asserted via the engine's collective-overlap counter,
    never assumed)."""
    monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
    streams, eng = _run_workload(tp_size=tp)
    assert streams == ref_streams
    assert eng.executor.overlap_collectives_active
    assert eng.collective_overlap_steps > 0


def test_engine_tp_overlap_off_matches_on(cpu_devices, monkeypatch):
    """Same mesh, hatch flipped: tp=2 overlap-ON ≡ tp=2 overlap-OFF —
    the schedule changes the lowering, never the numbers (and the OFF
    engine reports the collectives tier inactive)."""
    off, eng_off = _run_workload(tp_size=2)
    assert not eng_off.executor.overlap_collectives_active
    assert eng_off.collective_overlap_steps == 0
    monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
    on, eng_on = _run_workload(tp_size=2)
    assert eng_on.executor.overlap_collectives_active
    assert on == off


def test_engine_ep_parity_overlap(cpu_devices, monkeypatch):
    """The ep expert-combine ring all-reduce (ops/moe.py): ep=2 MoE
    streams under the hatch are bit-equal to the hatch-off ep=2 run —
    per-slot expert values are exact zeros off-shard, so the ring's +=
    reproduces psum's bits exactly (docs/SHARDING.md)."""
    from xllm_service_tpu.ops import moe as moe_ops

    def moe_cfg(**kw):
        base = dict(
            model="moe-shard-tiny", dtype="float32", block_size=BS,
            num_blocks=48, max_running_requests=4, max_seq_len=128,
            prefill_buckets=[32, 64, 128],
        )
        base.update(kw)
        return EngineConfig(**base)

    try:
        off, _ = _run_workload(model_cfg=moe_cfg, ep_size=2)
        monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
        on, eng = _run_workload(model_cfg=moe_cfg, ep_size=2)
        assert eng.executor.overlap_collectives_active
        assert eng.collective_overlap_steps > 0
        assert on == off
    finally:
        # Engine runs register trace-time thread-locals (the
        # test_moe_engine cleanup pattern).
        moe_ops.set_ep_context(None)


def _spec_overlap_streams():
    """One tp=2 speculative engine run: an accept-heavy greedy stream and
    a reject-heavy seeded one."""
    cfg = _cfg(tp_size=2, speculative_tokens=3)
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    cols = {}
    for name, prompt, sp in [
        ("accept", [7, 11, 13, 17] * 8,
         SamplingParams(temperature=0.0, max_new_tokens=12)),
        ("reject",
         list(np.random.RandomState(42).randint(0, 500, size=29)),
         SamplingParams(temperature=0.9, top_k=20, seed=7,
                        max_new_tokens=9)),
    ]:
        c = C()
        cols[name] = c
        eng.add_request(EngineRequest(name, list(prompt), sp, c))
    _drive(eng)
    assert all(c.done.is_set() for c in cols.values())
    assert eng.spec_pipeline_steps > 0
    return {k: [int(t) for t in c.tokens] for k, c in cols.items()}, eng


def _spec_overlap_child():
    """Body of test_spec_overlap_parity, run in a process of its own:
    prints one JSON line with both streams and whether the hatch took."""
    import json
    import os

    off, _ = _spec_overlap_streams()
    os.environ["XLLM_OVERLAP_COLLECTIVES"] = "1"
    on, eng = _spec_overlap_streams()
    print(json.dumps({
        "off": off, "on": on,
        "active": bool(eng.executor.overlap_collectives_active),
    }), flush=True)


def test_spec_overlap_parity():
    """Speculative decoding (the composed overlap+mixed pipeline) at
    tp=2: accept-heavy and reject-heavy streams under the hatch equal
    the hatch-off run byte for byte — the decomposed o-proj combine
    rides the verify/mixed-verify builders too.

    The engines run in a child process. On the virtual CPU mesh the
    hatch-on engine deadlocks in about one run in three (rendezvous.cc:
    one device waits in an all-reduce, the other in an all-to-all of the
    same run; 0 of 6 hatch-off runs did) and XLA aborts the interpreter
    60 s later, which would take the xdist worker down with it. A run
    that dies that way is tried once more; a second death, or any
    disagreement of the streams, fails the test."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.pop("XLLM_OVERLAP_COLLECTIVES", None)
    code = ("import conftest, test_overlap_collectives as t; "
            "t._spec_overlap_child()")
    deaths = []
    for _ in range(2):
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], cwd=here, env=env,
                capture_output=True, text=True, timeout=300,
            )
        except subprocess.TimeoutExpired:
            deaths.append("no end after 300 s")
            continue
        stuck = [ln for ln in proc.stderr.splitlines()
                 if "rendezvous.cc" in ln]
        if proc.returncode < 0 or stuck:
            deaths.append(f"rc {proc.returncode}: "
                          + (stuck[-1][:300] if stuck else "killed"))
            continue
        assert proc.returncode == 0, proc.stderr[-4000:]
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        assert got["active"]
        assert got["on"] == got["off"]
        return
    pytest.fail("the tp=2 speculative engine hung twice in a row: "
                + " | ".join(deaths), pytrace=False)


def test_guided_overlap_parity(cpu_devices, monkeypatch):
    """Guided (json) + unguided concurrent requests at tp=2: the
    in-graph mask gather composes with the ring-scheduled combines
    unchanged."""
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    def run():
        cfg = _cfg(tp_size=2)
        eng = InferenceEngine(
            cfg, executor=ModelExecutor(cfg, init_seed=0),
            eos_token_ids=(2,),
        )
        tok = ByteTokenizer()
        tb = tok.token_bytes_table(eng.executor.cfg.vocab_size)
        eng.set_guided_context(
            json_fsm.token_mask_table(tb, [2]), tb, eos_ids=[2]
        )
        cols = {}
        rng = np.random.RandomState(5)
        for i, guided in enumerate([None, "json", "json"]):
            c = C()
            cols[i] = c
            eng.add_request(EngineRequest(
                f"g{i}", list(rng.randint(1, 500, size=11 + 3 * i)),
                SamplingParams(
                    temperature=0.8 if i % 2 else 0.0, seed=i,
                    max_new_tokens=8,
                ),
                c, guided=guided,
            ))
        _drive(eng)
        assert all(c.done.is_set() for c in cols.values())
        return {k: c.tokens for k, c in cols.items()}

    off = run()
    monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
    on = run()
    assert on == off


# ------------------------------------------------- ops-level schedules


def test_ring_matmul_matches_einsum(cpu_devices, monkeypatch):
    """maybe_overlap_matmul under a declared tp mesh reproduces the
    replicated einsum to f32 reduction-order tolerance, and notes the
    traced site; ring_all_reduce reproduces psum BITWISE on the
    off-shard-zeros layout the ep combine feeds it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops import collective_matmul as cm

    monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
    rng = np.random.RandomState(0)
    for tp in (2, 4, 8):
        H, E = 32, 48
        x = jnp.asarray(rng.randn(6, H), jnp.float32)
        w = jnp.asarray(rng.randn(H, E), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
        try:
            att.set_shard_context(mesh)
            got = cm.maybe_overlap_matmul(x, w)
            assert got is not None
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(x @ w), rtol=2e-5, atol=2e-5
            )

            # Off-shard-zeros all-reduce: each element is non-zero on
            # exactly ONE shard (the ep expert-combine layout — every
            # slot's value lives on the shard holding its expert), so
            # the ring must equal psum bit for bit: adding exact zeros
            # commutes in every order.
            y = np.asarray(rng.randn(tp, 4, E), np.float32)
            Ec = E // tp
            for i in range(tp):
                keep = np.zeros((E,), bool)
                keep[i * Ec:(i + 1) * Ec] = True
                y[i, :, ~keep] = 0.0
            y = jnp.asarray(y)

            def ring(v):
                return cm.ring_all_reduce(v[0], "tp", tp)

            def psum(v):
                return jax.lax.psum(v[0], "tp")

            ring_out = shard_map(
                ring, mesh=mesh, in_specs=P("tp"), out_specs=P(),
                check_rep=False,
            )(y)
            psum_out = shard_map(
                psum, mesh=mesh, in_specs=P("tp"), out_specs=P(),
                check_rep=False,
            )(y)
            assert np.array_equal(np.asarray(ring_out), np.asarray(psum_out))
        finally:
            att.set_shard_context(None)


# -------------------------------------------------------- hatch routing


def test_hatch_parsing(monkeypatch):
    from xllm_service_tpu.ops import collective_matmul as cm

    for raw, want in [("", False), ("0", False), ("false", False),
                      ("off", False), ("1", True), ("ring", True)]:
        monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", raw)
        assert cm.overlap_collectives_enabled() is want
    monkeypatch.delenv("XLLM_OVERLAP_COLLECTIVES")
    assert cm.overlap_collectives_enabled() is False  # default OFF


def test_overlap_context_gated_by_hatch(cpu_devices, monkeypatch):
    """tp_overlap_context sees the declared mesh ONLY when the hatch is
    on — hatch-off traces must keep their original einsums with zero
    collective-matmul involvement."""
    import jax
    from jax.sharding import Mesh

    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops import collective_matmul as cm

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    try:
        att.set_shard_context(mesh)
        monkeypatch.delenv("XLLM_OVERLAP_COLLECTIVES", raising=False)
        assert cm.tp_overlap_context() is None
        monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
        assert cm.tp_overlap_context() is not None
    finally:
        att.set_shard_context(None)


def test_ineligible_geometry_falls_back(cpu_devices, monkeypatch):
    """maybe_overlap_matmul declines — returning None so the call site
    keeps its ORIGINAL einsum — when the hatch is off, no mesh is
    declared, or the tile math cannot divide (H % n, E % n, or a
    non-H trailing axis)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops import collective_matmul as cm

    x = jnp.zeros((4, 30), jnp.float32)   # 30 % 4 != 0
    w = jnp.zeros((30, 44), jnp.float32)
    ok_x = jnp.zeros((4, 32), jnp.float32)
    ok_w = jnp.zeros((32, 44), jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("tp",))
    try:
        # Hatch off: always None, even with a mesh declared.
        att.set_shard_context(mesh)
        monkeypatch.delenv("XLLM_OVERLAP_COLLECTIVES", raising=False)
        assert cm.maybe_overlap_matmul(ok_x, ok_w) is None
        monkeypatch.setenv("XLLM_OVERLAP_COLLECTIVES", "1")
        # Divisibility misses decline; the clean geometry engages.
        assert cm.maybe_overlap_matmul(x, w) is None          # H % n
        assert cm.maybe_overlap_matmul(
            ok_x, jnp.zeros((32, 42), jnp.float32)
        ) is None                                             # E % n
        assert cm.maybe_overlap_matmul(
            jnp.zeros((4, 44), jnp.float32), ok_w
        ) is None                                             # x≠H
        assert cm.maybe_overlap_matmul(ok_x, ok_w) is not None
        # No mesh declared: None regardless of the hatch.
        att.set_shard_context(None)
        assert cm.maybe_overlap_matmul(ok_x, ok_w) is None
    finally:
        att.set_shard_context(None)


# --------------------------------------- persistent compile cache tier


def _tiny_cfg(**kw):
    """Minimal bucket-program family: one prefill bucket, 4 context
    buckets max — prewarm in seconds, not minutes."""
    base = dict(
        model="llama3-tiny", dtype="float32", block_size=16,
        num_blocks=32, max_running_requests=4, max_seq_len=64,
        prefill_buckets=[32],
    )
    base.update(kw)
    return EngineConfig(**base)


def test_zero_fresh_lowerings_after_prewarm(cpu_devices):
    """THE tentpole-b acceptance: after prewarm_programs() walks the
    bucket/builder family (split + decode pipeline + mixed, both
    feedback variants + verify), a real workload spanning every builder
    lowers ZERO fresh programs — the engine's dispatch-cache instrument
    reads misses == 0 against the prewarm watermark, and the process
    builds no step program over the workload (obs/startup.py)."""
    from xllm_service_tpu.obs import STEP_PROGRAMS
    from xllm_service_tpu.obs.startup import TIMELINE

    def step_builds():
        return {
            k: v for k, v in TIMELINE.program_builds.items()
            if k[0] in STEP_PROGRAMS
        }

    cfg = _tiny_cfg()
    ex = ModelExecutor(cfg, init_seed=0)
    eng = InferenceEngine(cfg, executor=ex)
    before = sum(step_builds().values())  # the process's, not the engine's
    report = ex.prewarm_programs()
    assert report["programs"] == ex.prewarmed_lowerings
    assert ex.lowering_count() == ex.prewarmed_lowerings
    n0 = ex.lowering_count()
    built = step_builds()
    # (fewer builds than dispatch-cache entries: entries whose lowered
    # module is the same share one executable)
    assert 0 < sum(built.values()) - before <= report["programs"]

    cols = _mixed_workload(eng)
    _drive(eng)
    assert all(c.done.is_set() for c in cols.values())

    fresh = ex.lowering_count() - n0
    assert fresh == 0, (
        f"{fresh} fresh lowerings after prewarm — a bucket/builder "
        f"variant escaped the enumeration (report: {report})"
    )
    assert eng.compile_cache_misses() == 0
    assert step_builds() == built


def test_cold_vs_warm_cache_equivalence(cpu_devices, tmp_path,
                                        monkeypatch):
    """The on-disk cache changes timings, never tokens: a cold engine
    (fresh dir) and a warm engine (same dir, executables reloaded from
    disk) emit identical streams, and the dir actually holds compiled
    entries after the cold run."""
    from xllm_service_tpu.runtime import compile_cache as cc

    # Persist even sub-second compiles so the warm run exercises disk.
    monkeypatch.setenv("XLLM_COMPILE_CACHE_MIN_COMPILE_S", "0")
    # An outside placement wins over the config field; this test's is its own.
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    base = str(tmp_path / "jit-cache")
    kw = dict(compilation_cache_dir=base)

    def run():
        cfg = _tiny_cfg(**kw)
        eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
        cols = _mixed_workload(eng)
        _drive(eng)
        return {k: c.tokens for k, c in cols.items()}, eng

    cold, eng_cold = run()
    assert eng_cold.executor.compile_cache_dir == base
    assert cc.cache_entries(base) > 0
    warm, eng_warm = run()
    assert eng_warm.executor.compile_cache_dir == base
    assert warm == cold


def test_cache_disabled_fallback(cpu_devices, tmp_path, monkeypatch):
    """XLLM_COMPILE_CACHE=0 routes around the persistent cache
    entirely (no dir, no on-disk writes) and the engine still
    serves the identical streams — the hatch is an operational lever,
    never a numeric one."""
    off_dir = str(tmp_path / "never-used")
    monkeypatch.setenv("XLLM_COMPILE_CACHE", "0")

    cfg = _tiny_cfg(compilation_cache_dir=off_dir)
    ex = ModelExecutor(cfg, init_seed=0)
    assert ex.compile_cache_dir == ""
    eng = InferenceEngine(cfg, executor=ex)
    cols = _mixed_workload(eng)
    _drive(eng)
    streams = {k: c.tokens for k, c in cols.items()}

    monkeypatch.delenv("XLLM_COMPILE_CACHE")
    ref, _ = _run_workload(model_cfg=_tiny_cfg)
    assert streams == ref
    # The disabled run never materialized the dir.
    import os
    assert not os.path.isdir(off_dir) or not os.listdir(off_dir)


def test_prewarm_gates_on_start(cpu_devices, monkeypatch, tmp_path):
    """InferenceEngine.start(warmup) routes to the full-family prewarm
    only when a persistent cache dir is configured (the disk cache is
    what amortizes the enumeration across restarts) and falls back to
    the basic split warmup without one or under XLLM_COMPILE_CACHE=0."""
    from xllm_service_tpu.runtime import compile_cache as cc

    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    calls = []

    cfg = _tiny_cfg(
        warmup_on_start=True, compilation_cache_dir=str(tmp_path / "cc")
    )
    ex = ModelExecutor(cfg, init_seed=0)
    monkeypatch.setattr(
        ex, "prewarm_programs",
        lambda **kw: calls.append("prewarm") or {"programs": 0},
    )
    monkeypatch.setattr(ex, "warmup", lambda: calls.append("warmup"))
    eng = InferenceEngine(cfg, executor=ex)
    eng.start()
    eng.stop()
    assert calls == ["prewarm"]

    # No cache dir anywhere: the full walk would pay its whole compile
    # bill every start with no disk to replay from — legacy warmup.
    calls.clear()
    cfg_nodir = _tiny_cfg(warmup_on_start=True)
    ex2 = ModelExecutor(cfg_nodir, init_seed=0)
    monkeypatch.setattr(
        ex2, "prewarm_programs",
        lambda **kw: calls.append("prewarm") or {"programs": 0},
    )
    monkeypatch.setattr(ex2, "warmup", lambda: calls.append("warmup"))
    eng2 = InferenceEngine(cfg_nodir, executor=ex2)
    eng2.start()
    eng2.stop()
    assert calls == ["warmup"]

    calls.clear()
    monkeypatch.setenv("XLLM_COMPILE_CACHE", "0")
    eng3 = InferenceEngine(cfg, executor=ex)
    eng3.start()
    eng3.stop()
    assert calls == ["warmup"]
