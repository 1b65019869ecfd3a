"""Engine step timeline (docs/OBSERVABILITY.md): phase spans + phase-second
counters in the engine loop, the engine's queue wait, the prefill work
counts, and the names the benchmark's readers look for. No test asserts on
a wall clock: the phase helper takes its clock as an argument and the
queue-wait test replaces the engine module's `time`."""

import importlib.util
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.obs import ENGINE_PHASES, EnginePhases
from xllm_service_tpu.obs import spans as obs_spans
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime import engine as engine_mod
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk(**kw):
    base = dict(
        model="llama3-tiny", dtype="float32", block_size=16, num_blocks=64,
        max_running_requests=4, max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
    )
    base.update(kw)
    cfg = EngineConfig(**base)
    return InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))


class Collector:
    def __init__(self, clock=None):
        self.tokens, self.done, self.first = [], threading.Event(), None
        self._clock = clock

    def __call__(self, out):
        n = sum(len(so.token_ids) for so in out.outputs)
        if n and self.first is None and self._clock is not None:
            self.first = self._clock()
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
        if out.finished:
            self.done.set()
        return True


def _requests(n, plen, max_new, clock=None, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        c = Collector(clock)
        out.append((EngineRequest(
            f"r{i}", [int(t) for t in rng.randint(0, 500, size=plen + i)],
            SamplingParams(temperature=0.0, max_new_tokens=max_new), c,
        ), c))
    return out


def _series(eng):
    """The engine registry as the benchmark's stack.parse_metrics reads it."""
    out = {}
    for line in eng.metrics.render().splitlines():
        if line and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            out[head] = float(val)
    return out


class TickClock:
    """Advances one unit per reading: every interval has a length and the
    test owns the time."""

    def __init__(self):
        self.t = 0

    def __call__(self):
        self.t += 1
        return float(self.t)


class AnnotationLog:
    """Stands in for jax.profiler.TraceAnnotation: records which of the
    program's annotations are open, and whether one ever opened inside
    another."""

    def __init__(self):
        self.open, self.nested, self.names = [], [], set()

    def __call__(self, name):
        log = self

        class Scope:
            def __enter__(self):
                if log.open:
                    log.nested.append((tuple(log.open), name))
                log.open.append(name)
                log.names.add(name)

            def __exit__(self, *exc):
                assert log.open.pop() == name

        return Scope()


FLAVOURS = {
    "mixed": dict(),
    "overlap": dict(enable_mixed_step=False),
    "speculative": dict(speculative_tokens=2),
    "sync": dict(sync_engine=True),
}


@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_phases_are_exclusive_and_cover_the_loop(flavour, monkeypatch):
    eng = _mk(**FLAVOURS[flavour])
    if flavour == "overlap":
        assert not eng.mixed_step_enabled
    clock, log, seconds = TickClock(), AnnotationLog(), []
    eng._phases = EnginePhases(
        lambda p, dt: seconds.append((p, dt)), clock=clock, annotate=log,
    )
    # the executor's leaves go through the same stand-in, so that a leaf
    # opened under an engine annotation would show as nested
    monkeypatch.setattr(obs_spans, "annotation", log)
    reqs = _requests(3, plen=40, max_new=6)
    eng.start()
    try:
        for req, _ in reqs:
            eng.add_request(req)
        for _, c in reqs:
            assert c.done.wait(120)
        for _ in range(200):  # let the loop reach its idle wait
            if any(p == "idle" for p, _ in seconds):
                break
            eng.wake()
            threading.Event().wait(0.01)
    finally:
        eng.stop()
    assert not eng._thread.is_alive()
    # contiguous: the intervals of all phases, idle included, add up to the
    # loop's whole time on the injected clock (first reading to last)
    assert eng._phases._stack == [] and not log.open
    first_reading = 1.0
    assert sum(dt for _, dt in seconds) == clock.t - first_reading
    assert all(dt > 0 for _, dt in seconds)
    assert seconds[0][0] == "housekeeping"  # the loop's own scope
    # exclusive: no annotation of the program ever opened inside another
    assert log.nested == []
    by_phase = {p: 0.0 for p in ENGINE_PHASES}
    for p, dt in seconds:
        by_phase[p] += dt
    assert all(v > 0 for v in by_phase.values()), by_phase
    assert {"xllm.engine." + p for p in ENGINE_PHASES} <= log.names
    assert {
        "xllm.executor.host_inputs", "xllm.executor.launch",
    } <= log.names
    assert "xllm.executor.step_keys" not in log.names  # keys are in-graph
    assert all(len(c.tokens) == 6 for _, c in reqs)


def test_phase_outside_the_vocabulary_is_refused():
    phases = EnginePhases(lambda p, dt: None, clock=TickClock(), annotate=None)
    with pytest.raises(ValueError):
        phases.phase("sampling")


class FakeTime:
    """The engine module's `time`: monotonic() moves 1 ms per reading."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        self.now += 0.001
        return self.now

    def sleep(self, s):
        self.now += s


@pytest.mark.parametrize("mixed", [True, False], ids=["mixed", "split"])
def test_queue_wait_plus_ttft_is_add_to_first_token(mixed, monkeypatch):
    fake = FakeTime()
    monkeypatch.setattr(engine_mod, "time", fake)
    # a 32-token chunk budget: the four 40..43-token prompts queue behind
    # one another, two chunks each
    eng = _mk(enable_mixed_step=mixed, max_prefill_tokens=32)
    spans = []
    eng.span_hook = lambda rid, stage, **f: spans.append((rid, stage, f))
    reqs = _requests(4, plen=40, max_new=3, clock=lambda: fake.now)
    t_add = {}
    for req, _ in reqs:
        eng.add_request(req)
        t_add[req.request_id] = req.queued_at
    step_ms = 0.0
    for _ in range(400):
        if not eng.has_work():
            break
        t0 = fake.now
        eng.step()
        step_ms = max(step_ms, (fake.now - t0) * 1e3)
    assert all(c.done.is_set() for _, c in reqs)
    m = _series(eng)
    assert m["xllm_engine_queue_wait_ms_count"] == 4  # once per request
    assert m["xllm_engine_ttft_ms_count"] == 4
    tap_ms = sum(
        (c.first - t_add[req.request_id]) * 1e3 for req, c in reqs
    )
    inside = m["xllm_engine_queue_wait_ms_sum"] + m["xllm_engine_ttft_ms_sum"]
    # the two intervals meet at the first chunk's dispatch; what is left is
    # the way from the drain's clock reading to the callback, inside a step
    assert 0 <= tap_ms - inside <= 4 * step_ms
    assert m["xllm_engine_queue_wait_ms_sum"] > 0
    assert all(req.queued_at == 0.0 for req, _ in reqs)
    if mixed:
        chunks = [f for _, stage, f in spans if stage == "prefill_chunk"]
        assert len(chunks) >= 8  # two or more chunks a request
        queued = [f["queued_ms"] for f in chunks if "queued_ms" in f]
        assert len(queued) == 4  # each request's first chunk carries it
        assert sum(queued) == pytest.approx(
            m["xllm_engine_queue_wait_ms_sum"], abs=0.01
        )


@pytest.mark.parametrize("mixed", [True, False], ids=["mixed", "split"])
def test_prefill_counts_are_prompt_tokens_less_cached_ones(mixed):
    eng = _mk(enable_mixed_step=mixed, max_prefill_tokens=32)
    rng = np.random.RandomState(9)
    prompt = [int(t) for t in rng.randint(0, 500, size=70)]
    total = 0
    for i in range(2):  # the second time, the first four blocks are cached
        c = Collector()
        eng.add_request(EngineRequest(
            f"p{i}", list(prompt),
            SamplingParams(temperature=0.0, max_new_tokens=2), c,
        ))
        total += len(prompt)
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
        assert c.done.is_set()
    assert eng.prefix_cached_tokens == 64
    m = _series(eng)
    assert m["xllm_engine_prefill_tokens_total"] == total - 64
    # 70 tokens in chunks of 32: three; then the 6 left after the cached 64
    assert m["xllm_engine_prefill_chunks_total"] == 3 + 1


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
def test_sample_rows_count_slots_live_and_drawn_rows(mixed):
    """xllm_engine_sample_rows_total{kind}: what the sampler had to do,
    booked once a dispatched step from the arrays the pack is built
    from. Three requests of four slots, one of them greedy."""
    eng = _mk(enable_mixed_step=mixed)
    reqs = _requests(3, 20, 6)
    for i, (req, _) in enumerate(reqs):
        if i:
            req.sampling = SamplingParams(
                temperature=0.8, seed=i, max_new_tokens=6
            )
        eng.add_request(req)
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()
    assert all(c.done.is_set() for _, c in reqs)
    m = _series(eng)
    rows = {
        k: m[f'xllm_engine_sample_rows_total{{kind="{k}"}}']
        for k in ("slots", "live", "drawn")
    }
    steps = m["xllm_engine_decode_steps_total"]
    assert rows["slots"] == 4 * steps
    assert rows["live"] == m["xllm_engine_decode_batch_size_sum"]
    # every decode row of the two drawing requests, none of the greedy one
    assert 0 < rows["drawn"] < rows["live"] < rows["slots"]
    greedy_rows = len(reqs[0][1].tokens) - 1  # its first token is prefill's
    assert rows["live"] - rows["drawn"] == greedy_rows


def test_profiler_records_the_annotations_as_leaves(tmp_path):
    """A real profiler session on the CPU backend: the host plane holds the
    engine's and the executor's annotations, on the profiler's clock, and
    none of them encloses another."""
    import glob

    import jax

    eng = _mk()
    reqs = _requests(2, plen=40, max_new=8)
    eng.start()
    try:
        jax.profiler.start_trace(str(tmp_path))
        for req, _ in reqs:
            eng.add_request(req)
        for _, c in reqs:
            assert c.done.wait(120)
        jax.profiler.stop_trace()
    finally:
        eng.stop()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(files[0])
    mine = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("xllm."):
                    mine.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    names = {n for _, _, n in mine}
    assert {"xllm.engine.dispatch", "xllm.engine.device_wait",
            "xllm.engine.emit", "xllm.engine.schedule"} <= names
    assert {"xllm.executor.host_inputs", "xllm.executor.launch"} <= names
    assert "xllm.executor.step_keys" not in names  # keys are in-graph
    mine.sort()
    for (_, end, a), (start, _, b) in zip(mine, mine[1:]):
        assert start >= end, f"{a} encloses or overlaps {b}"


def test_importing_obs_imports_no_jax():
    code = (
        "import sys; import xllm_service_tpu.obs as obs; "
        "assert 'jax' not in sys.modules, 'obs imported jax'; "
        "assert not hasattr(obs.spans, 'write_chrome_trace'); "
        "print(len(obs.ENGINE_PHASES))"
    )
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert 1 <= int(p.stdout.strip()) <= 8


def test_names_the_benchmark_readers_look_for():
    """benchmarks/harness/readers.py finds the step programs by their
    jitted functions' names and paged_attention_roofline.batch the kernel
    by its op name; neither file may be edited by the PR that breaks it."""
    import jax
    import jax.numpy as jnp

    def load(*parts):
        path = os.path.join(REPO, "benchmarks", *parts)
        spec = importlib.util.spec_from_file_location("bench_" + parts[-1][:-3].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.path.insert(0, REPO)
        try:
            spec.loader.exec_module(mod)
        finally:
            sys.path.remove(REPO)
        return mod

    readers = load("harness", "readers.py")
    have = {ModelExecutor._decode_impl.__name__, ModelExecutor._mixed_impl.__name__}
    assert set(readers.DECODE_PROGRAMS) | set(readers.STEP_PROGRAMS) <= have
    roofline = load("layer_metrics", "paged_attention_roofline.batch.py")
    from xllm_service_tpu.ops.pallas.paged_attention import (
        paged_attention_kernel,
    )

    R, Hq, Hkv, D, BS, NB = 2, 4, 2, 128, 16, 4
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, bt, sl: paged_attention_kernel(
            q, k, v, bt, sl, D ** -0.5, interpret=True
        )
    )(
        jnp.zeros((R, Hq, D)), jnp.zeros((NB, Hkv, BS, D)),
        jnp.zeros((NB, Hkv, BS, D)), jnp.zeros((R, 2), jnp.int32),
        jnp.ones((R,), jnp.int32),
    )
    assert "name=paged_attention_kernel" in str(jaxpr)
    assert ("%" + "paged_attention_kernel").startswith(roofline.KERNEL)


def test_latency_metrics_do_not_mutate_and_survive_appends():
    eng = _mk()
    now = time.monotonic()
    eng._tbt_window.extend([(now - 100.0, 5000.0), (now - 1.0, 70.0)])
    eng._ttft_window.append((now - 2.0, 300.0))
    got = eng.get_latency_metrics(window_s=30.0)
    assert (got.recent_max_ttft, got.recent_max_tbt) == (300, 70)
    assert len(eng._tbt_window) == 2  # the reader pops nothing
    # the engine thread trims as it appends
    eng._window_append(eng._tbt_window, now, 80.0)
    assert [v for _, v in eng._tbt_window] == [70.0, 80.0]
    # a reader beside an appending engine thread: the old Python-level
    # iteration died of "deque mutated during iteration" within a few calls
    stop, errors = threading.Event(), []

    def engine_thread():
        t = now
        while not stop.is_set():
            t += 0.001
            eng._window_append(eng._tbt_window, t, 1.0)

    th = threading.Thread(target=engine_thread, daemon=True)
    th.start()
    try:
        for _ in range(3000):
            try:
                eng.get_latency_metrics()
            except RuntimeError as e:  # pragma: no cover — the old fault
                errors.append(e)
    finally:
        stop.set()
        th.join(timeout=10)
    assert errors == []


def test_profile_curves_stop_growing(monkeypatch):
    monkeypatch.setattr(engine_mod, "PROFILE_SAMPLES", 5)
    eng = _mk()
    for req, _ in _requests(4, plen=20, max_new=12):
        eng.add_request(req)
    for _ in range(400):
        if not eng.has_work():
            break
        eng.step()
    ttft, tpot = eng.profiling_data()
    assert len(ttft) == 4 and len(tpot) == 5  # 5 of >= 12 steps kept
