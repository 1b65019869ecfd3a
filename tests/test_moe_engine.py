"""Expert-parallel MoE serving: the EP differential suite (docs/MOE.md,
ROADMAP item 5 / ISSUE 15).

The contract under test mirrors the sharded-engine tier's: an ep-sharded
MoE engine is an IMPLEMENTATION DETAIL — token streams must be
byte-identical to the 1-device engine on the same weights across every
serving path the hot loop composes (greedy, seeded sampling, penalties,
staggered admission through the mixed step, and the composed
speculative pipeline). Runs on the conftest virtual 8-device CPU
platform; ep ∈ {2, 4} divide moe-shard-tiny's 8 experts.

The grouped Pallas product is asserted via kernel_report() — `moe` ==
"grouped" and `moe_shards` == ep under the XLLM_MOE_INTERPRET hook —
not assumed: the interpret-mode kernels actually launch once per ep
shard inside the engine's fused steps and must still match the 1-device
stream bit for bit.

Ops-level: kernel-vs-reference fuzz over ragged group sizes (balanced,
skewed, empty experts, every token on one expert, a held span that
starts past expert 0), grouped-vs-dense semantic parity, the share test
(the four spans of experts add up to the whole), and which product the
platform resolves to.
"""

import threading

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

MODEL = "moe-shard-tiny"
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


@pytest.fixture(autouse=True)
def _clear_moe_thread_state():
    """Engine runs register the executor's ep context on this thread (a
    trace-time thread-local); clear it so ops-level tests never run under
    a stale mesh."""
    from xllm_service_tpu.ops import moe as moe_ops

    yield
    moe_ops.set_ep_context(None)


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
    (its chunks ride the fused mixed dispatch) — prefill, decode, and
    mixed batches all cross the MoE block in one run."""
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


def _run_workload(**cfg_kw):
    cfg = _cfg(**cfg_kw)
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    cols = _mixed_workload(eng)
    _drive(eng)
    assert all(c.done.is_set() for c in cols.values())
    return {k: c.tokens for k, c in cols.items()}, eng


# ------------------------------------------------ engine-stream parity


@pytest.mark.parametrize("ep", [2, 4])
def test_engine_ep_parity_grouped_kernel(cpu_devices, monkeypatch, ep):
    """ep ∈ {2, 4} with the interpret-mode grouped Pallas dispatch
    driving every MoE block: kernel_report must RESOLVE to the grouped
    per-shard dispatch (moe_shards == ep — asserted, not assumed) and
    the streams must match the 1-device grouped run bit for bit."""
    monkeypatch.setenv("XLLM_MOE_INTERPRET", "1")
    ref, ref_eng = _run_workload()
    assert ref_eng.executor.kernel_report()["moe"] == "grouped"
    assert ref_eng.executor.kernel_report()["moe_shards"] == 1
    streams, eng = _run_workload(ep_size=ep)
    rep = eng.executor.kernel_report()
    assert rep["moe"] == "grouped"
    assert rep["moe_shards"] == ep
    assert eng.executor.mesh.shape.get("ep") == ep
    assert eng.mixed_steps > 0  # MoE rode the fused hot loop
    assert streams == ref


def test_engine_ep_parity_with_ragged_interpret(cpu_devices, monkeypatch):
    """The full composed fast path: the interpret-mode pair of attention
    kernels (decode + flash, the cells' route, through the `_interpret`
    seam) AND interpret-mode grouped MoE dispatch in the same fused mixed
    step, ep=2 ≡ 1-device byte for byte."""
    from xllm_service_tpu.ops import attention

    monkeypatch.setenv("XLLM_MOE_INTERPRET", "1")
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    ref, ref_eng = _run_workload()
    assert ref_eng.executor.kernel_report()["mixed"] == "paged+flash"
    streams, eng = _run_workload(ep_size=2)
    rep = eng.executor.kernel_report()
    assert rep["mixed"] == "paged+flash" and rep["moe"] == "grouped"
    assert rep["moe_shards"] == 2
    assert streams == ref


def test_spec_ep_parity(cpu_devices, monkeypatch):
    """Speculative decoding (the composed overlap+mixed pipeline) with
    the grouped dispatch on an ep=2 mesh: accept-heavy and reject-heavy
    workloads emit the 1-device streams byte-identically, and the
    engine actually ran the spec pipeline."""
    monkeypatch.setenv("XLLM_MOE_INTERPRET", "1")
    out = {}
    for ep in (1, 2):
        cfg = _cfg(ep_size=ep, speculative_tokens=3)
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
        out[ep] = {k: c.tokens for k, c in cols.items()}
    assert out[2] == out[1]


def test_ep_escape_hatch(cpu_devices, monkeypatch):
    """XLLM_SHARDED_KERNELS=0 drops the per-shard launch back to the
    grouped oracle under plain GSPMD (moe_shards resolves to 1) and the
    streams still match — the hatch changes the lowering, never the
    numbers."""
    ref, ref_eng = _run_workload()  # grouped-ref off-TPU
    assert ref_eng.executor.kernel_report()["moe"] == "grouped-ref"
    monkeypatch.setenv("XLLM_SHARDED_KERNELS", "0")
    streams, eng = _run_workload(ep_size=2)
    assert eng.executor.kernel_report()["moe_shards"] == 1
    assert streams == ref


def test_moe_stats_and_load_signal(cpu_devices, monkeypatch):
    """The obs tier saw the dispatch: expert-load counts accumulate,
    the engine registry renders the xllm_engine_moe_* family, and the
    hot-expert share rides LoadMetrics for the master's routing."""
    monkeypatch.setenv("XLLM_MOE_INTERPRET", "1")
    _, eng = _run_workload()
    stats = eng.executor.moe_stats(drain=True)
    assert stats["assignments"] > 0
    assert stats["dropped"] == 0  # no capacity, so nothing to drop
    assert int(stats["expert_counts"].sum()) == stats["assignments"]
    # every expert is held here: no pair is another holder's
    assert stats["held"] == stats["assignments"] and stats["absent"] == 0
    assert 1.0 / stats["experts"] <= stats["hot_expert_frac"] <= 1.0
    text = eng.metrics.render()
    for name in (
        "xllm_engine_moe_assignments_total",
        'xllm_engine_moe_pairs_total{where="held"}',
        'xllm_engine_moe_pairs_total{where="absent"}',
        "xllm_engine_moe_dropped_total",
        "xllm_engine_moe_hot_expert_frac",
        "xllm_engine_moe_expert_load",
        "xllm_engine_moe_pairs_per_expert_count",
        "xllm_engine_cache_row_bytes",
    ):
        assert name in text, name
    # the histogram saw one observation a held expert and drained step
    from benchmarks.harness.stack import parse_metrics

    m = parse_metrics(text)
    assert m["xllm_engine_moe_pairs_per_expert_count"] > 0
    assert m["xllm_engine_moe_pairs_per_expert_count"] % stats["experts"] == 0
    # ... of its pairs a LAYER (executor.book_moe over cfg.expert_layers)
    assert eng.executor.cfg.expert_layers == eng.executor.cfg.num_layers
    assert m["xllm_engine_moe_pairs_per_expert_sum"] == pytest.approx(
        stats["held"] / eng.executor.cfg.expert_layers
    )
    # one latent-free GQA row a token: 2 caches x layers x heads x dim x 4
    cfg = eng.executor.cfg
    assert m["xllm_engine_cache_row_bytes"] == (
        2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 4
    )
    lm = eng.get_load_metrics()
    assert lm.moe_hot_expert_frac == pytest.approx(
        stats["hot_expert_frac"]
    )
    # The signal survives the heartbeat wire format (tolerant decode).
    from xllm_service_tpu.common.types import LoadMetrics

    rt = LoadMetrics.from_json(lm.to_json())
    assert rt.moe_hot_expert_frac == pytest.approx(lm.moe_hot_expert_frac)
    assert LoadMetrics.from_json(
        {"waiting_requests_num": 0, "gpu_cache_usage_perc": 0.0}
    ).moe_hot_expert_frac == 0.0
    # ...and survives the master's InstanceMgr snapshot — its policy
    # view used to rebuild LoadMetrics positionally, silently zeroing
    # fields added later (caught driving the full master/instance stack:
    # the heartbeat carried the signal, the routing view dropped it).
    from xllm_service_tpu.cluster.instance_mgr import InstanceMgr
    from xllm_service_tpu.common.types import InstanceMetaInfo, InstanceType
    from xllm_service_tpu.coordination import MemoryStore

    store = MemoryStore()
    mgr = InstanceMgr(store, is_master=lambda: True)
    try:
        mgr._register(InstanceMetaInfo(
            name="moe0", rpc_address="moe0:9000",
            http_address="moe0:8000", type=InstanceType.MIX,
        ))
        mgr.record_load_metrics_update(
            "moe0", LoadMetrics(1, 0.2, moe_hot_expert_frac=0.4)
        )
        snap = mgr.get_load_metrics()["moe0"]
        assert snap.moe_hot_expert_frac == pytest.approx(0.4)
    finally:
        mgr.close()
        store.close()


def test_no_pair_dropped_with_every_token_on_one_expert(cpu_devices):
    """The worst imbalance there is: every token sends all its pairs to
    the same K experts (one group as large as the step, the others
    empty). The grouped product has no capacity, so it equals the dense
    combine, through the reference and through the kernels."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(23)
    T, K, X, E, F = 40, 2, 8, 128, 128
    x, _, w, wg, wu, wd = _rand_problem(rng, T, K, X, E, F)
    topi = jnp.asarray(np.tile(np.array([[5, 2]], np.int32), (T, 1)))
    gate = jnp.einsum("te,xef->txf", x, wg)
    up = jnp.einsum("te,xef->txf", x, wu)
    eo = jnp.einsum("txf,xfe->txe", jax.nn.silu(gate) * up, wd)
    dense = w[:, 0, None] * eo[:, 5] + w[:, 1, None] * eo[:, 2]
    for use_kernel in (False, True):
        y = moe_ops.grouped_moe(
            x, topi, w, wg, wu, wd, use_kernel=use_kernel,
            interpret=use_kernel,
        )
        assert float(jnp.max(jnp.abs(dense - y))) < 1e-5, use_kernel


# ------------------------------------------ which product, which share


def test_moe_dispatch_resolution(cpu_devices, monkeypatch):
    """What the expert product resolves to off-TPU: the reference
    (plain XLA) by default, the kernels under the interpret
    hook where the widths are lane multiples. There is no dense path to
    resolve to and no switch that picks one."""
    from xllm_service_tpu.ops import moe as moe_ops

    E, F = 128, 256
    monkeypatch.delenv("XLLM_MOE_INTERPRET", raising=False)
    assert moe_ops.resolved_moe_dispatch(E, F) == "grouped-ref"
    monkeypatch.setenv("XLLM_MOE_INTERPRET", "1")
    assert moe_ops.resolved_moe_dispatch(E, F) == "grouped"
    # Ineligible geometry (E not a lane multiple) declines the kernel.
    assert moe_ops.resolved_moe_dispatch(96, 64) == "grouped-ref"
    assert not hasattr(moe_ops, "grouped_moe_enabled")
    assert not hasattr(moe_ops, "moe_capacity")


def test_held_spans_add_up_to_the_whole(cpu_devices):
    """The share test at the op: four holders of two experts each, the
    same router's choice; the parts they compute add up to what one
    holder of all eight computes, and a holder's part of a token that
    chose none of its experts is exactly 0."""
    import jax.numpy as jnp

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(29)
    T, K, X, E, F = 18, 3, 8, 128, 128
    x, topi, w, wg, wu, wd = _rand_problem(rng, T, K, X, E, F)
    whole = moe_ops.grouped_moe(x, topi, w, wg, wu, wd, use_kernel=False)
    for use_kernel in (False, True):
        parts = [
            moe_ops.grouped_moe(
                x, topi, w, wg[lo:lo + 2], wu[lo:lo + 2], wd[lo:lo + 2],
                first=lo, num_experts=X, use_kernel=use_kernel,
                interpret=use_kernel,
            )
            for lo in (0, 2, 4, 6)
        ]
        assert float(jnp.max(jnp.abs(sum(parts) - whole))) < 1e-5
        none_here = ~np.isin(np.asarray(topi), (4, 5)).any(axis=1)
        assert none_here.any()
        assert bool(jnp.all(parts[2][none_here] == 0))


# ------------------------------------------- kernel-vs-oracle fuzz


def _rand_problem(rng, T, K, X, E, F, experts=None):
    import jax.numpy as jnp

    x = jnp.asarray(rng.randn(T, E) * 0.5, jnp.float32)
    wg = jnp.asarray(rng.randn(X, E, F) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.randn(X, E, F) * 0.05, jnp.float32)
    wd = jnp.asarray(rng.randn(X, F, E) * 0.05, jnp.float32)
    pool = experts if experts is not None else list(range(X))
    topi = np.stack([
        rng.permutation(pool)[:K] for _ in range(T)
    ]).astype(np.int32)
    w = jnp.asarray(rng.rand(T, K), jnp.float32)
    return x, jnp.asarray(topi), w, wg, wu, wd


def test_moe_kernel_vs_oracle_fuzz(cpu_devices):
    """Interpret-mode kernels vs the plain-XLA reference over fuzzed
    ragged group shapes: balanced, skewed (hot experts), EMPTY experts (a
    restricted routing pool), more pairs than one row tile, and a held
    span that starts past expert 0: every case must agree to f32
    tolerance."""
    import jax.numpy as jnp

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(7)
    cases = [
        dict(T=16, K=2, X=8, E=128, F=128),
        dict(T=9, K=2, X=4, E=128, F=256),
        # Empty experts: routing restricted to 2 of 8 groups.
        dict(T=12, K=2, X=8, E=128, F=128, experts=[1, 6]),
        # 300 pairs: three row tiles, spans that cross them.
        dict(T=100, K=3, X=4, E=128, F=128),
        dict(T=5, K=1, X=8, E=256, F=128, experts=[0, 3]),
        # Experts 3-5 of 8 held: the rest of the pairs are not computed.
        dict(T=33, K=2, X=8, E=128, F=128, held=(3, 3)),
    ]
    for case in cases:
        experts = case.pop("experts", None)
        lo, n = case.pop("held", (0, case["X"]))
        x, topi, w, wg, wu, wd = _rand_problem(
            rng, experts=experts, **case
        )
        kw = dict(first=lo, num_experts=case["X"])
        held = (wg[lo:lo + n], wu[lo:lo + n], wd[lo:lo + n])
        y_ref = moe_ops.grouped_moe(x, topi, w, *held, use_kernel=False, **kw)
        y_k = moe_ops.grouped_moe(
            x, topi, w, *held, use_kernel=True, interpret=True, **kw
        )
        err = float(jnp.max(jnp.abs(y_ref - y_k)))
        assert err < 1e-5, (case, err)


def test_row_mask_excludes_padding(cpu_devices):
    """Dead rows (padding lanes / inactive slots) under row_mask: their
    outputs are exactly 0, they make no pair (the recorded counts cover
    the live rows only), and the live rows' values are what they are
    without the dead ones."""
    import jax.numpy as jnp

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(17)
    T, K, X, E, F = 12, 2, 4, 128, 128
    x, topi, w, wg, wu, wd = _rand_problem(rng, T, K, X, E, F)
    mask = np.zeros((T,), bool)
    mask[: T // 2] = True  # rows 6..11 are padding
    with moe_ops.layer_stats() as stats:
        y = moe_ops.grouped_moe(
            x, topi, w, wg, wu, wd, use_kernel=False,
            row_mask=jnp.asarray(mask),
        )
    counts = np.asarray(stats.total())  # [2X]: pairs, then touched
    assert counts.shape == (2 * X,) and int(counts[:X].sum()) == (T // 2) * K
    np.testing.assert_array_equal(
        counts[:X], np.bincount(np.asarray(topi)[: T // 2].ravel(), minlength=X)
    )
    np.testing.assert_array_equal(counts[X:], counts[:X] > 0)
    assert bool(jnp.all(y[T // 2:] == 0))
    # outside a layer scope nothing is recorded (no tracer can leak)
    y_full = moe_ops.grouped_moe(x, topi, w, wg, wu, wd, use_kernel=False)
    assert moe_ops.layer_stats().total() is None
    assert float(jnp.max(jnp.abs(y[: T // 2] - y_full[: T // 2]))) < 1e-6


def test_grouped_matches_dense_at_lossless_capacity(cpu_devices):
    """Semantic anchor: the grouped product computes the dense
    all-experts combine (same experts, same weights) to f32
    accumulation noise."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(11)
    T, K, X, E, F = 14, 2, 8, 128, 128
    x, topi, w, wg, wu, wd = _rand_problem(rng, T, K, X, E, F)
    y = moe_ops.grouped_moe(x, topi, w, wg, wu, wd, use_kernel=False)
    comb = jnp.zeros((T, X), jnp.float32).at[
        jnp.arange(T)[:, None], topi
    ].set(w)
    gate = jnp.einsum("te,xef->txf", x, wg)
    up = jnp.einsum("te,xef->txf", x, wu)
    eo = jnp.einsum("txf,xfe->txe", jax.nn.silu(gate) * up, wd)
    dense = jnp.einsum("txe,tx->te", eo, comb)
    assert float(jnp.max(jnp.abs(dense - y))) < 1e-5


def test_grouped_ep_bitwise_ops_level(cpu_devices):
    """Dispatcher-level proof (the sharded-kernel-dispatchers analog):
    the grouped dispatch under an ep ∈ {2, 4} shard context is
    BIT-identical to its unsharded run — kernel and oracle both."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from xllm_service_tpu.ops import moe as moe_ops

    rng = np.random.RandomState(13)
    x, topi, w, wg, wu, wd = _rand_problem(rng, 10, 2, 8, 128, 128)
    try:
        for use_kernel in (False, True):
            moe_ops.set_ep_context(None)
            y0 = moe_ops.grouped_moe(
                x, topi, w, wg, wu, wd, use_kernel=use_kernel,
                interpret=use_kernel,
            )
            for ep in (2, 4):
                mesh = Mesh(np.asarray(jax.devices()[:ep]), ("ep",))
                moe_ops.set_ep_context(mesh)
                y = moe_ops.grouped_moe(
                    x, topi, w, wg, wu, wd, use_kernel=use_kernel,
                    interpret=use_kernel,
                )
                assert bool(jnp.all(y0 == y)), (use_kernel, ep)
    finally:
        moe_ops.set_ep_context(None)
