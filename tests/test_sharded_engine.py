"""Sharded engine tier: the virtual-mesh differential suite
(docs/SHARDING.md, ROADMAP item 3).

The contract under test: a tp-sharded engine is an IMPLEMENTATION
DETAIL — token streams must be byte-identical to the 1-device engine on
the same weights (same init_seed) across every serving path: greedy,
seeded sampling, guided decoding, speculative decoding (the composed
pipeline), the mixed step, the streamed PD handoff, and the
prefix-fabric block fetch. Runs on the conftest virtual 8-device CPU
platform; tp ∈ {2, 4, 8} all divide llama3-shard-tiny's 8 KV heads.

The per-shard KERNEL dispatch (ops/attention.py shard_map wrapping) is
asserted via the decision itself (executor._attention_routes(),
kernel_report()) — `shards` == tp and `mixed` == "paged+flash" under the
interpret seam — not assumed: the interpret-mode Pallas decode and flash
kernels actually launch once per shard inside the engine's fused step
and must still match the 1-device stream bit for bit.

The KV wire planes are exercised per-shard: a tp holder's exports ride
`shard_wire.ShardedKV` through kv_frame_to_bytes/kv_frame_array (N
per-shard block sets, no cross-shard host gather) and land onto
consumers of DIFFERENT tp (1, 2, 4) via executor.migration_sharding.
"""

import threading

import numpy as np
import pytest

from xllm_service_tpu.api.protocol import kv_frame_array, kv_frame_split, kv_frame_to_bytes
from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.parallel import shard_wire
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
    (its chunks ride the fused mixed dispatch), plus one multi-chunk
    prompt — the step builder serves prefill, decode, and mixed batches
    in one run."""
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


@pytest.fixture(scope="module")
def ref_streams(cpu_devices):
    streams, _ = _run_workload()
    return streams


# ------------------------------------------------ engine-stream parity


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_engine_tp_parity(cpu_devices, ref_streams, tp):
    """Greedy + seeded + penalized + staggered-admission streams on a
    tp-sharded engine match the 1-device engine byte for byte."""
    streams, eng = _run_workload(tp_size=tp)
    assert streams == ref_streams
    assert eng.executor.mesh.shape.get("tp") == tp


def test_engine_tp_parity_ragged_interpret(cpu_devices, monkeypatch):
    """tp ∈ {2, 8} with the pair of kernels the cells run (the decode
    kernel and the flash kernel, interpret mode through the `_interpret`
    seam) serving every step: the decision the dispatchers take must
    RESOLVE to per-shard dispatch (shards == tp — asserted, not
    assumed), and the streams must match the 1-device interpret run bit
    for bit."""
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    ref, ref_eng = _run_workload()
    assert ref_eng.executor.kernel_report()["mixed"] == "paged+flash"
    for tp in (2, 8):
        streams, eng = _run_workload(tp_size=tp)
        (routes,) = eng.executor._attention_routes()
        assert routes.decode and routes.prefill and routes.interpret
        assert routes.shards == tp
        rep = eng.executor.kernel_report()
        assert rep["mixed"] == "paged+flash" and rep["shards"] == tp
        assert eng.mixed_steps > 0
        # The engine's resolved dispatch counter saw the pair's label —
        # the per-shard launches are what every mixed step dispatched.
        assert eng._kernel_names["mixed"] == "paged+flash"
        assert streams == ref


def test_sharded_kernels_escape_hatch(cpu_devices, monkeypatch):
    """XLLM_SHARDED_KERNELS=0 restores the pre-shard GSPMD path (shards
    resolves to 1) and the streams still match — the hatch changes the
    lowering, never the numbers."""
    ref, _ = _run_workload()
    monkeypatch.setenv("XLLM_SHARDED_KERNELS", "0")
    streams, eng = _run_workload(tp_size=2)
    assert eng.executor.kernel_report()["shards"] == 1
    assert streams == ref


def test_guided_tp_parity(cpu_devices):
    """Guided (json) + unguided concurrent requests: the in-graph mask
    gather rides the sharded (V-sharded logits) step unchanged."""
    from xllm_service_tpu.guided import json_fsm
    from xllm_service_tpu.tokenizer import ByteTokenizer

    out = {}
    for tp in (1, 2):
        cfg = _cfg(tp_size=tp)
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
        out[tp] = {k: c.tokens for k, c in cols.items()}
    assert out[2] == out[1]


def test_spec_tp_parity(cpu_devices):
    """Speculative decoding (the composed overlap+mixed pipeline) on a
    tp=2 mesh: accept-heavy and reject-heavy workloads emit the
    1-device streams byte-identically, and the engine actually ran the
    spec pipeline."""
    out = {}
    for tp in (1, 2):
        cfg = _cfg(tp_size=tp, speculative_tokens=3)
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
        out[tp] = {k: c.tokens for k, c in cols.items()}
    assert out[2] == out[1]


# --------------------------------------------------- per-shard KV wire


def _prompt(n, seed=7):
    rng = np.random.RandomState(seed)
    return [int(x) for x in rng.randint(0, 500, size=n)]


class _RecStream:
    def __init__(self):
        self.chunks = []
        self.aborted = False

    def send_chunk(self, chunk):
        self.chunks.append(chunk)
        return True

    def dispose(self):
        self.aborted = True


def test_pd_streamed_handoff_tp_parity(cpu_devices):
    """PD pair at tp=2, chunked prefill streaming per-chunk KV: every
    chunk's export rides the per-shard wire frame (kv_shards == 2, no
    host gather), lands on the decode peer's sharded pools, and the
    joined stream equals the 1-device colocated oracle byte for byte."""
    def mk(tp):
        cfg = _cfg(
            tp_size=tp, num_blocks=64, max_seq_len=256,
            max_prefill_tokens=32,
            prefill_buckets=[32, 64, 128, 256],
        )
        return InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))

    oracle = mk(1)
    prompt = _prompt(5 * BS + 9)
    sampling = SamplingParams(temperature=0.0, max_new_tokens=6)
    oc = C()
    oracle.add_request(EngineRequest("oracle", list(prompt), sampling, oc))
    _drive(oracle)

    a, b = mk(2), mk(2)
    stream = _RecStream()
    handoffs, ca = [], C()
    a.add_request(EngineRequest(
        "pre", list(prompt), sampling, ca,
        prefill_only=True, handoff=handoffs.append, kv_stream=stream,
    ))
    _drive(a)
    assert len(handoffs) == 1 and stream.chunks
    for c in stream.chunks:
        # Chunk exports are tp-sharded device arrays; the wire frame
        # carries them as per-shard block sets.
        frame = kv_frame_to_bytes(
            {"block_hashes": [h.hex() for h in c.block_hashes]}, c.kv
        )
        header, body = kv_frame_split(frame)
        assert header.get("kv_shards") == [4, 4]  # Hkv=8 over tp=2
        kv = kv_frame_array(header, body)
        assert isinstance(kv, shard_wire.ShardedKV)
        assert tuple(kv.shape) == b.executor.migration_shape(
            len(c.block_hashes)
        )
        b.import_kv_blocks(list(c.block_hashes), kv)
    cb = C()
    b.import_sequence(
        EngineRequest("dec", list(prompt), sampling, cb), handoffs[0]
    )
    _drive(b)
    assert cb.done.is_set()
    assert ca.tokens + cb.tokens == oc.tokens


def _export_cached(eng, hashes, timeout=10.0):
    """Drive export_cached_blocks against an engine stepped manually
    (the test_prefix_fabric harness pattern)."""
    import time

    out = {}

    def go():
        out["r"] = eng.export_cached_blocks(hashes, timeout=timeout)

    t = threading.Thread(target=go, daemon=True)
    t.start()
    deadline = time.monotonic() + timeout
    while "r" not in out and time.monotonic() < deadline:
        eng.step()
        time.sleep(0.001)
    t.join(timeout=2.0)
    return out.get("r", ([], None))


def test_fabric_fetch_tp_cross_mesh(cpu_devices):
    """A tp=2 holder serves a prefix fetch as N per-shard block sets;
    the frames land byte-exactly on tp=1 and tp=4 consumers (the
    cross-tp assemble concatenates only at shard boundaries)."""
    from xllm_service_tpu.common.hashing import prefix_block_hashes

    def mk(tp):
        cfg = _cfg(tp_size=tp, num_blocks=64, max_seq_len=256,
                   prefill_buckets=[32, 64, 128, 256])
        return InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))

    holder = mk(2)
    prompt = _prompt(4 * BS, seed=13)
    c = C()
    holder.add_request(EngineRequest(
        "h", list(prompt),
        SamplingParams(temperature=0.0, max_new_tokens=2), c,
    ))
    _drive(holder)
    hashes = prefix_block_hashes(prompt, BS, holder.block_mgr.seed)[:3]
    served, kv = _export_cached(holder, hashes)
    assert [bytes(h) for h in served] == hashes
    assert isinstance(kv, shard_wire.ShardedKV)
    assert tuple(kv.shape) == holder.executor.migration_shape(len(served))

    # Wire round-trip preserves every byte of every shard.
    frame = kv_frame_to_bytes({"n": len(served)}, kv)
    header, body = kv_frame_split(frame)
    rt = kv_frame_array(header, body)
    assert np.array_equal(np.asarray(rt), np.asarray(kv))

    for tp_consumer in (1, 4):
        cons = mk(tp_consumer)
        cons.import_kv_blocks(list(served), rt)
        _drive(cons)
        ids = [cons.block_mgr.lookup_hash(h) for h in served]
        assert all(i is not None for i in ids)
        back = shard_wire.to_host(
            cons.executor.export_blocks(np.asarray(ids, np.int32))
        )
        assert np.array_equal(np.asarray(back), np.asarray(kv))


def test_sharded_wire_roundtrip_units(cpu_devices):
    """ShardedKV protocol units: logical shape, concat compat, leading-
    axis indexing, and serialization equivalence with the flat wire."""
    rng = np.random.RandomState(0)
    full = rng.randn(2, 2, 3, 8, 4, 16).astype(np.float32)
    skv = shard_wire.ShardedKV(
        [full[:, :, :, 0:2], full[:, :, :, 2:5], full[:, :, :, 5:8]]
    )
    assert skv.shape == full.shape
    assert skv.head_sizes == [2, 3, 3]
    assert np.array_equal(np.asarray(skv), full)
    sub = skv[:, :, np.asarray([2, 0])]
    assert np.array_equal(np.asarray(sub), full[:, :, [2, 0]])
    f1 = kv_frame_to_bytes({"x": 1}, skv)
    h1, b1 = kv_frame_split(f1)
    assert h1["kv_shards"] == [2, 3, 3]
    assert np.array_equal(np.asarray(kv_frame_array(h1, b1)), full)
    # Flat frames stay flat (1-device wires are unchanged bytes).
    f0 = kv_frame_to_bytes({"x": 1}, full)
    h0, b0 = kv_frame_split(f0)
    assert "kv_shards" not in h0
    assert np.array_equal(kv_frame_array(h0, b0), full)


# -------------------------------------------- per-shard kernel dispatch


def test_sharded_kernel_dispatchers_bitwise(cpu_devices, monkeypatch):
    """Direct dispatcher-level proof: decode / flash-prefill / mq
    kernels, and the mixed step's pair of them, under a declared shard
    context (interpret mode, tp ∈ {2, 4}) are BIT-identical to their
    unsharded kernel runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from xllm_service_tpu.ops import attention as att

    R, Hq, Hkv, D, NB = 4, 16, 8, 128, 12
    k = np.asarray(
        np.random.RandomState(1).randn(NB, Hkv, BS, D), np.float32
    )
    v = np.asarray(
        np.random.RandomState(2).randn(NB, Hkv, BS, D), np.float32
    )
    q = np.asarray(np.random.RandomState(3).randn(R, Hq, D), np.float32)
    qp = np.asarray(
        np.random.RandomState(4).randn(R, 4, Hq, D), np.float32
    )
    tables = np.tile(np.arange(NB, dtype=np.int32), (R, 1))
    seq_lens = np.asarray([30, 17, 1, 60], np.int32)
    start = np.asarray([26, 13, 0, 56], np.int32)
    tlen = np.asarray([4, 4, 1, 4], np.int32)
    scale = D ** -0.5
    try:
        att.set_shard_context(None)
        dec0 = att.paged_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(seq_lens), scale,
            use_kernel=True, interpret=True,
        )
        pf0 = att.prefill_attention(
            jnp.asarray(qp), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(start), jnp.asarray(tlen),
            scale, use_kernel=True, interpret=True,
        )
        mq0 = att.prefill_attention(
            jnp.asarray(qp), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(start), jnp.asarray(tlen),
            scale, interpret=True,
        )
        # the mixed step's pair takes no `interpret`: the seam gives it
        monkeypatch.setattr(att, "_interpret", lambda: True)
        mx0 = att.mixed_attention(
            jnp.asarray(q), jnp.asarray(qp), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(seq_lens),
            jnp.asarray(tables), jnp.asarray(start), jnp.asarray(tlen), scale,
        )
        assert np.array_equal(np.asarray(mx0[0]), np.asarray(dec0))
        assert np.array_equal(np.asarray(mx0[1]), np.asarray(mq0))
        for tp in (2, 4):
            mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
            ks = jax.device_put(
                k, NamedSharding(mesh, P(None, "tp", None, None))
            )
            vs = jax.device_put(
                v, NamedSharding(mesh, P(None, "tp", None, None))
            )
            qs = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
            qps = jax.device_put(
                qp, NamedSharding(mesh, P(None, None, "tp", None))
            )
            att.set_shard_context(mesh)
            assert att.shard_context() is not None
            dec = att.paged_attention(
                qs, ks, vs, jnp.asarray(tables), jnp.asarray(seq_lens),
                scale, use_kernel=True, interpret=True,
            )
            assert np.array_equal(np.asarray(dec), np.asarray(dec0))
            pf = att.prefill_attention(
                qps, ks, vs, jnp.asarray(tables), jnp.asarray(start),
                jnp.asarray(tlen), scale, use_kernel=True, interpret=True,
            )
            assert np.array_equal(np.asarray(pf), np.asarray(pf0))
            mq = att.prefill_attention(
                qps, ks, vs, jnp.asarray(tables), jnp.asarray(start),
                jnp.asarray(tlen), scale, interpret=True,
            )
            assert np.array_equal(np.asarray(mq), np.asarray(mq0))
            mx = att.mixed_attention(
                qs, qps, ks, vs, jnp.asarray(tables), jnp.asarray(seq_lens),
                jnp.asarray(tables), jnp.asarray(start), jnp.asarray(tlen),
                scale,
            )
            for got, want in zip(mx, mx0):
                assert np.array_equal(np.asarray(got), np.asarray(want))
    finally:
        att.set_shard_context(None)


def test_gather_fallback_is_visible(cpu_devices):
    """resolve_kv_packing's unpacked-layout downgrade (tp=2 over
    llama3-packed-tiny's single packed row) surfaces as
    `gather-fallback` in kernel_report AND as the engine's resolved
    decode dispatch label — the xllm_engine_kernel_dispatch_total
    counter series, not a buried log line."""
    cfg = EngineConfig(
        model="llama3-packed-tiny", dtype="float32", block_size=16,
        num_blocks=32, max_running_requests=2, max_seq_len=64,
        prefill_buckets=[32, 64], tp_size=2,
    )
    ex = ModelExecutor(cfg, init_seed=0)
    assert ex.kv_pack_fallback
    assert ex.cfg.kv_pack_disable
    rep = ex.kernel_report()
    assert rep["decode"] == "gather-fallback"
    eng = InferenceEngine(cfg, executor=ex)
    assert eng._kernel_names["decode"] == "gather-fallback"
    # An unaffected tp=2 geometry stays on the ordinary labels.
    ex2 = ModelExecutor(_cfg(tp_size=2), init_seed=0)
    assert not ex2.kv_pack_fallback
    assert ex2.kernel_report()["decode"] != "gather-fallback"
