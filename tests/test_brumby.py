"""Power retention on the normal path (models/brumby.py, ops/retention.py,
the executor's state pool, the engine's slot ownership), on the CPU with
`brumby-tiny`: the three forms agree, the engine's prefill-then-decode
through the state pool matches the dense oracle and the benchmark family's
plain reference in logits, preemption resumes exactly, slots are never
shared and a freed slot is clean, and what is not built is refused by
name."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.common.types import FinishReason
from xllm_service_tpu.models import brumby, get_module
from xllm_service_tpu.models.configs import get_model_config
from xllm_service_tpu.ops import retention
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import (
    StateFamilyUnsupported,
    StateSlotManager,
)
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _qkvg(T, Hq, Hkv, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (T, Hq, d))
    k = jax.random.normal(ks[1], (T, Hkv, d))
    v = jax.random.normal(ks[2], (T, Hkv, d))
    # per-token decays in the band the benchmark's weights give
    gamma = jnp.log(jax.random.uniform(ks[3], (T, Hkv), minval=0.995, maxval=0.9999))
    return q, k, v, gamma


def _serve_through_pool(q, k, v, gamma, use_kernel, chunk=16, n_prefill=28):
    """Chunked prefill (a chunk boundary inside the prompt, a ragged last
    chunk, a padding row) then decode row by row, through a dirty pool."""
    T, _, d = q.shape
    Hkv = k.shape[1]
    s_shape, z_shape = retention.state_shapes(2, 4, Hkv, d)
    S, z = jnp.full(s_shape, 3.0), jnp.ones(z_shape)  # never-cleaned slots
    layer, slot, ys = jnp.int32(1), 2, []
    kw = dict(use_kernel=use_kernel, interpret=True)
    for start in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - start)
        rows = [
            jnp.stack([jnp.pad(a[start:start + n], ((0, chunk - n),) + ((0, 0),) * (a.ndim - 1))] * 2)
            for a in (q, k, v, gamma)
        ]
        y, S, z = retention.chunk_update(
            S, z, layer, jnp.array([slot, 0]), jnp.array([start, 0]),
            jnp.array([n, 0]), *rows, **kw)
        ys.append(y[0, :n])
    for t in range(n_prefill, T):
        rows = [jnp.stack([a[t]] * 3) for a in (q, k, v, gamma)]
        y, S, z = retention.decode_update(
            S, z, layer, jnp.array([0, slot, 1]), jnp.array([False, True, False]),
            *rows, **kw)
        ys.append(y[1:2])
        assert float(jnp.abs(y[0]).max()) == 0.0  # inactive rows read nothing
    # no other slot and no other layer was touched
    assert float(S[0].min()) == 3.0 and float(S[1, :2].min()) == 3.0
    assert float(S[1, 3].min()) == 3.0 and float(z[1, 3].min()) == 1.0
    return jnp.concatenate(ys)


@pytest.mark.parametrize(
    "d,use_kernel", [(16, False), (16, True), (128, True)],
    ids=["xla-d16", "pallas-interpret-d16", "pallas-interpret-d128"],
)
def test_recurrent_chunked_and_attention_forms_agree(d, use_kernel):
    with jax.default_matmul_precision("highest"):
        q, k, v, gamma = _qkvg(40, 4, 2, d)
        ref = retention.attention_form(q, k, v, gamma)
        out = _serve_through_pool(q, k, v, gamma, use_kernel)
    assert float(jnp.abs(out - ref).max()) < 1e-4 * max(1.0, float(jnp.abs(ref).max()))


def test_feature_map_is_the_squared_inner_product():
    q, k, _, _ = _qkvg(5, 2, 2, 16, seed=3)
    lhs = jnp.einsum("thnc,thnc->th", retention.phi(q), retention.phi(k))
    rhs = (jnp.einsum("thd,thd->th", q, k) / 4.0) ** 2  # sqrt(16) = 4
    np.testing.assert_allclose(lhs, rhs, rtol=1e-5)
    assert retention.feature_rows(128) * 128 == 8320  # stored, for 8256 true


def test_kernels_equal_the_xla_route_on_one_step():
    """The two Pallas kernels (interpret mode) against ops/retention.py's
    jax.numpy route: same outputs, same pool, live and dead rows mixed."""
    with jax.default_matmul_precision("highest"):
        q, k, v, gamma = _qkvg(64, 4, 2, 128, seed=7)
        s_shape, z_shape = retention.state_shapes(1, 3, 2, 128)
        S0 = jax.random.normal(jax.random.key(1), s_shape)
        z0 = jnp.abs(jax.random.normal(jax.random.key(2), z_shape)) + 1.0
        rows = [a[:3] for a in (q, k, v, gamma)]
        args = (jnp.int32(0), jnp.array([2, 0, 1]), jnp.array([True, False, True]))
        a = retention.decode_update(S0, z0, *args, *rows, use_kernel=False)
        b = retention.decode_update(S0, z0, *args, *rows, use_kernel=True, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-4)
        rows = [a.reshape((2, 32) + a.shape[1:]) for a in (q, k, v, gamma)]
        args = (jnp.int32(0), jnp.array([1, 0]), jnp.array([32, 0]), jnp.array([20, 32]))
        a = retention.chunk_update(S0, z0, *args, *rows, use_kernel=False)
        b = retention.chunk_update(S0, z0, *args, *rows, use_kernel=True, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------- engine


def _engine(R=4, max_seq_len=512, **kw):
    """Synchronous stepping: on the CPU backend an overlapped engine's
    host arrays can move on while XLA:CPU still reads them (PERF.md
    section 7), which flips a greedy token now and then, more often on a
    loaded machine; slot ownership, chunked prefill, preemption and the
    resume by recompute are the same code in both modes. The mixed step
    is held to the split steps below, without an engine, and served
    through the whole stack by benchmarks/tests/test_brumby.py."""
    kw.setdefault("sync_engine", True)
    cfg = EngineConfig(
        model="brumby-tiny", dtype="float32", max_running_requests=R,
        max_seq_len=max_seq_len, max_prefill_tokens=32, prefill_buckets=[32], **kw,
    )
    ex = ModelExecutor(cfg)
    return InferenceEngine(cfg, executor=ex), ex


def _req(rid, outs, prompt, max_new=8, offline=False, **kw):
    def cb(o):
        for s in o.outputs:
            outs.setdefault(rid, []).extend(s.token_ids)
            outs.setdefault(rid + "/lp", []).extend(lp.data.logprob for lp in s.logprobs)
        if o.finished:
            outs.setdefault("_finished", []).append(rid)
            outs[rid + "/reason"] = o.outputs[0].finish_reason if o.outputs else None
        return True

    return EngineRequest(
        request_id=rid, prompt_token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                logprobs=True, ignore_eos=True),
        callback=cb, offline=offline, **kw,
    )


def _drain(eng, steps=600):
    for _ in range(steps):
        if not eng.has_work():
            return
        eng.step()
    raise AssertionError("the engine did not drain")


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "brumby-tiny", "family": "brumby"})


def _family_config():
    c = get_model_config("brumby-tiny")
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim, "rope_theta": c.rope_theta,
        "rms_norm_eps": c.rms_norm_eps, "retention_degree": 2,
        "retention_eps": retention.EPS, "tie_word_embeddings": False,
        "max_position_embeddings": c.max_position_embeddings,
    }


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights (gate decays in
    0.995-0.9999), three prompts of 1-3 chunks with a ragged tail served
    concurrently, 12 greedy tokens each."""
    eng, ex = _engine()
    fam, m = _family(), _family_config()
    ex.params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    rng = np.random.default_rng(0)
    prompts = {f"r{i}": list(rng.integers(0, 512, n)) for i, n in enumerate((23, 64, 75))}
    outs = {}
    for rid, p in prompts.items():
        eng.add_request(_req(rid, outs, p, max_new=12))
    _drain(eng)
    return eng, ex, fam, m, prompts, outs


def _logprobs_of(logits, ids):
    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return np.asarray(lp[np.arange(len(ids)), np.asarray(ids)])


def test_engine_matches_the_dense_oracle_in_logits(served):
    eng, ex, _, _, prompts, outs = served
    cfg = get_model_config("brumby-tiny")
    assert get_module(cfg) is brumby
    for rid, p in prompts.items():
        seq = p + outs[rid]
        assert len(outs[rid]) == 12
        logits = brumby.forward_dense(ex.params, cfg, jnp.asarray(seq, jnp.int32)[None])[0]
        rows = logits[len(p) - 1:len(seq) - 1]
        assert [int(t) for t in jnp.argmax(rows, -1)] == outs[rid]
        np.testing.assert_allclose(
            outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=2e-4)


def test_engine_matches_the_family_reference_in_logits(served):
    _, ex, fam, m, prompts, outs = served
    with jax.default_matmul_precision("highest"):
        for rid, p in prompts.items():
            seq = np.zeros((128,), np.int32)
            seq[:len(p) + 12] = p + outs[rid]
            idx = np.arange(len(p) - 1, len(p) + 11)
            rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
            np.testing.assert_allclose(
                outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=2e-4)


def test_state_pool_metrics_and_no_prefix_cache(served):
    eng = served[0]
    text = eng.metrics.render() if hasattr(eng.metrics, "render") else ""
    for name in ("xllm_engine_state_slots", "xllm_engine_state_slots_in_use",
                 "xllm_engine_state_pool_bytes", "xllm_engine_state_recomputes_total"):
        assert name in text, name
    assert eng.prefix_cached_tokens == 0
    assert isinstance(eng.block_mgr, StateSlotManager)
    assert eng.block_mgr.slots_in_use == 0 and len(eng._free_slots) == eng.R


def test_same_prompt_twice_is_recomputed_not_cached(served):
    eng, _, _, _, prompts, outs = served
    again = {}
    eng.add_request(_req("again", again, prompts["r1"], max_new=12))
    _drain(eng)
    assert again["again"] == outs["r1"]  # a reused, never-cleaned slot
    assert eng.prefix_cached_tokens == 0


def test_two_requests_never_share_a_slot():
    eng, _ = _engine(R=3)
    outs, seen = {}, set()
    rng = np.random.default_rng(1)
    for i in range(7):  # more requests than slots: they queue for one
        eng.add_request(_req(f"q{i}", outs, rng.integers(0, 512, 40), max_new=6))
    for _ in range(400):
        if not eng.has_work():
            break
        eng.step()
        held = [s for s in list(eng._running.values()) + list(eng._pf_active.values())]
        slots = [tuple(s.block_ids) for s in held]
        assert all(len(b) == 1 for b in slots) and len(set(slots)) == len(slots)
        assert len(held) <= 3
        seen.update(b[0] for b in slots)
    assert seen == {1, 2, 3} and len(outs["_finished"]) == 7


def test_preempted_request_resumes_exactly():
    prompt = list(np.random.default_rng(5).integers(1, 400, 45))
    ref, outs = {}, {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("solo", ref, prompt, max_new=30, offline=True))
    _drain(eng)
    eng, _ = _engine(R=2)
    eng.add_request(_req("victim", outs, prompt, max_new=30, offline=True))
    for _ in range(8):
        eng.step()
    for i in range(2):  # an online burst takes every slot
        eng.add_request(_req(f"b{i}", outs, prompt[:20 + i], max_new=5))
    _drain(eng)
    assert eng.preemptions >= 1 and eng.state_recomputes >= 1
    assert outs["victim"] == ref["solo"]
    np.testing.assert_allclose(outs["victim/lp"], ref["solo/lp"], atol=1e-5)


@pytest.mark.parametrize("sync", [True, False], ids=["sync-drain", "overlapped-drain"])
def test_decoding_into_the_context_limit_finishes_with_length(sync):
    """A block is as long as `max_seq_len`, so a sequence that decodes up
    to the limit holds one FULL block at exactly that length: it is not
    committed (there is no prefix cache to commit to), the sequence ends
    with LENGTH, the slot comes back, and the next request is served."""
    eng, _ = _engine(R=2, max_seq_len=64, sync_engine=sync)
    outs = {}
    rng = np.random.default_rng(3)
    eng.add_request(_req("long", outs, rng.integers(0, 512, 40), max_new=100))
    eng.add_request(_req("short", outs, rng.integers(0, 512, 33), max_new=5))
    _drain(eng)
    assert len(outs["long"]) == 64 - 40 and outs["long/reason"] == FinishReason.LENGTH
    assert len(outs["short"]) == 5
    eng.add_request(_req("next", outs, rng.integers(0, 512, 63), max_new=9))
    _drain(eng)
    assert len(outs["next"]) == 1 and outs["next/reason"] == FinishReason.LENGTH
    assert len(eng._free_slots) == eng.R and eng.block_mgr.slots_in_use == 0
    assert eng.block_mgr.take_cache_event().empty()  # nothing told to the fabric


def test_mixed_step_equals_its_split_steps():
    """One program for decode rows and a prefill chunk gives what the
    decode program and the prefill program give one after the other: same
    logits, same pool (the halves touch disjoint slots)."""
    cfg = get_model_config("brumby-tiny")
    params = brumby.init_params(cfg, jax.random.key(3), jnp.float32)
    S, z = (jnp.zeros(sh, jnp.float32) for sh in brumby.state_shapes(cfg, 4))
    rng = np.random.default_rng(2)
    warm = jnp.asarray(rng.integers(0, 512, (2, 32)), jnp.int32)
    _, S, z = brumby.prefill_batch_step(  # two sequences already in slots 0 and 2
        params, cfg, S, z, warm, jnp.zeros(2, jnp.int32), jnp.full(2, 32, jnp.int32),
        jnp.array([[1], [3]], jnp.int32))
    dec = (jnp.asarray(rng.integers(0, 512, 3), jnp.int32), jnp.array([32, 0, 32], jnp.int32),
           jnp.array([[1], [0], [3]], jnp.int32), jnp.array([True, False, True]))
    pf = (jnp.asarray(rng.integers(0, 512, (1, 32)), jnp.int32), jnp.zeros(1, jnp.int32),
          jnp.array([20], jnp.int32), jnp.array([[2]], jnp.int32))
    d_logits, p_logits, Sm, zm = brumby.mixed_step(params, cfg, S, z, *dec, *pf)
    d_ref, Ss, zs = brumby.decode_step(params, cfg, S, z, *dec)
    p_ref, Ss, zs = brumby.prefill_batch_step(params, cfg, Ss, zs, *pf)
    np.testing.assert_allclose(d_logits[jnp.array([0, 2])], d_ref[jnp.array([0, 2])], atol=1e-4)
    np.testing.assert_allclose(p_logits, p_ref, atol=1e-4)
    np.testing.assert_allclose(Sm, Ss, atol=1e-5)
    np.testing.assert_allclose(zm, zs, atol=1e-5)
    assert float(jnp.abs(Sm[:, 3]).max()) == 0.0  # the fourth slot: never touched


# -------------------------------------------------------------- refusals


def test_named_refusals():
    with pytest.raises(StateFamilyUnsupported, match="speculative_tokens"):
        _engine(speculative_tokens=2)
    with pytest.raises(StateFamilyUnsupported, match="prefix cache"):
        _engine(num_host_blocks=8)
    with pytest.raises(StateFamilyUnsupported, match="kv_cache_dtype"):
        _engine(kv_cache_dtype="int8")
    eng, ex = _engine(R=2)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.import_sequence(_req("pd", {}, [1, 2, 3]), None)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    # the content-addressed half of the slot manager is inert, not refused:
    # the engine thread reaches it at exactly max_seq_len tokens
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])
    assert eng.block_mgr.lookup_hash(b"h") is None
    assert eng.block_mgr.take_cache_event().empty()
    with pytest.raises(StateFamilyUnsupported, match="exactly one slot"):
        eng.block_mgr.allocate(2)


def test_config_from_hf_rejects_brumby_by_name(tmp_path):
    from xllm_service_tpu.runtime.weights import config_from_hf

    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "brumby", "architectures": ["BrumbyForCausalLM"],
        "hidden_size": 5120, "num_attention_heads": 40}))
    with pytest.raises(ValueError, match="brumby.*no checkpoint loader"):
        config_from_hf(str(tmp_path), name="x")


def test_state_pool_is_sized_by_its_bytes_and_refused_when_too_large():
    cfg = get_model_config("brumby-14b")
    want = 8 * 8320 * 129 * 4 * cfg.num_layers  # stored: 8320 for 8256 true
    assert retention.state_bytes(cfg.num_layers, 1, 8, 128) == want
    eng, ex = _engine(R=4)
    assert ex.num_blocks == 5 and ex.block_size == 512 and ex.max_blocks_per_seq == 1
    assert ex.state_pool_bytes == retention.state_bytes(2, 4, 2, 16)
    assert ex.prefill_buckets == [32]  # max_seq_len bounds no program
    with pytest.raises(ValueError, match="state pool: 4000000 slots"):
        _engine(R=4_000_000)
