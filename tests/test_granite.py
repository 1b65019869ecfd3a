"""The hybrid family on the normal path (models/granite.py, ops/mamba.py,
the executor's two kinds of sequence memory, the engine's ownership of a
state slot beside K/V blocks), on the CPU with `granite-tiny`: the chunk
form, the recurrence and the benchmark family's plain reference agree in
output, SSM state and convolution state; the kernel equals the jax.numpy
route; prefill-then-decode through the engine matches the dense oracle
and the reference in logits; preemption resumes exactly; slot and blocks
come back on finish, cancel and preemption; the two holders' shares add up
to the uncut layer; what is not built is refused by name."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.models import get_module, granite, llama
from xllm_service_tpu.models.configs import approx_param_count, get_model_config
from xllm_service_tpu.ops import mamba
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import (
    HybridBlockManager,
    StateFamilyUnsupported,
)
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("granite-tiny")
H, P, G, N, K = 8, 16, 1, 16, 4
CONV = H * P + 2 * G * N


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "granite-tiny", "family": "granite"})


def _family_config(c=CFG):
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.moe_intermediate_size,
        "shared_intermediate_size": c.n_shared_experts * c.moe_intermediate_size,
        "num_hidden_layers": c.num_layers, "layer_types": list(c.layer_types),
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "num_local_experts": c.held_experts[1], "num_local_experts_published": c.num_experts,
        "experts_held": list(c.held_experts), "num_experts_per_tok": c.num_experts_per_tok,
        "mamba_n_heads": H, "mamba_d_head": P, "mamba_n_groups": G, "mamba_d_state": N,
        "mamba_d_conv": K, "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "position_embedding_type": "nope", "attention_bias": False, "tie_word_embeddings": True,
        "embedding_multiplier": c.embedding_multiplier, "attention_multiplier": c.attention_multiplier,
        "residual_multiplier": c.residual_multiplier, "logits_scaling": c.logits_scaling,
        "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
        "max_position_embeddings": c.max_position_embeddings,
    }


# ------------------------------------------------------------------- ops


def _scan_inputs(T, seed=2):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 3.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B, C = jax.random.normal(ks[3], (T, G, N)), jax.random.normal(ks[4], (T, G, N))
    return x, dt, A, B, C, 1.0 + 0.1 * jax.random.normal(ks[5], (H,))


def _pad(a, n):
    return jnp.pad(a, ((0, n - a.shape[0]),) + ((0, 0),) * (a.ndim - 1), constant_values=1.0)


@pytest.mark.parametrize("n_prefill", [32, 28, 41], ids=["whole-chunks", "ragged-tail", "three-chunks"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_chunk_form_equals_the_recurrence_through_a_dirty_pool(n_prefill, use_kernel):
    """Chunked prefill (a padding row beside the live one, a short last
    chunk) then decode row by row, through never-cleaned pools: output
    and final SSM state equal the token-by-token recurrence."""
    T, chunk = n_prefill + 6, 16
    x, dt, A, B, C, D = _scan_inputs(T)
    y_ref, S_ref = mamba.recurrent_form(x, dt, A, B, C, D)
    np.testing.assert_allclose(mamba.chunk_form(x, dt, A, B, C, D), y_ref, atol=2e-5)
    S = jnp.full(mamba.state_shapes(2, 4, H, P, N, K, CONV)[0], 3.0)
    layer, slot, ys = jnp.int32(1), 2, []
    for start in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - start)
        rows = [jnp.stack([_pad(a[start:start + n], chunk)] * 2) for a in (x, dt, B, C)]
        y, S = mamba.chunk_update(
            S, layer, jnp.array([slot, -1]), jnp.array([start, 0]), jnp.array([n, 0]),
            rows[0], rows[1], A, rows[2], rows[3], D)
        ys.append(y[0, :n])
    for t in range(n_prefill, T):  # decode: the slot is the ROW
        act = jnp.arange(4) == slot
        bc = lambda a: jnp.broadcast_to(a[t], (4,) + a.shape[1:])
        y, S = mamba.decode_update(S, layer, act, bc(x), bc(dt), A, bc(B), bc(C), D,
                                   use_kernel=use_kernel, interpret=True)
        assert float(jnp.abs(y[0]).max()) == 0.0  # an inactive row reads out nothing
        ys.append(y[slot][None])
    np.testing.assert_allclose(jnp.concatenate(ys), y_ref, atol=3e-5)
    k = mamba.pack_factor(H, P)
    np.testing.assert_allclose(mamba.from_pool(S[1, slot], k), S_ref, atol=3e-5)
    assert float(jnp.abs(S[0] - 3.0).max()) == 0.0  # the other layer: never touched
    assert float(jnp.abs(S[1, 0] - 3.0).max()) == 0.0  # nor the padding row's slot


@pytest.mark.parametrize("live", [(True, False, True, True), (False,) * 4, (True,) * 4],
                         ids=["some-live", "none-live", "all-live"])
def test_update_kernel_equals_the_xla_route(live):
    x, dt, A, B, C, D = _scan_inputs(4, seed=5)
    S = jax.random.normal(jax.random.key(9), mamba.state_shapes(3, 4, H, P, N, K, CONV)[0])
    act = jnp.array(live)
    y0, S0 = mamba.decode_update(S, 2, act, x, dt, A, B, C, D, use_kernel=False)
    y1, S1 = mamba.decode_update(S, 2, act, x, dt, A, B, C, D, use_kernel=True, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(S1, S0, atol=1e-6)
    dead = jnp.logical_not(act)
    assert float(jnp.abs((S1 - S)[2][dead]).max(initial=0.0)) == 0.0
    assert float(jnp.abs((S1 - S)[:2]).max()) == 0.0


@pytest.mark.parametrize("n_prefill", [32, 27], ids=["whole-chunks", "ragged-tail"])
def test_convolution_state_is_the_last_rows_and_carries(n_prefill):
    T, chunk = n_prefill + 5, 16
    ks = jax.random.split(jax.random.key(4), 3)
    xbc = jax.random.normal(ks[0], (T, CONV))
    w, b = jax.random.normal(ks[1], (K, CONV)) / 2, 0.1 * jax.random.normal(ks[2], (CONV,))
    ref = mamba.conv_dense(xbc, w, b)
    conv = jnp.full(mamba.state_shapes(2, 4, H, P, N, K, CONV)[1], 5.0)
    outs = []
    for start in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - start)
        rows = jnp.stack([_pad(xbc[start:start + n], chunk)] * 2)
        c, conv = mamba.conv_chunk(conv, 1, jnp.array([-1, 3]), jnp.array([0, start]),
                                   jnp.array([0, n]), rows, w, b)
        outs.append(c[1, :n])
    np.testing.assert_allclose(conv[1, 3].reshape(K - 1, CONV), xbc[n_prefill - 3:n_prefill], atol=1e-6)
    for t in range(n_prefill, T):
        c, conv = mamba.conv_decode(conv, 1, jnp.arange(4) == 3,
                                    jnp.broadcast_to(xbc[t], (4, CONV)), w, b)
        outs.append(c[3][None])
    np.testing.assert_allclose(jnp.concatenate(outs), ref, atol=1e-5)
    np.testing.assert_allclose(conv[1, 3].reshape(K - 1, CONV), xbc[T - 3:], atol=1e-6)
    assert float(jnp.abs(conv[0] - 5.0).max()) == 0.0 and float(jnp.abs(conv[1, :3] - 5.0).max()) == 0.0


def test_mamba_mixer_equals_the_family_reference():
    """The program's mixer through its pools in chunks against the
    benchmark family's plain token-by-token mixer, over the family's own
    weights (decays and steps where a trained model's lie)."""
    fam, m = _family(), _family_config()
    w = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(7))
    lp = {k: v[1] for k, v in w["mamba"].items()}
    u = jax.random.normal(jax.random.key(8), (40, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.mamba_mixer(u, lp, m)
        S, conv = (jnp.ones(sh) for sh in granite.state_shapes(CFG, 4))
        outs = []
        for start in (0, 16, 32):
            n = min(16, 40 - start)
            pf = granite._Pf(1, 16, None, jnp.array([1]), jnp.array([start]), jnp.array([n]), None)
            y, S, conv = granite._mamba_mixer(lp, CFG, _pad(u[start:start + n], 16), 1, S, conv, None, pf)
            outs.append(y[:n])
    np.testing.assert_allclose(jnp.concatenate(outs), ref, atol=2e-4)
    dt = jax.nn.softplus(u @ lp["w_in"][:, -H:] + lp["dt_bias"])
    decay = jnp.exp(-jnp.exp(lp["A_log"]) * dt)
    assert 0.5 < float(decay.min()) and float(decay.max()) < 0.999999  # the family's band


# ---------------------------------------------------------------- engine


def _engine(R=4, max_seq_len=512, num_blocks=64, **kw):
    """Synchronous stepping, as tests/test_brumby.py's engines and for
    its reason."""
    kw.setdefault("sync_engine", True)
    cfg = EngineConfig(
        model="granite-tiny", dtype="float32", max_running_requests=R, block_size=16,
        num_blocks=num_blocks, max_seq_len=max_seq_len, max_prefill_tokens=32,
        prefill_buckets=[32], **kw,
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
        return True

    return EngineRequest(
        request_id=rid, prompt_token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                logprobs=True, ignore_eos=True),
        callback=cb, offline=offline, **kw,
    )


def _drain(eng, steps=3000):
    for _ in range(steps):
        if not eng.has_work():
            return
        eng.step()
    raise AssertionError("the engine did not drain")


def _nothing_held(eng):
    return len(eng._free_slots) == eng.R and eng.block_mgr.num_referenced_blocks == 0


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts of 1-3
    chunks with a ragged tail served concurrently, 12 greedy tokens each."""
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
    assert get_module(CFG) is granite and isinstance(eng.block_mgr, HybridBlockManager)
    for rid, p in prompts.items():
        seq = p + outs[rid]
        assert len(outs[rid]) == 12
        logits = granite.forward_dense(ex.params, CFG, jnp.asarray(seq, jnp.int32)[None])[0]
        rows = logits[len(p) - 1:len(seq) - 1]
        assert [int(t) for t in jnp.argmax(rows, -1)] == outs[rid]
        np.testing.assert_allclose(outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=2e-5)


def test_engine_matches_the_family_reference_in_logits(served):
    _, ex, fam, m, prompts, outs = served
    with jax.default_matmul_precision("highest"):
        for rid, p in prompts.items():
            seq = np.zeros((128,), np.int32)
            seq[:len(p) + 12] = p + outs[rid]
            idx = np.arange(len(p) - 1, len(p) + 11)
            rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
            np.testing.assert_allclose(outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=2e-5)


def test_both_kinds_of_memory_are_counted_and_nothing_is_cached(served):
    eng, ex = served[0], served[1]
    text = eng.metrics.render()
    for name in ("xllm_engine_state_slots", "xllm_engine_state_slots_in_use",
                 "xllm_engine_state_pool_bytes", "xllm_engine_state_slot_bytes",
                 "xllm_engine_state_recomputes_total", "xllm_engine_cache_row_bytes",
                 "xllm_engine_moe_pairs_per_expert"):
        assert name in text, name
    slot = (H * P * N + (K - 1) * CONV) * 4 * CFG.num_state_layers
    assert ex.state_slot_bytes == slot
    assert ex.state_pool_bytes == 4 * slot
    # K and V of the ONE attention layer: 2 KV heads of 16 lanes, float32
    assert ex.cache_row_bytes == 2 * 1 * 2 * 16 * 4 and ex.block_size == 16
    assert ex.has_state_pool and ex.has_paged_cache and ex.slot_column
    assert ex.kernel_report()["state"] == "mamba-xla"
    assert eng.prefix_cached_tokens == 0 and _nothing_held(eng)
    assert eng.block_mgr.take_cache_event().empty()  # nothing told to the fabric


def test_same_prompt_twice_is_recomputed_not_cached(served):
    eng, _, _, _, prompts, outs = served
    again = {}
    eng.add_request(_req("again", again, prompts["r1"], max_new=12))
    _drain(eng)
    assert again["again"] == outs["r1"]  # a reused, never-cleaned slot; blocks not matched
    assert eng.prefix_cached_tokens == 0


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
    for i in range(2):  # an online burst takes every row
        eng.add_request(_req(f"b{i}", outs, prompt[:20 + i], max_new=5))
    _drain(eng)
    assert eng.preemptions >= 1 and eng.state_recomputes >= 1
    assert outs["victim"] == ref["solo"]
    np.testing.assert_allclose(outs["victim/lp"], ref["solo/lp"], atol=1e-5)
    assert _nothing_held(eng)


def test_slot_and_blocks_come_back_on_finish_cancel_and_preemption():
    """200 requests through 4 rows and a pool too small for four long
    ones (9 blocks of 16 tokens): finishes, cancels (queued, mid-prefill and decoding) and
    block-pressure preemptions; in the end no row and no block is held."""
    eng, _ = _engine(R=4, num_blocks=10)
    outs, rng = {}, np.random.default_rng(11)
    for i in range(200):
        n = int(rng.integers(4, 70))
        eng.add_request(_req(f"q{i}", outs, rng.integers(0, 512, n), max_new=int(rng.integers(1, 40))))
        if i % 7 == 3:
            eng.cancel(f"q{i}")  # still queued
        if i % 4 == 0:
            for _ in range(3):
                eng.step()
        if i % 9 == 5:
            eng.cancel(f"q{i - 2}")  # prefilling, decoding or already done
        held = list(eng._running.values()) + list(eng._pf_active.values())
        assert len({s.slot for s in held}) == len(held) <= 4
        blocks = [b for s in held for b in s.block_ids]
        assert len(set(blocks)) == len(blocks)
    _drain(eng)
    assert len(outs["_finished"]) == 200 and eng.preemptions >= 1
    assert _nothing_held(eng) and eng.block_mgr.num_free_blocks == 9


def test_mixed_step_equals_its_split_steps():
    params = granite.init_params(CFG, jax.random.key(3), jnp.float32)
    ssm, conv = (jnp.zeros(sh, jnp.float32) for sh in granite.state_shapes(CFG, 4))
    kv = jnp.zeros((1, 12, 2, 16, 16), jnp.float32)
    kc, vc = (kv, ssm), (kv, conv)
    rng = np.random.default_rng(2)
    warm = jnp.asarray(rng.integers(0, 512, (2, 32)), jnp.int32)
    _, kc, vc = granite.prefill_batch_step(  # two sequences already in rows 0 and 2
        params, CFG, kc, vc, warm, jnp.zeros(2, jnp.int32), jnp.full(2, 32, jnp.int32),
        jnp.array([[1, 2, 3, 1], [4, 5, 6, 3]], jnp.int32))
    dec = (jnp.asarray(rng.integers(0, 512, 4), jnp.int32), jnp.array([32, 0, 32, 0], jnp.int32),
           jnp.array([[1, 2, 3], [0, 0, 0], [4, 5, 6], [0, 0, 0]], jnp.int32),
           jnp.array([True, False, True, False]))
    pf = (jnp.asarray(rng.integers(0, 512, (1, 32)), jnp.int32), jnp.zeros(1, jnp.int32),
          jnp.array([20], jnp.int32), jnp.array([[7, 8, 0, 2]], jnp.int32))
    d_logits, p_logits, km, vm = granite.mixed_step(params, CFG, kc, vc, *dec, *pf)
    d_ref, ks, vs = granite.decode_step(params, CFG, kc, vc, *dec)
    p_ref, ks, vs = granite.prefill_batch_step(params, CFG, ks, vs, *pf)
    live = jnp.array([0, 2])
    np.testing.assert_allclose(d_logits[live], d_ref[live], atol=1e-5)
    np.testing.assert_allclose(p_logits, p_ref, atol=1e-5)
    for a, b in zip(jax.tree.leaves((km, vm)), jax.tree.leaves((ks, vs))):
        np.testing.assert_allclose(a[:, 1:] if a.ndim == 5 and a.shape[1] == 12 else a,
                                   b[:, 1:] if b.ndim == 5 and b.shape[1] == 12 else b, atol=1e-5)
    assert float(jnp.abs(km[1][:, 3]).max()) == 0.0  # the fourth slot: never touched


# ------------------------------------------------------ the share and model


def test_two_holders_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of 8 at the tiny size: the two holders' routed
    parts plus the shared MLP counted once are the uncut layer, in the
    program's expert block and in the family's reference alike."""
    whole = dataclasses.replace(CFG, experts_held=())
    params = granite.init_params(whole, jax.random.key(1), jnp.float32)
    lp = {k: v[2] for k, v in params["layers"].items()}
    u = jax.random.normal(jax.random.key(2), (24, CFG.hidden_size))
    full = llama._mlp_block(lp, whole, u)
    shared = llama._shared_experts(lp, u)
    parts = []
    for first in (0, 4):
        held = dataclasses.replace(CFG, experts_held=(first, 4))
        cut = {**lp, **{k: lp[k][first:first + 4] for k in llama.EXPERT_LEAVES}}
        parts.append(llama._mlp_block(cut, held, u) - shared)
    assert float(jnp.abs(parts[0]).max()) > 1e-3 and float(jnp.abs(parts[1]).max()) > 1e-3
    np.testing.assert_allclose(parts[0] + parts[1] + shared, full, atol=1e-5)
    fam, m = _family(), _family_config(whole)
    leaves = {k: v for k, v in params["layers"].items()}
    with jax.default_matmul_precision("highest"):
        ref = fam.expert_layer(u, leaves, 2, m)
        halves = [fam.expert_layer(u, leaves, 2, m, shared=False, span=(f, 4)) for f in (0, 4)]
        ref_shared = fam.expert_layer(u, leaves, 2, m, span=(0, 4)) - halves[0]
    np.testing.assert_allclose(halves[0] + halves[1] + ref_shared, ref, atol=1e-5)
    np.testing.assert_allclose(ref, full, atol=1e-4)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = get_model_config("granite-4.0-h-small")
    assert get_module(c) is granite
    assert c.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert [(s.kind, s.first, s.kind_first, s.n) for s in granite._segments(c)] == [
        ("mamba", 0, 0, 5), ("attention", 5, 0, 1), ("mamba", 6, 5, 4)]
    assert (c.hidden_size, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_d_conv) \
        == (4096, 128, 64, 128, 4)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.attention_multiplier) == (32, 8, 128, 1 / 128)
    assert (c.num_experts, c.held_experts, c.num_experts_per_tok, c.moe_intermediate_size) \
        == (72, (0, 36), 10, 768)
    assert c.n_shared_experts * c.moe_intermediate_size == 1536 and c.mamba_conv_dim == 8448
    assert abs(approx_param_count(c) / 4757e6 - 1) < 0.01
    ssm, conv = granite.state_shapes(c, 64)
    assert ssm == (9, 64, 64, 128, 128) and conv == (9, 64, 3 * 8448)
    assert sum(math.prod(sh) for sh in granite.state_shapes(c, 1)) * 4 == 38_661_120
    assert granite.cache_row_dims(c) == (8, 128) and c.num_attention_layers == 1


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens"),
    (dict(num_host_blocks=8), "prefix cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(checkpoint_path="/nowhere"), "checkpoint_path"),
    (dict(tp_size=2), "tp_size/ep_size/sp_size/dp_size"),
], ids=["speculation", "prefix-tiers", "int8-cache", "checkpoint", "tensor-parallel"])
def test_named_refusals_at_build(kw, match):
    with pytest.raises(StateFamilyUnsupported, match=match):
        _engine(**kw)


def test_named_refusals_at_the_request_and_an_inert_prefix_half():
    eng, ex = _engine(R=2)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.import_sequence(_req("pd", {}, [1, 2, 3]), None)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])
    assert eng.block_mgr.lookup_hash(b"h") is None
    assert eng.block_mgr.take_cache_event().empty()
    assert len(eng.block_mgr.allocate(3)) == 3  # blocks grow: no one-slot rule here


def test_pools_are_sized_one_after_the_other():
    """The state pool is sized by its slots and refused when it does not
    fit; the K/V pool gets half of what is left beside weights AND state."""
    eng, ex = _engine(R=4, num_blocks=0)  # auto-size against the nominal 16 GiB
    c = ex.cfg
    block = 2 * c.num_attention_layers * 16 * 2 * 16 * 4
    left = 16 * 2**30 * 0.9 - approx_param_count(c) * 4 - ex.state_pool_bytes
    assert ex.num_blocks == int(left / 2 // block)
    assert ex.prefill_buckets == [32]  # max_seq_len bounds no program
    with pytest.raises(ValueError, match="state pool: 4000000 slots"):
        _engine(R=4_000_000)


def test_the_parameter_tree_has_a_replicated_rule_for_every_leaf():
    from xllm_service_tpu.parallel.mesh import build_mesh
    from xllm_service_tpu.parallel.sharding import param_shardings

    rules = param_shardings(CFG, build_mesh(tp=1))
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(rules)
    for leaf, rule in zip(jax.tree.leaves(shapes), jax.tree.leaves(rules)):
        assert len(rule.spec) == leaf.ndim and not any(rule.spec)
