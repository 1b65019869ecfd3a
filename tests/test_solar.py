"""The KDA hybrid on the normal path (models/granite.py's third mixer kind,
ops/kda.py, the executor's pools by state-layer kind), on the CPU with
`solar-tiny`: the chunk form, the recurrence and the benchmark family's
plain reference agree in output and state; the kernel equals the jax.numpy
route; prefill-then-decode through the engine matches the dense oracle and
the reference in logits; the mixed step equals its split steps; the
sixteenths of a layer add up to the uncut layer; what is not built is
refused by name; and the other hybrid's numbers come out as before."""

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
from xllm_service_tpu.ops import kda, mamba
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import (
    HybridBlockManager,
    StateFamilyUnsupported,
)
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("solar-tiny")
H, D, K = CFG.kda_n_heads, CFG.kda_d_head, CFG.kda_d_conv
CONV = 3 * H * D


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "solar-tiny", "family": "solar"})


def _family_config(c=CFG):
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "moe_intermediate_size": c.moe_intermediate_size, "n_shared_experts": c.n_shared_experts,
        "num_hidden_layers": c.num_layers,
        "gqa_layers": [l for l, k in enumerate(c.layer_types) if k == "attention"],
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim, "n_routed_experts": c.held_experts[1],
        "n_routed_experts_published": c.num_experts, "experts_held": list(c.held_experts),
        "num_experts_per_tok": c.num_experts_per_tok, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "first_k_dense_replace": 0,
        "linear_attn_config": {"short_conv_kernel_size": K, "head_dim": D, "num_heads": H},
        "kda_gate_rank": c.kda_gate_rank, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "use_gqa_gate": True, "use_rope": False,
        "tie_word_embeddings": False, "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
        "max_position_embeddings": c.max_position_embeddings,
    }


# ------------------------------------------------------------------- ops

REGIMES = {
    # (log-decay scale, beta's logit shift): decays near 1, near 0, beta near 2
    "slow": (-1e-4, 0.0), "fast": (-12.0, 0.0), "mixed": (-0.05, 0.0), "beta-2": (-0.05, 5.0),
}


def _rule_inputs(T, regime, seed=2):
    glo, shift = REGIMES[regime]
    ks = jax.random.split(jax.random.key(seed), 6)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = l2(jax.random.normal(ks[0], (T, H, D))) * D ** -0.5
    k, v = l2(jax.random.normal(ks[1], (T, H, D))), jax.random.normal(ks[2], (T, H, D))
    g = glo * jax.random.uniform(ks[3], (T, H, D)) * jnp.exp(jax.random.normal(ks[5], (T, H, 1)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)) + shift)
    return q, k, v, g, beta


def _pad(a, n):
    return jnp.pad(a, ((0, n - a.shape[0]),) + ((0, 0),) * (a.ndim - 1), constant_values=1.0)


@pytest.mark.parametrize("gram", [True, False], ids=["gram-kernel", "gram-xla"])
@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("n_prefill,use_kernel", [(64, False), (57, True), (83, False)],
                         ids=["whole-chunks-xla", "ragged-tail-kernel", "three-chunks-xla"])
def test_chunk_form_equals_the_recurrence_through_a_dirty_pool(regime, n_prefill, use_kernel, gram):
    """Chunked prefill (a padding row beside the live one, a short last
    chunk, a first chunk that starts at 0 on a never-cleaned slot) then
    decode row by row: output and final state equal the token-by-token
    recurrence, with decays near 1 and near 0 and beta near 2, whichever
    route the gram's diagonal takes (`kda_gram_kernel` | `jax.numpy`)."""
    T, chunk = n_prefill + 6, 32
    x = _rule_inputs(T, regime)
    o_ref, S_ref = kda.recurrent_form(*x)
    o_all, S_all = kda.chunk_form(*x, chunk=16, use_kernel=gram, interpret=True)
    scale = float(jnp.abs(o_ref).max())
    np.testing.assert_allclose(o_all, o_ref, atol=2e-5 * max(scale, 1.0))
    np.testing.assert_allclose(S_all, S_ref, atol=3e-5)
    S = jnp.full(kda.state_shapes(2, 4, H, D, K)[0], 3.0)
    layer, slot, os_ = jnp.int32(1), 2, []
    for start in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - start)
        rows = [jnp.stack([_pad(a[start:start + n], chunk)] * 2) for a in x]
        o, S = kda.chunk_update_heads(S, layer, jnp.array([slot, -1]), jnp.array([start, 0]),
                                jnp.array([n, 0]), *rows, chunk=16, use_kernel=gram, interpret=True)
        os_.append(o[0, :n])
    for t in range(n_prefill, T):  # decode: the slot is the ROW
        act = jnp.arange(4) == slot
        o, S = kda.decode_update(S, layer, act, *(jnp.broadcast_to(a[t], (4,) + a.shape[1:]) for a in x),
                                 use_kernel=use_kernel, interpret=True)
        assert float(jnp.abs(o[0]).max()) == 0.0  # an inactive row reads out nothing
        os_.append(o[slot][None])
    np.testing.assert_allclose(jnp.concatenate(os_), o_ref, atol=3e-5 * max(scale, 1.0))
    np.testing.assert_allclose(S[1, slot], S_ref, atol=3e-5)
    assert float(jnp.abs(S[0] - 3.0).max()) == 0.0  # the other layer: never touched
    assert float(jnp.abs(S[1, 0] - 3.0).max()) == 0.0  # nor the padding row's slot


@pytest.mark.parametrize("live", [(True, False, True, True), (False,) * 4, (True,) * 4],
                         ids=["some-live", "none-live", "all-live"])
def test_update_kernel_equals_the_xla_route(live):
    x = _rule_inputs(4, "mixed", seed=5)
    S = jax.random.normal(jax.random.key(9), kda.state_shapes(3, 4, H, D, K)[0])
    act = jnp.array(live)
    o0, S0 = kda.decode_update(S, 2, act, *x, use_kernel=False)
    o1, S1 = kda.decode_update(S, 2, act, *x, use_kernel=True, interpret=True)
    np.testing.assert_allclose(o1, o0, atol=1e-5)
    np.testing.assert_allclose(S1, S0, atol=1e-6)
    dead = jnp.logical_not(act)
    assert float(jnp.abs((S1 - S)[2][dead]).max(initial=0.0)) == 0.0
    assert float(jnp.abs((S1 - S)[:2]).max()) == 0.0


@pytest.mark.parametrize("case", ["one-block", "chunk-64", "wide-lanes", "underflow"])
def test_gram_kernel_equals_the_xla_route(case):
    """Both grams (M[k], M[q]) through `kda_gram_kernel` equal the
    `jax.numpy` route's: the diagonal sub-blocks alone (one block of 16),
    whole (four sub-blocks: the off-diagonal products around the kernel's
    diagonal), at the benchmark's 128 lanes, and where every decay of a
    chunk underflows: 0, the limit, never NaN. Nothing above the diagonal."""
    C, d, glo = {"one-block": (16, D, -0.05), "chunk-64": (64, D, -0.05),
                 "wide-lanes": (32, 128, -0.05), "underflow": (64, D, -400.0)}[case]
    ks = jax.random.split(jax.random.key(11), 3)
    shape = (3, 2, H, C, d)  # chunks, rows, heads: as `_chunk_scan` hands them over
    k, q = (jax.random.normal(key, shape) for key in ks[:2])
    G = jnp.cumsum(glo * jax.random.uniform(ks[2], shape, minval=0.5), axis=-2)
    x = jnp.stack([k, q])
    want = kda._decayed_gram(x, k, G)
    got = kda._decayed_gram(x, k, G, use_kernel=True, interpret=True)
    assert got.shape == want.shape == (2, *shape[:-1], C)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
    if case == "underflow":  # a token sees itself and nothing else
        eye = jnp.einsum("...id,...id->...i", x, k[None])
        np.testing.assert_allclose(jnp.diagonal(got, axis1=-2, axis2=-1), eye, rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(jnp.tril(got, -1)).max()) == 0.0
    one = kda._decayed_gram(q, k, G, use_kernel=True, interpret=True)  # one row set, no leading axis
    np.testing.assert_allclose(one, want[1], atol=2e-5 * float(jnp.abs(want).max()))


def _chunk_rows(regime, Lc, seed=4):
    """Three rows of one step as the convolution leaves them (q | k | v
    side by side, not normalised): a ragged row that starts mid-context
    on a carried state, a padding row, and a row that starts at 0 on a
    dirty slot and ends inside a sub-block."""
    _, _, _, g, beta = _rule_inputs(3 * Lc, regime, seed=seed)
    qkv = 3.0 * jax.random.normal(jax.random.key(seed), (3, Lc, CONV))
    rows = (qkv, g.reshape(3, Lc, H, D), beta.reshape(3, Lc, H))
    return rows, jnp.array([3, -1, 1]), jnp.array([Lc, 0, 0]), jnp.array([Lc - 5, 0, Lc // 2 + 3])


@pytest.mark.parametrize("regime,chunk", [("slow", 64), ("fast", 64), ("mixed", 64), ("beta-2", 64),
                                          ("mixed", 32), ("mixed", 16)])
def test_chunk_kernel_equals_the_xla_route(regime, chunk):
    """`kda_chunk_kernel` (interpret mode) against `qkv_heads` and
    `_chunk_scan` through `chunk_update`: two chunks a row, valid
    lengths that are no multiple of the sub-block, a row that starts
    mid-context on a non-zero state, one that starts at 0 on a
    never-cleaned slot, a padding row beside them, decays near 1 and near
    0, beta near 2; 4, 2 and 1 sub-blocks a chunk (two, one and no level
    of joins in the inverse). Output and state within the gram kernel's
    tolerance; what the rows do not own is not touched."""
    rows, slots, start, length = _chunk_rows(regime, 128 if chunk == 64 else 64)
    S = jax.random.normal(jax.random.key(3), kda.state_shapes(2, 5, H, D, K)[0])
    want_o, want_S = kda.chunk_update(S, 1, slots, start, length, *rows, chunk=chunk, use_kernel=False)
    got_o, got_S = kda.chunk_update(S, 1, slots, start, length, *rows, chunk=chunk,
                                         use_kernel=True, interpret=True)
    assert bool(jnp.isfinite(got_o).all())
    for p in (0, 2):  # the live rows, as far as they are valid
        n = int(length[p])
        np.testing.assert_allclose(got_o[p, :n], want_o[p, :n], atol=2e-5 * float(jnp.abs(want_o).max()))
    assert float(jnp.abs(got_o[1]).max()) == 0.0  # the padding row reads out nothing
    np.testing.assert_allclose(got_S, want_S, atol=2e-5 * float(jnp.abs(want_S).max()))
    assert float(jnp.abs(got_S[1, 3] - S[1, 3]).max()) > 0.0
    untouched = jnp.array([0, 2, 4])  # slot 0 is where the padding row's -1 clips to
    assert float(jnp.abs((got_S - S)[1, untouched]).max()) == 0.0
    assert float(jnp.abs((got_S - S)[0]).max()) == 0.0  # the other layer


def test_chunk_kernel_with_no_live_row_moves_nothing():
    rows, slots, start, _ = _chunk_rows("mixed", 64)
    S = jax.random.normal(jax.random.key(3), kda.state_shapes(2, 5, H, D, K)[0])
    o, S1 = kda.chunk_update(S, 1, slots, start, jnp.zeros(3, jnp.int32), *rows,
                                  use_kernel=True, interpret=True)
    assert float(jnp.abs(o).max()) == 0.0 and float(jnp.abs(S1 - S).max()) == 0.0


def test_chunk_kernel_carries_the_state_across_token_tiles():
    """A row of 1,024 tokens is two grid steps of 512 a head tile: the
    state rides the output block from one to the next, the decay's mask
    counts from the tile's first token, and a ragged end falls in the
    second tile."""
    Lc = 1024
    _, _, _, g, beta = _rule_inputs(Lc, "mixed", seed=7)
    qkv = 3.0 * jax.random.normal(jax.random.key(7), (1, Lc, CONV))
    rows = (qkv, g[None], beta[None])
    S = jax.random.normal(jax.random.key(3), kda.state_shapes(1, 2, H, D, K)[0])
    meta = (0, jnp.array([1]), jnp.array([64]), jnp.array([Lc - 37]))
    want_o, want_S = kda.chunk_update(S, *meta, *rows, use_kernel=False)
    got_o, got_S = kda.chunk_update(S, *meta, *rows, use_kernel=True, interpret=True)
    np.testing.assert_allclose(got_o[0, :Lc - 37], want_o[0, :Lc - 37],
                               atol=2e-5 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(got_S, want_S, atol=2e-5 * float(jnp.abs(want_S).max()))


def test_chunk_update_is_chunk_update_heads_of_the_split():
    """The `jax.numpy` side of `chunk_update` is `qkv_heads` then
    `chunk_update_heads`: the model's q, k and v are that split."""
    (qkv, g, beta), slots, start, length = _chunk_rows("mixed", 64)
    S = jax.random.normal(jax.random.key(3), kda.state_shapes(2, 5, H, D, K)[0])
    q, k, v = kda.qkv_heads(qkv, H, D)
    np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), D ** -0.5, atol=1e-5)
    want = kda.chunk_update_heads(S, 1, slots, start, length, q, k, v, g, beta, use_kernel=False)
    got = kda.chunk_update(S, 1, slots, start, length, qkv, g, beta, use_kernel=False)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=0)


@pytest.mark.parametrize("n", [16, 64], ids=["forward-substitution", "joined-halves"])
def test_the_inverse_of_a_unit_lower_triangle(n):
    # entries as the chunk form's are: beta (k_i . k_j) with unit keys, here up to 0.4
    A = 0.1 * jnp.tril(jax.random.normal(jax.random.key(1), (3, n, n)), -1)
    inv = kda._unit_lower_inverse(A)
    want = jnp.broadcast_to(jnp.eye(n), A.shape)
    np.testing.assert_allclose(inv @ (jnp.eye(n) + A), want, atol=2e-5)
    np.testing.assert_allclose(jnp.triu(inv, 1), 0.0 * want, atol=0)


@pytest.mark.parametrize("n_prefill", [32, 27], ids=["whole-chunks", "ragged-tail"])
def test_kda_mixer_equals_the_family_reference_and_the_convolution_carries(n_prefill):
    """The program's mixer through its pools in chunks and then token by
    token against the benchmark family's plain mixer, over the family's
    own weights: the convolution's rows carried across every boundary (a
    mixer that dropped them would differ at each chunk's first 3 tokens)."""
    fam, m = _family(), _family_config()
    w = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(7))
    lp = {k: v[1] for k, v in w["kda"].items()}
    T = n_prefill + 5
    u = jax.random.normal(jax.random.key(8), (T, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.kda_mixer(u, lp, m)
        S, conv = (jnp.ones(sh) for sh in granite.state_shapes(CFG, 4))
        outs = []
        for start in range(0, n_prefill, 16):
            n = min(16, n_prefill - start)
            pf = granite._Pf(1, 16, None, jnp.array([1]), jnp.array([start]), jnp.array([n]), None)
            y, S, conv = granite._kda_mixer(lp, CFG, _pad(u[start:start + n], 16), 1, S, conv, None, pf)
            outs.append(y[:n])
        qkv = jnp.concatenate([u @ lp["wq"], u @ lp["wk"], u @ lp["wv"]], axis=-1)
        np.testing.assert_allclose(conv[1, 1].reshape(K - 1, CONV), qkv[n_prefill - 3:n_prefill], atol=1e-5)
        for t in range(n_prefill, T):
            dec = granite._Dec(4, None, None, jnp.arange(4) == 1, None, False)
            y, S, conv = granite._kda_mixer(lp, CFG, jnp.broadcast_to(u[t], (4, u.shape[1])), 1, S, conv, dec, None)
            outs.append(y[1][None])
    np.testing.assert_allclose(jnp.concatenate(outs), ref, atol=3e-4)
    assert float(jnp.abs(conv[0] - 1.0).max()) == 0.0 and float(jnp.abs(S[1, 0] - 1.0).max()) == 0.0


# ---------------------------------------------------------------- engine


def _engine(R=4, max_seq_len=512, num_blocks=64, **kw):
    kw.setdefault("sync_engine", True)
    cfg = EngineConfig(
        model="solar-tiny", dtype="float32", max_running_requests=R, block_size=16,
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


PROMPTS = {"one-chunk": 23, "two-chunks": 64, "three-chunks": 75}


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts of 1, 2
    and 3 chunks (a ragged tail on two) served concurrently, 12 greedy
    tokens each."""
    eng, ex = _engine()
    fam, m = _family(), _family_config()
    ex.params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    rng = np.random.default_rng(0)
    prompts = {rid: list(rng.integers(0, 512, n)) for rid, n in PROMPTS.items()}
    outs = {}
    for rid, p in prompts.items():
        eng.add_request(_req(rid, outs, p, max_new=12))
    _drain(eng)
    return eng, ex, fam, m, prompts, outs


# The family's draw puts a standing value of 50 through the delta rule's
# prediction (families/solar.py "The delta rule"): the chunk form and the
# recurrence then differ by float32's last places of 50, not of 1.
LOGPROB_ATOL = 3e-4


def _logprobs_of(logits, ids):
    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return np.asarray(lp[np.arange(len(ids)), np.asarray(ids)])


@pytest.mark.parametrize("rid", sorted(PROMPTS))
def test_engine_matches_the_dense_oracle_in_logits(served, rid):
    eng, ex, _, _, prompts, outs = served
    assert get_module(CFG) is granite and isinstance(eng.block_mgr, HybridBlockManager)
    p, seq = prompts[rid], prompts[rid] + outs[rid]
    assert len(outs[rid]) == 12
    logits = granite.forward_dense(ex.params, CFG, jnp.asarray(seq, jnp.int32)[None])[0]
    rows = logits[len(p) - 1:len(seq) - 1]
    assert [int(t) for t in jnp.argmax(rows, -1)] == outs[rid]
    np.testing.assert_allclose(outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=LOGPROB_ATOL)


@pytest.mark.parametrize("rid", sorted(PROMPTS))
def test_engine_matches_the_family_reference_in_logits(served, rid):
    """Prefill through the pools in 1, 2 and 3 chunks and then decode,
    against the reference's full forward pass (the recurrence,
    materialised attention, one expert at a time)."""
    _, ex, fam, m, prompts, outs = served
    p = prompts[rid]
    with jax.default_matmul_precision("highest"):
        seq = np.zeros((128,), np.int32)
        seq[:len(p) + 12] = p + outs[rid]
        idx = np.arange(len(p) - 1, len(p) + 11)
        rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
    np.testing.assert_allclose(outs[rid + "/lp"], _logprobs_of(rows, outs[rid]), atol=LOGPROB_ATOL)


def test_both_kinds_of_memory_are_counted_and_nothing_is_cached(served):
    eng, ex = served[0], served[1]
    text = eng.metrics.render()
    for name in ("xllm_engine_state_slots", "xllm_engine_state_slots_in_use",
                 "xllm_engine_state_pool_bytes", "xllm_engine_state_slot_bytes",
                 "xllm_engine_state_recomputes_total", "xllm_engine_cache_row_bytes",
                 "xllm_engine_moe_pairs_per_expert"):
        assert name in text, name
    slot = (H * D * D + (K - 1) * CONV) * 4 * CFG.num_state_layers
    assert ex.state_slot_bytes == slot == 19200
    assert ex.state_pool_bytes == 4 * slot
    # K and V of the ONE attention layer: 2 KV heads of 16 lanes, float32
    assert ex.cache_row_bytes == 2 * 1 * 2 * 16 * 4 and ex.block_size == 16
    assert ex.has_state_pool and ex.has_paged_cache and ex.slot_column
    assert ex.kernel_report()["state"] == "kda-xla"
    assert eng.prefix_cached_tokens == 0 and _nothing_held(eng)
    assert eng.block_mgr.take_cache_event().empty()  # nothing told to the fabric


def test_same_prompt_twice_is_recomputed_not_cached(served):
    eng, _, _, _, prompts, outs = served
    again = {}
    eng.add_request(_req("again", again, prompts["two-chunks"], max_new=12))
    _drain(eng)
    assert again["again"] == outs["two-chunks"]  # a reused, never-cleaned slot
    assert eng.prefix_cached_tokens == 0


def test_the_engine_emits_the_same_tokens_through_the_chunk_kernel(monkeypatch):
    """Three prompts of 1, 2 and 3 chunks served together (mixed steps,
    then decode steps) on a float32 engine whose KDA layers' chunks go
    through `kda_chunk_kernel` (interpret mode) emit the tokens the XLA
    form's engine emits, and the process's counter says which form each
    engine's programs hold."""
    from xllm_service_tpu.obs.startup import TIMELINE

    rng = np.random.default_rng(1)
    prompts = {rid: list(rng.integers(0, 512, n)) for rid, n in PROMPTS.items()}
    real, emitted = kda.chunk_update, {}
    for form in ("xla", "kernel"):
        if form == "kernel":
            monkeypatch.setattr(kda, "chunk_update", lambda *a, **kw: real(
                *a, **{**kw, "use_kernel": True, "interpret": True}))
        before = dict(TIMELINE.kda_chunk_forms)
        eng, _ = _engine(sync_engine=False)
        outs = emitted.setdefault(form, {})
        for rid, p in prompts.items():
            eng.add_request(_req(rid, outs, p, max_new=6))
        _drain(eng)
        other = "xla" if form == "kernel" else "kernel"
        assert eng.mixed_steps > 0
        assert TIMELINE.kda_chunk_forms[form] > before[form]
        assert TIMELINE.kda_chunk_forms[other] == before[other]
        series = [line for line in eng.metrics.render().splitlines()
                  if line.startswith("xllm_engine_kda_chunk_kernel_total{")]
        assert len(series) == 2 and any(f'form="{form}"' in line for line in series)
    for rid in prompts:
        assert emitted["kernel"][rid] == emitted["xla"][rid] and len(emitted["xla"][rid]) == 6


def _two_warm_rows(cfg, seed):
    """Pools of 4 slots and 12 blocks with two sequences of 32 tokens
    prefilled into rows 0 and 2, and a step's halves: decode tokens for
    those rows, one chunk of 20 tokens for slot 1."""
    params = granite.init_params(cfg, jax.random.key(seed), jnp.float32)
    state, conv = (jnp.zeros(sh, jnp.float32) for sh in granite.state_shapes(cfg, 4))
    kv = jnp.zeros((cfg.num_attention_layers, 12, 2, 16, 16), jnp.float32)
    rng = np.random.default_rng(seed)
    warm = jnp.asarray(rng.integers(0, 512, (2, 32)), jnp.int32)
    _, kc, vc = granite.prefill_batch_step(
        params, cfg, (kv, state), (kv, conv), warm, jnp.zeros(2, jnp.int32),
        jnp.full(2, 32, jnp.int32), jnp.array([[1, 2, 3, 1], [4, 5, 6, 3]], jnp.int32))
    dec = (jnp.asarray(rng.integers(0, 512, 4), jnp.int32), jnp.array([32, 0, 32, 0], jnp.int32),
           jnp.array([[1, 2, 3], [0, 0, 0], [4, 5, 6], [0, 0, 0]], jnp.int32),
           jnp.array([True, False, True, False]))
    pf = (jnp.asarray(rng.integers(0, 512, (1, 32)), jnp.int32), jnp.zeros(1, jnp.int32),
          jnp.array([20], jnp.int32), jnp.array([[7, 8, 0, 2]], jnp.int32))
    return params, kc, vc, dec, pf


def test_mixed_step_equals_its_split_steps():
    params, kc, vc, dec, pf = _two_warm_rows(CFG, 3)
    d_logits, p_logits, km, vm = granite.mixed_step(params, CFG, kc, vc, *dec, *pf)
    d_ref, ks, vs = granite.decode_step(params, CFG, kc, vc, *dec)
    p_ref, ks, vs = granite.prefill_batch_step(params, CFG, ks, vs, *pf)
    live = jnp.array([0, 2])
    np.testing.assert_allclose(d_logits[live], d_ref[live], atol=1e-5)
    np.testing.assert_allclose(p_logits, p_ref, atol=1e-5)
    for a, b in zip(jax.tree.leaves((km, vm)), jax.tree.leaves((ks, vs))):
        paged = a.ndim == 5 and a.shape[1] == 12  # block 0 takes the padding rows' writes
        np.testing.assert_allclose(a[:, 1:] if paged else a, b[:, 1:] if paged else b, atol=1e-5)
    assert float(jnp.abs(km[1][:, 3]).max()) == 0.0  # the fourth slot: never touched


@pytest.mark.parametrize("flavour", ["decode", "mixed"])
def test_a_pattern_that_repeats_is_one_scan_over_its_period(flavour, monkeypatch):
    """`A K K K` twice is ONE scan over the period (each kind's layer body
    once in the program), and gives what four runs one after the other
    give: logits and all four pools. A pattern with no repeat (Granite's)
    keeps its runs."""
    two = dataclasses.replace(CFG, num_layers=8, layer_types=CFG.layer_types * 2)
    period, reps = granite._period(granite._segments(two))
    assert reps == 2 and [(s.kind, s.first, s.kind_first, s.n) for s in period] == [
        ("attention", 0, 0, 1), ("kda", 1, 0, 3)]
    assert granite._period(granite._segments(CFG))[1] == 1
    g = granite._segments(get_model_config("granite-4.0-h-small"))
    assert granite._period(g) == (g, 1)

    def run():
        params, kc, vc, dec, pf = _two_warm_rows(two, 5)
        if flavour == "decode":
            return granite.decode_step(params, two, kc, vc, *dec)
        return granite.mixed_step(params, two, kc, vc, *dec, *pf)

    scanned = run()
    monkeypatch.setattr(granite, "_period", lambda segs: (segs, 1))
    for a, b in zip(jax.tree.leaves(scanned), jax.tree.leaves(run())):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.abs(scanned[-2][1][3:]).max()) > 0.0  # the second period's state layers ran


# ------------------------------------------------------ the share and model


def test_the_holders_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5 and 6-7 of 8 at the tiny size (the benchmark
    cuts 320 into sixteen spans of 20 the same way): the holders' routed
    parts plus the shared expert counted once are the uncut layer, in the
    program's expert block and in the family's reference alike."""
    whole = dataclasses.replace(CFG, experts_held=())
    params = granite.init_params(whole, jax.random.key(1), jnp.float32)
    lp = {k: v[2] for k, v in params["layers"].items()}
    u = jax.random.normal(jax.random.key(2), (24, CFG.hidden_size))
    full = llama._mlp_block(lp, whole, u)
    shared = llama._shared_experts(lp, u)
    parts = []
    for first in (0, 2, 4, 6):
        held = dataclasses.replace(CFG, experts_held=(first, 2))
        cut = {**lp, **{k: lp[k][first:first + 2] for k in llama.EXPERT_LEAVES}}
        parts.append(llama._mlp_block(cut, held, u) - shared)
    assert sum(float(jnp.abs(p).max()) > 1e-3 for p in parts) >= 3
    np.testing.assert_allclose(sum(parts) + shared, full, atol=1e-5)
    fam, m = _family(), _family_config(whole)
    leaves = dict(params["layers"])
    with jax.default_matmul_precision("highest"):
        ref = fam.expert_layer(u, leaves, 2, m)
        spans = [fam.expert_layer(u, leaves, 2, m, shared=False, span=(f, 2)) for f in (0, 2, 4, 6)]
        ref_shared = fam.expert_layer(u, leaves, 2, m, span=(0, 2)) - spans[0]
    np.testing.assert_allclose(sum(spans) + ref_shared, ref, atol=1e-5)
    np.testing.assert_allclose(ref, full, atol=1e-4)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = get_model_config("solar-open2-250b")
    assert get_module(c) is granite
    assert c.layer_types == ("attention", "kda", "kda", "kda") * 2
    assert [(s.kind, s.first, s.kind_first, s.n) for s in granite._segments(c)] == [
        ("attention", 0, 0, 1), ("kda", 1, 0, 3), ("attention", 4, 1, 1), ("kda", 5, 3, 3)]
    assert (c.hidden_size, c.kda_n_heads, c.kda_d_head, c.kda_d_conv, c.kda_gate_rank) \
        == (4096, 64, 128, 4, 128)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.attn_gate) == (64, 8, 128, True)
    assert (c.num_experts, c.held_experts, c.num_experts_per_tok, c.moe_intermediate_size) \
        == (320, (0, 20), 8, 1280)
    assert c.n_shared_experts == 1 and c.kda_conv_dim == 24576 and not c.tie_word_embeddings
    assert abs(approx_param_count(c) / 3898.8e6 - 1) < 1e-3
    state, conv = granite.state_shapes(c, 96)
    assert state == (6, 96, 64, 128, 128) and conv == (6, 96, 3 * 24576)
    assert sum(math.prod(sh) for sh in granite.state_shapes(c, 1)) * 4 == 26_935_296
    assert granite.cache_row_dims(c) == (8, 128) and c.num_attention_layers == 2


def test_the_state_layer_properties_name_a_kind_not_mamba():
    """`has_state_pool`, `num_state_layers` and `state_shapes` are written
    for "a layer kind with a state slot": both hybrids answer them, and
    the Granite preset's numbers are what PR 42 measured."""
    g, s = get_model_config("granite-4.0-h-small"), get_model_config("solar-open2-250b")
    assert (g.state_layer_kind, g.num_state_layers, g.num_attention_layers) == ("mamba", 9, 1)
    assert (s.state_layer_kind, s.num_state_layers, s.num_attention_layers) == ("kda", 6, 2)
    assert g.has_state_pool and s.has_state_pool and g.has_paged_cache and s.has_paged_cache
    assert granite.state_shapes(g, 64) == ((9, 64, 64, 128, 128), (9, 64, 3 * 8448))
    assert sum(math.prod(sh) for sh in granite.state_shapes(g, 1)) * 4 == 38_661_120
    assert abs(approx_param_count(g) / 4757e6 - 1) < 0.01
    plain = get_model_config("llama3-tiny")
    assert (plain.state_layer_kind, plain.num_state_layers, plain.has_state_pool) == ("", 0, False)
    both = dataclasses.replace(CFG, layer_types=("mamba", "kda", "attention", "kda"))
    with pytest.raises(ValueError, match="two state-layer kinds"):
        both.state_layer_kind
    with pytest.raises(ValueError, match="layer_types"):
        granite._segments(dataclasses.replace(CFG, layer_types=("kda", "ring", "kda", "kda")))


def test_every_state_layer_kind_is_one_row_of_one_table():
    """What the stack, the parameter count and the weight quantizer ask of a
    layer kind with a state slot is one row a kind (granite.STATE_KINDS),
    not a branch a question: mixer, pool shapes, kernel eligibility and the
    parameter stack; the executor quantizes the stacks MIXER_STACKS names."""
    import inspect

    from xllm_service_tpu.models import configs

    assert tuple(granite.STATE_KINDS) == configs.STATE_LAYER_KINDS == tuple(configs._STATE_MIXER_PARAMS)
    assert set(granite.MIXER_STACKS) == set(granite.STATE_KINDS) | {"attention", "window"}
    # a layer kind is its mixers (configs.LAYER_MIXERS): the five of one mixer and a stack of its own,
    # the parallel kind of two, and the sparse kind of the attention mixer over the attention stack
    assert set(granite.MIXER_REGIONS) == set(configs.LAYER_MIXERS) == set(granite.MIXER_STACKS) | {
        "parallel", "sparse"}
    assert all(set(mx) <= set(granite.MIXER_STACKS) for mx in configs.LAYER_MIXERS.values())
    assert all(len(row) == 4 and all(callable(f) for f in row) for row in granite.STATE_KINDS.values())
    for name in ("solar-tiny", "granite-tiny"):
        c = get_model_config(name)
        kind = c.state_layer_kind
        params = granite.init_params(c, jax.random.key(0), jnp.float32)
        assert set(params) == {"embed", "final_norm", "layers", "attn", granite.MIXER_STACKS[kind]} \
            | (set() if c.tie_word_embeddings else {"lm_head"})
        state, conv = granite.state_shapes(c, 3)
        assert state[:2] == conv[:2] == (c.num_state_layers, 3)
        assert granite.state_route(c, jnp.zeros(state, jnp.float32)) == f"{kind}-xla"
        stack = params[granite.MIXER_STACKS[kind]]
        matrices = sum(v.size for k, v in stack.items() if v.ndim == 3) // c.num_state_layers
        assert matrices == configs._STATE_MIXER_PARAMS[kind](c)
    assert "MIXER_STACKS" in inspect.getsource(ModelExecutor._quantize_weights)


def test_a_kda_stack_is_low_rank_pairs_and_nothing_else():
    """`kda_gate_rank` is the rank of the decay's and the gate's pairs (the
    published model has `kda_use_full_proj: false`); there is no "0 = full"."""
    with pytest.raises(ValueError, match="kda_gate_rank > 0"):
        granite.init_params(dataclasses.replace(CFG, kda_gate_rank=0), jax.random.key(0), jnp.float32)
    kda_stack = granite.init_params(CFG, jax.random.key(0), jnp.float32)["kda"]
    r = CFG.kda_gate_rank
    assert kda_stack["w_f1"].shape[1:] == kda_stack["w_g1"].shape[1:] == (CFG.hidden_size, r)
    assert kda_stack["w_f2"].shape[1:] == kda_stack["w_g2"].shape[1:] == (r, CFG.kda_d_inner)


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens"),
    (dict(num_host_blocks=8), "prefix cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(checkpoint_path="/nowhere"), "checkpoint_path"),
    (dict(tp_size=2), "tp_size/ep_size/sp_size/dp_size"),
], ids=["speculation", "prefix-tiers", "int8-cache", "checkpoint", "sharded-state"])
def test_named_refusals_at_build(kw, match):
    from xllm_service_tpu.ops import attention

    try:
        with pytest.raises(StateFamilyUnsupported, match=match):
            _engine(**kw)
    finally:  # a build at tp > 1 declares its mesh for this thread before it refuses
        attention.set_shard_context(None)


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


def test_pools_are_sized_one_after_the_other():
    eng, ex = _engine(R=4, num_blocks=0)  # auto-size against the nominal 16 GiB
    c = ex.cfg
    block = 2 * c.num_attention_layers * 16 * 2 * 16 * 4
    left = 16 * 2**30 * 0.9 - approx_param_count(c) * 4 - ex.state_pool_bytes
    assert ex.num_blocks == int(left / 2 // block)
    assert ex.prefill_buckets == [32]
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
    assert {"wq", "wk", "wv", "w_f1", "w_f2", "w_g1", "w_g2", "w_beta"} <= set(shapes["kda"])
    assert "w_ogate" in shapes["attn"] and "lm_head" in shapes
