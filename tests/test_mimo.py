"""The window family on the normal path (models/granite.py's "window"
kind, a second paged pool and block table, runtime/block_manager.py
WindowBlockManager), on the CPU with `mimo-tiny` (2 full + 4 window layers,
a window of 8 over blocks of 4, 4 of 8 sigmoid top-2 experts held, a dense
first layer): prefill-then-decode and the mixed step agree with the
benchmark family's plain reference in float32 so tightly that a window off
by one, a dropped sink, a swapped theta, a left-out value scale or a
selection bias used as a weight each fail; the holders' shares of a layer
add up to the uncut layer; a sequence's window blocks stay bounded while
its full table grows, and every way out returns both pools; what is not
built is refused by name."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.models import get_module, granite, llama
from xllm_service_tpu.models.configs import approx_param_count, get_model_config
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import (
    OutOfBlocksError,
    WindowBlockManager,
    WindowFamilyUnsupported,
)
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("mimo-tiny")
BS, W = 4, CFG.sliding_window
ATOL = 2e-5  # float32, logits of about 1


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "mimo-tiny", "family": "mimo"})


def _family_config(c=CFG):
    pattern = [int(k == "window") for k in c.layer_types]
    freq = [int(l >= c.first_k_dense_replace) for l in range(c.num_layers)]
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim, "v_head_dim": c.value_head_dim,
        "swa_num_attention_heads": c.num_heads, "swa_num_key_value_heads": c.window_kv_heads,
        "swa_head_dim": c.head_dim, "swa_v_head_dim": c.value_head_dim,
        "sliding_window": c.sliding_window, "sliding_window_size": c.sliding_window,
        "rope_theta": c.rope_theta, "swa_rope_theta": c.window_rope_theta,
        "partial_rotary_factor": 0.334, "attention_value_scale": c.attn_value_scale,
        "hybrid_layer_pattern": pattern, "moe_layer_freq": freq,
        "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
        "attention_bias": False, "layernorm_epsilon": c.rms_norm_eps,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_routed_experts": c.held_experts[1], "n_routed_experts_published": c.num_experts,
        "experts_held": list(c.held_experts), "n_shared_experts": None,
        "num_experts_per_tok": c.num_experts_per_tok, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "tie_word_embeddings": False,
        "max_position_embeddings": c.max_position_embeddings,
    }


def test_the_family_file_reads_the_preset_back():
    fam, m = _family(), _family_config()
    assert fam.model_config("mimo-tiny", m) == CFG
    assert get_module(CFG) is granite and CFG.rotary_dim == int(0.334 * 24) == 8
    want = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    have = jax.eval_shape(lambda: fam.make_weights(m, jax.random.key(0), jnp.float32))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(want) == shape(have)


# ---------------------------------------------------- steps vs the reference


class _Seq:
    """One sequence's two tables over hand-made pools, the window table
    slid as the engine slides it (WindowBlockManager)."""

    def __init__(self, mgr, blocks, CB=16):
        self.mgr, self.ids, self.lo, self.CB = mgr, mgr.allocate(blocks), 0, CB
        self.full = np.zeros((CB,), np.int32)
        self.full[:blocks] = self.ids
        self.win = np.zeros((CB,), np.int32)

    def table(self, first, end):
        lo = max(0, first - W + 1) // BS
        self.lo = self.mgr.slide(self.ids, self.lo, lo, (end - 1) // BS + 1, self.win)
        return np.concatenate([self.full, self.win])


def _pools(cfg=CFG, blocks=40, window_blocks=12):
    (kf, vf), (kw, vw) = granite.pool_shapes(cfg, blocks, window_blocks, BS)
    z = lambda s: jnp.zeros(s, jnp.float32)
    return (z(kf), z(kw)), (z(vf), z(vw)), WindowBlockManager(blocks, window_blocks, BS)


def _serve(params, cfg, toks, n_prefill, chunk=8):
    """Logits of every position from n_prefill - 1 on: the prompt in
    chunks, then token by token, through the step functions (each one
    program of this configuration: `cfg` is closed over)."""
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, cfg, K, V, *a))
    decode = jax.jit(lambda p, K, V, *a: granite.decode_step(p, cfg, K, V, *a))
    K, V, mgr = _pools(cfg)
    seq, outs = _Seq(mgr, -(-len(toks) // BS)), []
    for pos in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - pos)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n] = toks[pos:pos + n]
        lg, K, V = prefill(
            params, K, V, jnp.asarray(ids), jnp.asarray([pos]), jnp.asarray([n]),
            jnp.asarray(seq.table(pos, pos + n))[None])
    outs.append(lg[0])
    for t in range(n_prefill, len(toks)):
        tab = np.zeros((2, 2 * seq.CB), np.int32)
        tab[1] = seq.table(t, t + 1)
        lg, K, V = decode(
            params, K, V, jnp.asarray([0, toks[t]]), jnp.asarray([0, t]), jnp.asarray(tab),
            jnp.asarray([False, True]))
        outs.append(lg[1])
    return jnp.stack(outs), mgr


@pytest.fixture(scope="module")
def seeded():
    fam, m = _family(), _family_config()
    params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    toks = np.asarray(jax.random.randint(jax.random.key(6), (45,), 0, CFG.vocab_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.forward_logits(params, m, jnp.asarray(toks, jnp.int32), jnp.arange(45))
    return fam, m, params, toks, ref


@pytest.mark.parametrize("n_prefill", [8, 21, 32], ids=["one-chunk", "ragged", "four-chunks"])
def test_prefill_then_decode_equals_the_family_reference(seeded, n_prefill):
    _, _, params, toks, ref = seeded
    got, mgr = _serve(params, CFG, toks, n_prefill)
    np.testing.assert_allclose(got, ref[n_prefill - 1:], atol=ATOL)
    # the window pool held the window and no more: 3 blocks cover 8 positions
    assert mgr.window_blocks_live <= -(-(W - 1) // BS) + 1 and mgr.window_blocks_freed >= 6
    np.testing.assert_allclose(
        granite.forward_dense(params, CFG, jnp.asarray(toks)[None])[0], ref, atol=ATOL)


BROKEN = {
    "window-7": dict(sliding_window=W - 1),
    "window-9": dict(sliding_window=W + 1),
    "no-sink": dict(window_sink=False),
    "theta-swapped": dict(rope_theta=CFG.window_rope_theta, window_rope_theta=CFG.rope_theta),
    "full-rotary": dict(rotary_dim=CFG.head_dim),
    "unscaled": dict(attn_value_scale=1.0),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_each_departure_from_the_equations_fails_the_comparison(seeded, fault):
    """The comparison is tight enough to see each one: the program with one
    field of its configuration wrong is off by 100x the tolerance."""
    _, _, params, toks, ref = seeded
    broken = dataclasses.replace(CFG, **BROKEN[fault])
    got, _ = _serve(params, broken, toks, 21)
    assert float(jnp.abs(got - ref[20:]).max()) > 100 * ATOL


def test_the_selection_bias_selects_and_does_not_weigh(seeded):
    fam, m, params, _, _ = seeded
    lp = {k: v[1] for k, v in params["layers"].items() if k not in ("attn_norm", "mlp_norm")}
    u = jax.random.normal(jax.random.key(3), (40, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.expert_layer(u, params["layers"], 1, m)
        np.testing.assert_allclose(llama._mlp_block(lp, CFG, u), ref, atol=ATOL)
        chosen, w = llama.moe_route(lp, CFG, u)
        unbiased, _ = llama.moe_route({**lp, "router_bias": 0 * lp["router_bias"]}, CFG, u)
        assert bool((jnp.sort(chosen) != jnp.sort(unbiased)).any())  # the bias moves the choice
        sc = jax.nn.sigmoid(u @ lp["router"])
        own = jnp.take_along_axis(sc, chosen, axis=-1)
        np.testing.assert_allclose(w, own / own.sum(-1, keepdims=True), atol=1e-6)
        as_weight = jnp.take_along_axis(sc + lp["router_bias"], chosen, axis=-1)
        assert float(jnp.abs(w - as_weight / as_weight.sum(-1, keepdims=True)).max()) > 1e-4


def test_mixed_step_equals_its_split_steps(seeded):
    _, _, params, toks, ref = seeded
    K, V, mgr = _pools()
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, CFG, K, V, *a))
    a, b = _Seq(mgr, 12), _Seq(mgr, 6)
    other = np.asarray(jax.random.randint(jax.random.key(8), (24,), 0, CFG.vocab_size))
    for seq, ids in ((a, toks), (b, other)):  # 16 tokens of each, two chunks
        for pos in (0, 8):
            _, K, V = prefill(
                params, K, V, jnp.asarray(ids[None, pos:pos + 8]), jnp.asarray([pos]),
                jnp.asarray([8]), jnp.asarray(seq.table(pos, pos + 8))[None])
    dec_tab = np.zeros((2, 32), np.int32)
    dec_tab[0] = a.table(16, 17)
    dec = (jnp.asarray([toks[16], 0]), jnp.asarray([16, 0]), jnp.asarray(dec_tab),
           jnp.asarray([True, False]))
    pf = (jnp.asarray(other[None, 16:24]), jnp.asarray([16]), jnp.asarray([8]),
          jnp.asarray(b.table(16, 24))[None])
    step = lambda fn: jax.jit(lambda p, K, V, *a: fn(p, CFG, K, V, *a))
    d_logits, p_logits, Km, Vm = step(granite.mixed_step)(params, K, V, *dec, *pf)
    d_ref, Ks, Vs = step(granite.decode_step)(params, K, V, *dec)
    p_ref, Ks, Vs = step(granite.prefill_batch_step)(params, Ks, Vs, *pf)
    np.testing.assert_allclose(d_logits, d_ref, atol=ATOL)
    np.testing.assert_allclose(p_logits, p_ref, atol=ATOL)
    np.testing.assert_allclose(d_logits[0], ref[16], atol=ATOL)
    for got, want in zip(jax.tree.leaves((Km, Vm)), jax.tree.leaves((Ks, Vs))):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], atol=1e-6)  # block 0 is garbage


def test_the_dense_prefix_and_the_kinds_are_segments_of_one_scan():
    segs = granite._segments(CFG)
    assert [(s.kind, s.n, s.dense) for s in segs] == [
        ("attention", 1, True), ("window", 2, False), ("attention", 1, False), ("window", 2, False)]
    assert granite._period(segs) == (segs, 1)  # the dense layer breaks the period
    cut = get_model_config("mimo-v2-flash")
    assert [(s.kind, s.n, s.dense, s.kind_first) for s in granite._segments(cut)] == [
        ("attention", 1, True, 0), ("window", 5, False, 0), ("attention", 1, False, 1)]
    # a stack without a dense prefix has the segments it had
    solar = get_model_config("solar-open2-250b")
    assert all(not s.dense for s in granite._segments(solar))
    assert granite._period(granite._segments(solar))[1] == 2


# ------------------------------------------------------------- the holders


def test_the_holders_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of 8 at the tiny size (the benchmark cuts 256
    into sixteen spans of 16 the same way): with no shared expert the
    holders' parts ARE the uncut layer, in the program's expert block and
    in the family's reference alike."""
    whole = dataclasses.replace(CFG, experts_held=())
    params = granite.init_params(whole, jax.random.key(1), jnp.float32)
    lp = {k: v[2] for k, v in params["layers"].items() if k not in ("attn_norm", "mlp_norm")}
    u = jax.random.normal(jax.random.key(2), (24, CFG.hidden_size))
    full = llama._mlp_block(lp, whole, u)
    parts = []
    for first in (0, 4):
        held = dataclasses.replace(CFG, experts_held=(first, 4))
        cut = {**lp, **{k: lp[k][first:first + 4] for k in llama.EXPERT_LEAVES}}
        parts.append(llama._mlp_block(cut, held, u))
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    np.testing.assert_allclose(sum(parts), full, atol=1e-5)
    fam, m = _family(), _family_config(whole)
    with jax.default_matmul_precision("highest"):
        ref = fam.expert_layer(u, params["layers"], 2, m)
        spans = [fam.expert_layer(u, params["layers"], 2, m, span=(f, 4)) for f in (0, 4)]
    np.testing.assert_allclose(sum(spans), ref, atol=1e-5)
    np.testing.assert_allclose(ref, full, atol=1e-4)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = get_model_config("mimo-v2-flash")
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.window_kv_heads) == (4096, 64, 4, 8)
    assert (c.head_dim, c.value_head_dim, c.rotary_dim, c.sliding_window) == (192, 128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok) \
        == (16384, 2048, 256, 8)
    assert (c.num_attention_layers, c.num_window_layers, c.first_k_dense_replace) == (2, 5, 1)
    assert c.held_experts == (0, 16) and c.vocab_size * 8 == 152576
    assert approx_param_count(c) == 3_429_892_096  # 6.86 GB in bfloat16
    assert granite.key_lanes(c) == 256 and granite.key_lanes(CFG) == 24
    (kf, vf), (kw, vw) = granite.pool_shapes(c, 100, 20, 128)
    assert (kf, vf) == ((2, 100, 4, 128, 256), (2, 100, 4, 128, 128))
    assert (kw, vw) == ((5, 20, 8, 128, 256), (5, 20, 8, 128, 128))


# ------------------------------------------------------------------ engine


def _engine(R=4, max_seq_len=256, num_blocks=200, model="mimo-tiny", **kw):
    kw.setdefault("sync_engine", True)
    cfg = EngineConfig(
        model=model, dtype="float32", max_running_requests=R, block_size=BS,
        num_blocks=num_blocks, max_seq_len=max_seq_len, max_prefill_tokens=16,
        prefill_buckets=[16], **kw,
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


def _drain(eng, steps=3000, each=None):
    for _ in range(steps):
        if not eng.has_work():
            return
        eng.step()
        if each is not None:
            each()
    raise AssertionError("the engine did not drain")


def _nothing_held(eng):
    mgr = eng.block_mgr
    return (len(eng._free_slots) == eng.R and mgr.num_referenced_blocks == 0
            and mgr.window.num_referenced_blocks == 0 and mgr.window_blocks_live == 0)


PROMPTS = {"one-chunk": 13, "two-chunks": 32, "five-chunks": 75}


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts of 1, 2
    and 5 chunks (a ragged tail on two) served concurrently; the shortest
    decodes to ten times the window."""
    eng, ex = _engine()
    fam, m = _family(), _family_config()
    ex.params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    rng = np.random.default_rng(0)
    prompts = {rid: list(rng.integers(0, 512, n)) for rid, n in PROMPTS.items()}
    outs, seen = {}, {"window": [], "full": []}

    def each():
        seq = next((s for s in eng._running.values() if s.req.request_id == "one-chunk"), None)
        if seq is not None:
            live = sum(1 for b in seq.block_ids if b in eng.block_mgr._beside)
            seen["window"].append(live)
            seen["full"].append(len(seq.block_ids))

    for rid, p in prompts.items():
        eng.add_request(_req(rid, outs, p, max_new=10 * W if rid == "one-chunk" else 12))
    _drain(eng, each=each)
    return eng, ex, fam, m, prompts, outs, seen


@pytest.mark.parametrize("rid", sorted(PROMPTS))
def test_engine_matches_the_family_reference_in_logits(served, rid):
    eng, ex, fam, m, prompts, outs, _ = served
    assert isinstance(eng.block_mgr, WindowBlockManager)
    p, out = prompts[rid], outs[rid]
    assert len(out) == (10 * W if rid == "one-chunk" else 12)
    with jax.default_matmul_precision("highest"):
        seq = np.zeros((128,), np.int32)
        seq[:len(p) + len(out)] = p + out
        idx = np.arange(len(p) - 1, len(p) + len(out) - 1)
        rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
    assert [int(t) for t in jnp.argmax(rows, -1)] == out
    lp = jax.nn.log_softmax(rows, axis=-1)[np.arange(len(out)), np.asarray(out)]
    np.testing.assert_allclose(outs[rid + "/lp"], lp, atol=ATOL)


def test_window_blocks_stay_bounded_while_the_full_table_grows(served):
    eng, ex, _, _, _, _, seen = served
    bound = -(-(W - 1) // BS) + 1  # the blocks 8 positions can straddle
    assert max(seen["window"]) <= bound and seen["window"][-1] >= 2
    assert seen["full"][-1] >= seen["full"][0] + 10 * W // BS - 1  # one block every 4 tokens
    assert eng.block_mgr.window_blocks_freed > 10 * W // BS
    assert _nothing_held(eng) and eng.prefix_cached_tokens == 0
    # the window pool is sized by what its sequences can hold at once
    assert ex.window_blocks == 1 + ex.R * (bound + 1) + 2 * (16 // BS)
    assert eng.block_mgr.window.num_blocks == ex.window_blocks
    assert ex.kernel_report()["window"] == "window-xla" and ex.window_tables
    # one context bucket: every step takes the whole table
    assert ex._ctx_bucket(1) == ex.max_blocks_per_seq == 64
    # K and V of the 2 full layers: 1 KV head of 24 + 16 lanes, float32
    assert ex.cache_row_bytes == 2 * 1 * (24 + 16) * 4
    text = eng.metrics.render()
    for series in ('xllm_engine_kv_blocks_live{pool="full"} 0', 'xllm_engine_kv_blocks_live{pool="window"} 0',
                   'xllm_engine_kv_block_bytes{pool="window"} %d' % (4 * 2 * BS * (24 + 16) * 4),
                   "xllm_engine_window_blocks_freed_total %d" % eng.block_mgr.window_blocks_freed):
        assert series in text, series


def test_same_prompt_twice_is_recomputed_not_cached(served):
    eng, _, _, _, prompts, outs, _ = served
    again = {}
    eng.add_request(_req("again", again, prompts["two-chunks"], max_new=12))
    _drain(eng)
    assert again["again"] == outs["two-chunks"] and eng.prefix_cached_tokens == 0
    assert _nothing_held(eng)


def test_abort_and_preemption_return_both_pools():
    """A cancelled sequence, a preempted one and the one it was preempted
    for all give back every block of both pools; the preempted sequence
    resumes by recomputing and emits what an undisturbed run emits."""
    prompt = list(np.random.default_rng(5).integers(1, 400, 21))
    solo = {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("solo", solo, prompt, max_new=30, offline=True))
    _drain(eng)
    outs = {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("victim", outs, prompt, max_new=30, offline=True))
    eng.add_request(_req("gone", outs, prompt[:9], max_new=200, offline=True))
    for _ in range(12):
        eng.step()
    assert eng.block_mgr.window_blocks_live >= 2
    eng.cancel("gone")
    for i in range(2):
        eng.add_request(_req(f"on{i}", outs, prompt[:7 + i], max_new=6))
    _drain(eng)
    assert eng.preemptions >= 1 and outs["victim"] == solo["solo"]
    assert "gone" not in outs.get("_finished", []) or len(outs["gone"]) < 200
    assert _nothing_held(eng)


def test_a_window_pool_that_runs_out_says_so():
    mgr = WindowBlockManager(16, 3, BS)
    ids, row = mgr.allocate(6), np.zeros((8,), np.int32)
    assert mgr.slide(ids, 0, 0, 2, row) == 0 and list(row[:3]) == [row[0], row[1], 0]
    with pytest.raises(OutOfBlocksError):
        mgr.slide(ids, 0, 0, 4, row)
    lo = mgr.slide(ids, 0, 2, 4, row)  # behind first, then ahead
    assert lo == 2 and list(row[:2]) == [0, 0] and row[2] and row[3]
    assert mgr.window_blocks_freed == 2 and mgr.window_blocks_live == 2
    mgr.free(ids)
    assert mgr.window_blocks_live == 0 and mgr.window.num_referenced_blocks == 0
    assert mgr.num_referenced_blocks == 0 and mgr.match_prefix([1, 2, 3, 4]) == (0, [])


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens"),
    (dict(num_host_blocks=8), "prefix cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(checkpoint_path="/nowhere"), "checkpoint_path"),
    (dict(ep_size=2), "tp_size/ep_size/sp_size/dp_size"),
], ids=["speculation", "prefix-tiers", "int8-cache", "checkpoint", "sharded"])
def test_named_refusals_at_build(kw, match):
    with pytest.raises(WindowFamilyUnsupported, match=match):
        _engine(**kw)


def test_named_refusals_at_the_request_and_an_inert_prefix_half():
    eng, ex = _engine(R=2)
    with pytest.raises(WindowFamilyUnsupported, match="PD handoff"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(WindowFamilyUnsupported, match="PD handoff"):
        eng.import_sequence(_req("pd", {}, [1, 2, 3]), None)
    with pytest.raises(WindowFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])
    assert eng.block_mgr.take_cache_event().empty()
    with pytest.raises(ValueError, match="window layers' pools ride"):
        granite.init_params(dataclasses.replace(CFG, layer_types=("mamba", "window") * 3),
                            jax.random.key(0), jnp.float32)


def test_a_sink_takes_the_verify_shapes_off_the_multi_query_kernel(monkeypatch):
    """The multi-query kernel has no sink logit: over a pool whose layers
    carry one, verify shapes go as the chunks do (the flash kernel, which
    has), and the decision says so to the dispatcher and the report alike."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    k = jax.ShapeDtypeStruct((2, 4, 2, 16, 256), jnp.bfloat16)
    plain = attention.attention_routes(k, 4, 256)
    sink = attention.attention_routes(k, 4, 256, sinks=True)
    assert plain.verify and plain.report()["mq"] == "mq"
    assert not sink.verify and sink.prefill and sink.report()["mq"] == "flash"
    assert sink.bounded_by_context and sink.report()["mixed"] == "paged+flash"


def test_the_parameter_tree_has_a_replicated_rule_for_every_leaf():
    from xllm_service_tpu.parallel.mesh import build_mesh
    from xllm_service_tpu.parallel.sharding import param_shardings

    rules = param_shardings(CFG, build_mesh(tp=1))
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(rules)
    for leaf, rule in zip(jax.tree.leaves(shapes), jax.tree.leaves(rules)):
        assert len(rule.spec) == leaf.ndim and not any(rule.spec)
    assert set(shapes) == {"embed", "final_norm", "lm_head", "layers", "dense_layers", "attn", "attn_w"}
    assert shapes["layers"]["router"].shape[0] == CFG.num_layers - 1 == shapes["layers"]["w_gate"].shape[0]
    assert shapes["attn_w"]["sink"].shape == (4, CFG.num_heads)


# ------------------------------------------------- kernels, interpreted


def _kernel_case(seed, R=3, Hq=8, Hkv=2, D=256, Dv=128, BS=16, MB=8, N=40):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    k, v = f(N, Hkv, BS, D), f(N, Hkv, BS, Dv)
    bt = jnp.asarray(rng.choice(np.arange(1, N), size=(R, MB), replace=False).astype(np.int32))
    sinks = jnp.asarray(rng.uniform(1.0, 4.0, Hq), jnp.float32)
    return f, k, v, bt, sinks


@pytest.mark.parametrize("window,sink", [(0, False), (24, True), (0, True), (24, False)],
                         ids=["plain", "window-sink", "sink", "window"])
def test_decode_kernel_with_a_sink_and_narrow_value_rows(window, sink):
    """K rows of 256 lanes, V rows of 128, a sink logit a head, a window:
    the Pallas decode kernel (interpreted) against its jax.numpy twin."""
    from xllm_service_tpu.ops.pallas.paged_attention import paged_attention_kernel

    f, k, v, bt, sinks = _kernel_case(3)
    q = f(3, 8, 256)
    seq_lens = jnp.asarray([5, 0, 100], jnp.int32)
    kw = {"window": window, "sinks": sinks if sink else None}
    ref = attention.paged_attention_gather(q, k, v, bt, seq_lens, 0.07, **kw)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, 0.07, interpret=True, **kw)
    assert out.shape == ref.shape == (3, 8, 128)
    np.testing.assert_allclose(out[::2], ref[::2], atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(out[1]).max()) == 0.0  # a dead row (the twin averages garbage there)
    if sink:  # the sink takes mass: the output shrinks
        plain = attention.paged_attention_gather(q, k, v, bt, seq_lens, 0.07, window=window)
        assert float(jnp.abs(ref[0] - plain[0]).max()) > 1e-2


@pytest.mark.parametrize("window,sink", [(24, True), (0, True), (0, False)],
                         ids=["window-sink", "sink", "plain"])
def test_flash_kernel_with_a_sink_and_narrow_value_rows(window, sink):
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    f, k, v, bt, sinks = _kernel_case(4, R=2)
    q = f(2, 32, 8, 256)
    start, length = jnp.asarray([16, 48], jnp.int32), jnp.asarray([32, 19], jnp.int32)
    kw = {"window": window, "sinks": sinks if sink else None}
    ref = jax.vmap(lambda qi, ti, sp, tl: attention.prefill_attention_blockwise(
        qi, k, v, ti, sp, tl, 0.07, **kw))(q, bt, start, length)
    out = flash_prefill_kernel(q, k, v, bt, start, length, 0.07, interpret=True, tile_q=16, **kw)
    assert out.shape == ref.shape == (2, 32, 8, 128)
    np.testing.assert_allclose(out[0], ref[0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out[1, :19], ref[1, :19], atol=2e-5, rtol=2e-5)
    dense = jax.vmap(lambda qi, ti, sp, tl: attention.prefill_attention_gather(
        qi, k, v, ti, sp, tl, 0.07, **kw))(q, bt, start, length)
    np.testing.assert_allclose(ref[0], dense[0], atol=2e-5, rtol=2e-5)


def test_kv_write_kernel_takes_pools_of_two_widths():
    from xllm_service_tpu.ops import kv_write

    K, V = jnp.zeros((2, 6, 2, 16, 256)), jnp.zeros((2, 6, 2, 16, 128))
    tables = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    start, length = jnp.asarray([14, 0], jnp.int32), jnp.asarray([5, 3], jnp.int32)
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.standard_normal((2 * 8, 2, 256)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2 * 8, 2, 128)), jnp.float32)
    outs = []
    for interpret in (False, True):  # the scatter route, then the tile write
        plan = kv_write.write_plan(K, tables, start, length, 8, interpret=interpret)
        assert (plan.units is not None) == interpret
        outs.append(kv_write.write_kv(K, V, plan, k, v, 1))
    for a, b in zip(*outs):
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], atol=0)  # block 0 is garbage
    assert float(jnp.abs(outs[1][0][1, 1, :, 14:]).max()) > 0 and float(jnp.abs(outs[1][0][0]).max()) == 0
