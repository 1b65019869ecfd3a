"""The Laguna family on the normal path (models/granite.py's two attention
kinds at DIFFERENT query heads, rotary lanes and rotary tables, a gate per
head, a shared expert under a routed scale), on the CPU with `laguna-tiny`
(2 full layers of 6 query heads + 3 window layers of 8, both over 2 KV
heads; rotary on 8 of 16 lanes by YaRN's table on the full kind and on all
16 by a plain one on the window kind; a window of 24 over blocks of 8: three
blocks; 32 sigmoid top-4 experts, all held, times 2.5, beside a shared one;
a dense first layer): prefill-then-decode and the mixed step agree with the
benchmark family's plain reference in float32 so tightly that each reading
of the published config, changed, fails; a sequence's window blocks stay
bounded at several blocks while its full table grows, a preempted one
resumes with the same logits, the window pool never runs out under the
fullest step; the kernels at a query group of 6 and 3 and the grouped
product at more experts than row tiles agree with their twins; what is not
built is refused by name."""

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
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops import moe as moe_ops
from xllm_service_tpu.ops import rope as rope_ops
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import WindowBlockManager, WindowFamilyUnsupported
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("laguna-tiny")
CUT = get_model_config("laguna-xs.2")
BS, W = 8, CFG.sliding_window
REST = -(-(W - 1) // BS) + 1  # blocks 24 positions can straddle: 4
ATOL = 2e-5  # float32, logits of about 1


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "laguna-tiny", "family": "laguna"})


def _family_config(c=CFG):
    kinds = {"attention": "full_attention", "window": "sliding_attention"}
    return {
        "model_type": "laguna", "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim, "max_position_embeddings": c.max_position_embeddings,
        "attention_bias": False, "rms_norm_eps": c.rms_norm_eps,
        "num_experts": c.num_experts, "num_experts_per_tok": c.num_experts_per_tok,
        "moe_intermediate_size": c.moe_intermediate_size,
        "shared_expert_intermediate_size": c.moe_intermediate_size,
        "tie_word_embeddings": False, "gating": True, "sliding_window": c.sliding_window,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": c.rope_theta, "rope_type": "yarn", "factor": c.rope_scaling_factor,
                "original_max_position_embeddings": c.rope_original_max_position,
                "beta_slow": c.rope_beta_slow, "beta_fast": c.rope_beta_fast,
                "attention_factor": c.rope_attention_factor,
                "partial_rotary_factor": c.rotary_dim / c.head_dim},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": c.window_rope_theta,
                "partial_rotary_factor": c.window_rotary_dim / c.head_dim},
        },
        "layer_types": [kinds[k] for k in c.layer_types],
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense" if l < c.first_k_dense_replace else "sparse"
                            for l in range(c.num_layers)],
        "moe_routed_scaling_factor": c.routed_scaling_factor,
        "num_attention_heads_per_layer": [c.attn_heads(k) for k in c.layer_types],
    }


def test_the_family_file_reads_the_preset_back():
    fam, m = _family(), _family_config()
    assert fam.model_config("laguna-tiny", m) == CFG
    assert get_module(CFG) is granite
    assert (CFG.attn_heads("attention"), CFG.attn_heads("window")) == (6, 8)
    assert (CFG.attn_rotary_dim("attention"), CFG.attn_rotary_dim("window")) == (8, 16)
    want = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    have = jax.eval_shape(lambda: fam.make_weights(m, jax.random.key(0), jnp.float32))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(want) == shape(have)
    # a gate a HEAD, by kind; the window stack is 8 heads wide, the full one 6
    assert want["attn"]["w_ogate"].shape == (2, 64, 6) and want["attn_w"]["w_ogate"].shape == (3, 64, 8)
    assert want["attn"]["wq"].shape == (2, 64, 6 * 16) and want["attn_w"]["wo"].shape == (3, 8 * 16, 64)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = CUT
    assert (c.hidden_size, c.num_heads, c.window_num_heads, c.num_kv_heads, c.window_kv_heads) \
        == (2048, 48, 64, 8, 8)
    assert (c.head_dim, c.rotary_dim, c.window_rotary_dim, c.sliding_window) == (128, 64, 128, 512)
    assert (c.intermediate_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            c.n_shared_experts, c.routed_scaling_factor) == (8192, 512, 256, 8, 1, 2.5)
    assert (c.num_attention_layers, c.num_window_layers, c.first_k_dense_replace) == (2, 3, 1)
    assert c.held_experts == (0, 256) and c.vocab_size == 100352
    assert approx_param_count(c) == 3_869_835_264  # 7.74 GB in bfloat16
    (kf, vf), (kw, vw) = granite.pool_shapes(c, 100, 20, 128)
    assert kf == vf == (2, 100, 8, 128, 128) and kw == vw == (3, 20, 8, 128, 128)
    with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-xs.2.json")) as f:
        import json

        m = json.load(f)
    assert _family().model_config("laguna-xs.2", m) == c
    assert m["layers_held"] == [0, 1, 2, 3, 4] and m["reduced"] == ["num_hidden_layers"]
    assert len(m["layer_types"]) == len(m["num_attention_heads_per_layer"]) == 40


# ------------------------------------------------------------ the tables


def _hf_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """transformers' _compute_yarn_parameters (truncate on), written out."""
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor


@pytest.mark.parametrize("cfg", [CFG, CUT], ids=["tiny", "published"])
def test_the_full_layers_table_is_hfs_yarn_and_the_window_layers_is_plain(cfg):
    tables = granite.rotary_tables(cfg)
    full, window = tables["attention"], tables["window"]
    want = _hf_yarn(cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling_factor,
                    cfg.rope_original_max_position, cfg.rope_beta_fast, cfg.rope_beta_slow)
    np.testing.assert_allclose(full.inv_freq, want, rtol=1e-6)
    assert full.lanes == cfg.rotary_dim and full.scale == cfg.rope_attention_factor
    assert window.inv_freq is None and window.scale == 1.0
    assert (window.lanes, window.theta) == (cfg.window_rotary_dim, cfg.window_rope_theta)
    plain = cfg.rope_theta ** -(np.arange(0, cfg.rotary_dim, 2) / cfg.rotary_dim)
    assert float(np.abs(full.inv_freq / plain - 1).max()) > 0.5  # the table IS scaled
    if cfg is CUT:  # pairs 0-5 keep their frequency, pairs 16-31 take it over 64
        np.testing.assert_allclose(full.inv_freq[:6], plain[:6], rtol=1e-6)
        np.testing.assert_allclose(full.inv_freq[16:], plain[16:] / 64, rtol=1e-6)
        assert abs(full.scale - (0.1 * math.log(64) + 1)) < 1e-5
        assert (full.name, window.name) == ("yarn x64 / 64 lanes", "plain / 128 lanes")
    fam, m = _family(), _family_config(cfg)
    lanes, inv, factor = fam.rotary_table(m, "attention")
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert (lanes, factor) == (cfg.rotary_dim, cfg.rope_attention_factor)


def test_the_attention_factor_is_on_the_rotated_lanes_alone():
    x = jax.random.normal(jax.random.key(0), (5, 3, 16))
    pos = jnp.arange(5) * 7
    t = granite.rotary_tables(CFG)["attention"]
    y = rope_ops.apply_partial_rope_table(x, pos, t.inv_freq, t.scale, t.lanes)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])  # the lanes that pass
    pairs = lambda a: a[..., :4] ** 2 + a[..., 4:8] ** 2  # a rotation keeps a pair's norm
    np.testing.assert_allclose(pairs(y), t.scale ** 2 * pairs(x), rtol=1e-5)
    np.testing.assert_allclose(y[0, :, :8], t.scale * x[0, :, :8], rtol=1e-6)  # position 0
    whole = rope_ops.apply_partial_rope_table(x, pos, np.ones(8, np.float32), 1.0, 16)
    np.testing.assert_allclose(whole, rope_ops._rotate(x, pos[:, None].astype(jnp.float32)
                                                       * jnp.ones(8)), rtol=1e-6)


# ---------------------------------------------------- steps vs the reference


class _Seq:
    """One sequence's two tables over hand-made pools, the window table
    slid as the engine slides it (WindowBlockManager)."""

    def __init__(self, mgr, blocks, CB=16, window=W):
        self.mgr, self.ids, self.lo, self.CB, self.W = mgr, mgr.allocate(blocks), 0, CB, window
        self.full = np.zeros((CB,), np.int32)
        self.full[:blocks] = self.ids
        self.win = np.zeros((CB,), np.int32)

    def table(self, first, end):
        lo = max(0, first - self.W + 1) // BS
        self.lo = self.mgr.slide(self.ids, self.lo, lo, (end - 1) // BS + 1, self.win)
        return np.concatenate([self.full, self.win])


def _pools(cfg=CFG, blocks=40, window_blocks=14):
    (kf, vf), (kw, vw) = granite.pool_shapes(cfg, blocks, window_blocks, BS)
    z = lambda s: jnp.zeros(s, jnp.float32)
    return (z(kf), z(kw)), (z(vf), z(vw)), WindowBlockManager(blocks, window_blocks, BS)


def _serve(params, cfg, toks, n_prefill, chunk=16):
    """Logits of every position from n_prefill - 1 on: the prompt in
    chunks, then token by token, through the step functions."""
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, cfg, K, V, *a))
    decode = jax.jit(lambda p, K, V, *a: granite.decode_step(p, cfg, K, V, *a))
    K, V, mgr = _pools(cfg)
    seq, outs = _Seq(mgr, -(-len(toks) // BS), window=cfg.sliding_window), []
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


N_TOKS = 96  # four windows


@pytest.fixture(scope="module")
def seeded():
    fam, m = _family(), _family_config()
    params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    toks = np.asarray(jax.random.randint(jax.random.key(6), (N_TOKS,), 0, CFG.vocab_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.forward_logits(params, m, jnp.asarray(toks, jnp.int32), jnp.arange(N_TOKS))
    return fam, m, params, toks, ref


@pytest.mark.parametrize("n_prefill", [16, 37, 64], ids=["one-chunk", "ragged", "four-chunks"])
def test_prefill_then_decode_equals_the_family_reference(seeded, n_prefill):
    _, _, params, toks, ref = seeded
    got, mgr = _serve(params, CFG, toks, n_prefill)
    np.testing.assert_allclose(got, ref[n_prefill - 1:], atol=ATOL)
    # the window pool held the window and no more: 4 blocks cover 24 positions
    assert mgr.window_blocks_live <= REST and mgr.window_blocks_freed >= N_TOKS // BS - REST
    np.testing.assert_allclose(
        granite.forward_dense(params, CFG, jnp.asarray(toks)[None])[0], ref, atol=ATOL)


BROKEN = {
    "window-23": dict(sliding_window=W - 1),
    "window-25": dict(sliding_window=W + 1),
    "window-one-block": dict(sliding_window=BS),
    "no-gate": dict(attn_gate=False),
    "no-yarn": dict(rope_scaling_type=""),
    "no-attn-factor": dict(rope_attention_factor=1.0),
    "yarn-not-truncated": dict(rope_scaling_truncate=False),
    "lanes-swapped": dict(rotary_dim=CFG.window_rotary_dim, window_rotary_dim=CFG.rotary_dim),
    "lanes-alike": dict(window_rotary_dim=0),
    "thetas-swapped": dict(rope_theta=5000.0, window_rope_theta=20000.0),
    "no-scale": dict(routed_scaling_factor=1.0),
    "softmax-scores": dict(scoring_func="softmax"),
    "not-renormalised": dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_each_departure_from_the_equations_fails_the_comparison(seeded, fault):
    """The comparison is tight enough to see each one: the program with one
    field of its configuration wrong is off by 100x the tolerance."""
    _, _, params, toks, ref = seeded
    broken = dataclasses.replace(CFG, **BROKEN[fault])
    got, _ = _serve(params, broken, toks[:56], 37)
    assert float(jnp.abs(got - ref[36:56]).max()) > 100 * ATOL


@pytest.mark.parametrize("fault", ["one-gate-for-all", "gate-per-lane-of-head-0", "no-shared"])
def test_a_wrong_gate_or_a_missing_shared_expert_fails_the_comparison(seeded, fault, monkeypatch):
    _, _, params, toks, ref = seeded
    if fault == "no-shared":
        monkeypatch.setattr(llama, "_shared_experts", lambda lp, x: jnp.zeros_like(x))
    else:
        sound = granite._gated

        def wrong(lp, cfg, h, o):
            w = lp["w_ogate"]
            if fault == "one-gate-for-all":  # head 0's gate on every head
                w = jnp.repeat(w[:, :1], w.shape[1], axis=1)
                return sound({**lp, "w_ogate": w}, cfg, h, o)
            # a gate a LANE made of the per-head columns, tiled: Solar's form
            lanes = jnp.tile(w, (1, o.shape[1] // w.shape[1]))
            return sound({**lp, "w_ogate": lanes}, dataclasses.replace(cfg, attn_gate_per_head=False), h, o)

        monkeypatch.setattr(granite, "_gated", wrong)
    got, _ = _serve(params, CFG, toks[:56], 37)
    assert float(jnp.abs(got - ref[36:56]).max()) > 100 * ATOL


def test_the_router_renormalises_sigmoid_scores_and_scales_them(seeded):
    fam, m, params, _, _ = seeded
    lp = {k: v[1] for k, v in params["layers"].items() if k not in ("attn_norm", "mlp_norm")}
    u = jax.random.normal(jax.random.key(3), (40, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        chosen, w = llama.moe_route(lp, CFG, u)
        sc = jax.nn.sigmoid(u @ lp["router"])
        own = jnp.take_along_axis(sc, chosen, axis=-1)
        np.testing.assert_allclose(w, 2.5 * own / own.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
        assert set(np.asarray(chosen[0])) == set(np.asarray(jnp.argsort(-sc[0])[:4]))  # no bias
        assert "router_bias" not in params["layers"]
        # the expert block: the routed sum AND the shared expert, unweighted
        ref = fam.expert_layer(u, params["layers"], 1, m)
        np.testing.assert_allclose(llama._mlp_block(lp, CFG, u), ref, atol=ATOL)
        shared = llama._shared_experts(lp, u)
        assert float(jnp.abs(shared).max()) > 1e-2
        dense = fam.route(u, lp["router"], m)
        np.testing.assert_allclose(jnp.sort(dense, -1)[:, -4:].sum(-1), 2.5, rtol=1e-5)


def test_mixed_step_equals_its_split_steps(seeded):
    _, _, params, toks, ref = seeded
    K, V, mgr = _pools()
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, CFG, K, V, *a))
    a, b = _Seq(mgr, 12), _Seq(mgr, 8)
    other = np.asarray(jax.random.randint(jax.random.key(8), (64,), 0, CFG.vocab_size))
    for seq, ids in ((a, toks), (b, other)):  # 48 tokens of each, three chunks: past the window
        for pos in (0, 16, 32):
            _, K, V = prefill(
                params, K, V, jnp.asarray(ids[None, pos:pos + 16]), jnp.asarray([pos]),
                jnp.asarray([16]), jnp.asarray(seq.table(pos, pos + 16))[None])
    dec_tab = np.zeros((2, 32), np.int32)
    dec_tab[0] = a.table(48, 49)
    dec = (jnp.asarray([toks[48], 0]), jnp.asarray([48, 0]), jnp.asarray(dec_tab),
           jnp.asarray([True, False]))
    pf = (jnp.asarray(other[None, 48:64]), jnp.asarray([48]), jnp.asarray([16]),
          jnp.asarray(b.table(48, 64))[None])
    step = lambda fn: jax.jit(lambda p, K, V, *a: fn(p, CFG, K, V, *a))
    d_logits, p_logits, Km, Vm = step(granite.mixed_step)(params, K, V, *dec, *pf)
    d_ref, Ks, Vs = step(granite.decode_step)(params, K, V, *dec)
    p_ref, Ks, Vs = step(granite.prefill_batch_step)(params, Ks, Vs, *pf)
    np.testing.assert_allclose(d_logits, d_ref, atol=ATOL)
    np.testing.assert_allclose(p_logits, p_ref, atol=ATOL)
    np.testing.assert_allclose(d_logits[0], ref[48], atol=ATOL)
    for got, want in zip(jax.tree.leaves((Km, Vm)), jax.tree.leaves((Ks, Vs))):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], atol=1e-6)  # block 0 is garbage


def test_the_kinds_are_segments_and_every_other_family_keeps_its_defaults():
    segs = granite._segments(CFG)
    assert [(s.kind, s.n, s.dense, s.kind_first) for s in segs] == [
        ("attention", 1, True, 0), ("window", 3, False, 0), ("attention", 1, False, 1)]
    for name in ("mimo-v2-flash", "solar-open2-250b", "falcon-h1-34b", "minicpm-sala",
                 "granite-4.0-h-small"):
        c = get_model_config(name)
        assert c.attn_heads("window") == c.attn_heads("attention") == c.num_heads
        assert c.attn_rotary_dim("window") == c.rotary_dim and not c.attn_gate_per_head
        assert all(t.inv_freq is None for t in granite.rotary_tables(c).values())


@pytest.mark.parametrize("change, match", [
    ({"rope_scaling_type": "longrope"}, "picks a table by position"),
    ({"attn_gate": False}, "set attn_gate too"),
])
def test_a_configuration_the_stack_would_misread_is_refused_by_name(change, match):
    """A scaling type that chooses its table by position would rotate by the
    short table everywhere, and a gate form without a gate would build no
    gate at all: both are refused where the stack is built."""
    cfg = dataclasses.replace(CFG, **change)
    with pytest.raises(ValueError, match=match):
        granite.rotary_tables(cfg)
        granite.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


# ------------------------------------------------------------------ engine


def _engine(R=4, max_seq_len=512, num_blocks=300, model="laguna-tiny", **kw):
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


def _drain(eng, steps=4000, each=None):
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


PROMPTS = {"one-chunk": 13, "three-chunks": 48, "six-chunks": 91}


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts of 1, 3
    and 6 chunks (a ragged tail on two) served concurrently; the shortest
    decodes to five times the window."""
    eng, ex = _engine()
    fam, m = _family(), _family_config()
    ex.params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    rng = np.random.default_rng(0)
    prompts = {rid: list(rng.integers(0, 512, n)) for rid, n in PROMPTS.items()}
    outs, seen = {}, {"window": [], "full": [], "pool": []}

    def each():
        seen["pool"].append(eng.block_mgr.window.num_referenced_blocks)
        seq = next((s for s in eng._running.values() if s.req.request_id == "one-chunk"), None)
        if seq is not None:
            seen["window"].append(sum(1 for b in seq.block_ids if b in eng.block_mgr._beside))
            seen["full"].append(len(seq.block_ids))

    for rid, p in prompts.items():
        eng.add_request(_req(rid, outs, p, max_new=5 * W if rid == "one-chunk" else 12))
    _drain(eng, each=each)
    return eng, ex, fam, m, prompts, outs, seen


@pytest.mark.parametrize("rid", sorted(PROMPTS))
def test_engine_matches_the_family_reference_in_logits(served, rid):
    eng, ex, fam, m, prompts, outs, _ = served
    assert isinstance(eng.block_mgr, WindowBlockManager)
    p, out = prompts[rid], outs[rid]
    assert len(out) == (5 * W if rid == "one-chunk" else 12)
    with jax.default_matmul_precision("highest"):
        seq = np.zeros((160,), np.int32)
        seq[:len(p) + len(out)] = p + out
        idx = np.arange(len(p) - 1, len(p) + len(out) - 1)
        rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
    assert [int(t) for t in jnp.argmax(rows, -1)] == out
    lp = jax.nn.log_softmax(rows, axis=-1)[np.arange(len(out)), np.asarray(out)]
    np.testing.assert_allclose(outs[rid + "/lp"], lp, atol=ATOL)


def test_window_blocks_stay_bounded_at_several_blocks_while_the_full_table_grows(served):
    eng, ex, _, _, _, _, seen = served
    # at rest a sequence holds the blocks 24 positions can straddle (4), one
    # more while a step writes into a new one
    assert max(seen["window"]) <= REST + 1 and seen["window"][-1] >= REST - 1
    assert seen["full"][-1] >= seen["full"][0] + 5 * W // BS - 1
    assert eng.block_mgr.window_blocks_freed > 5 * W // BS
    assert _nothing_held(eng) and eng.prefix_cached_tokens == 0
    assert ex.window_blocks == 1 + ex.R * (REST + 1) + 2 * (16 // BS)
    assert eng.block_mgr.window.num_blocks == ex.window_blocks
    assert max(seen["pool"]) < ex.window_blocks
    rep = ex.kernel_report()
    assert rep["window"] == "window-xla" and ex.window_tables
    assert rep["kinds"] == {
        "attention": {"launch": rep["mixed"], "query_group": 3, "window_blocks": 0,
                      "rotary": "yarn x16 / 8 lanes"},
        "window": {"launch": "window-xla", "query_group": 4, "window_blocks": 3,
                   "rotary": "plain / 16 lanes"},
    }
    assert ex._ctx_bucket(1) == ex.max_blocks_per_seq == 64  # one context bucket
    # K and V of the 2 full layers: 2 KV heads of 16 + 16 lanes, float32
    assert ex.cache_row_bytes == 2 * 2 * (16 + 16) * 4
    text = eng.metrics.render()
    stats = ex.moe_stats()
    assert 0 < stats["touched"] <= stats["held_reads"] and stats["held_reads"] % (32 * 4) == 0
    for series in ('xllm_engine_kv_blocks_live{pool="window"} 0',
                   'xllm_engine_kv_block_bytes{pool="window"} %d' % (3 * 2 * 2 * BS * 16 * 4),
                   "xllm_engine_window_blocks_freed_total %d" % eng.block_mgr.window_blocks_freed,
                   "xllm_engine_moe_experts_held_total %d" % stats["held_reads"],
                   "xllm_engine_moe_experts_touched_total %d" % stats["touched"]):
        assert series in text, series


def test_a_preempted_sequence_with_several_window_blocks_resumes_with_the_same_logits():
    """The victim holds four window blocks when it is preempted (its context
    is past the window); it resumes by recomputing, through chunks whose
    window blocks are freed behind them again, and every logprob it emits
    is an undisturbed run's. Every way out returns both pools."""
    prompt = list(np.random.default_rng(5).integers(1, 400, 45))
    solo = {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("solo", solo, prompt, max_new=40, offline=True))
    _drain(eng)
    outs, held = {}, []
    eng, _ = _engine(R=2)
    eng.add_request(_req("victim", outs, prompt, max_new=40, offline=True))
    eng.add_request(_req("gone", outs, prompt[:9], max_new=300, offline=True))
    for _ in range(14):
        eng.step()
    victim = next(s for s in eng._running.values() if s.req.request_id == "victim")
    held.append(sum(1 for b in victim.block_ids if b in eng.block_mgr._beside))
    assert held[0] >= REST - 1 >= 3  # several window blocks, not mimo's two
    eng.cancel("gone")
    for i in range(2):
        eng.add_request(_req(f"on{i}", outs, prompt[:7 + i], max_new=6))
    _drain(eng)
    assert eng.preemptions >= 1 and outs["victim"] == solo["solo"]
    np.testing.assert_allclose(outs["victim/lp"], solo["solo/lp"], atol=ATOL)
    assert _nothing_held(eng)


def test_the_window_pool_never_runs_out_under_the_fullest_step():
    """Every slot decoding past the window while a queue of prompts keeps
    a chunk in every step: the pool `_decide_window_blocks` sized (R x
    (rest + 1) + the chunks' blocks + the garbage block) is never exhausted
    and its high-water mark stays under it."""
    eng, ex = _engine(R=4)
    rng = np.random.default_rng(2)
    outs, peak = {}, [0]
    for i in range(10):
        n = int(rng.integers(20, 90))
        eng.add_request(_req(f"r{i}", outs, list(rng.integers(1, 400, n)), max_new=3 * W))

    def each():
        peak[0] = max(peak[0], eng.block_mgr.window.num_referenced_blocks)

    _drain(eng, steps=8000, each=each)
    assert len(outs["_finished"]) == 10 and _nothing_held(eng)
    assert ex.window_blocks == 1 + 4 * (REST + 1) + 2 * 2 == 25
    assert 4 * (REST - 1) <= peak[0] <= ex.window_blocks - 1


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens.*no window table"),
    (dict(num_host_blocks=8), "prefix cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype.*second pool"),
    (dict(checkpoint_path="/nowhere"), "checkpoint_path"),
    (dict(ep_size=2), r"query heads \(6 and 8 over 2 KV heads\)"),
], ids=["speculation", "prefix-tiers", "int8-cache", "checkpoint", "sharded"])
def test_named_refusals_at_build_name_the_mechanism_and_no_sink(kw, match):
    with pytest.raises(WindowFamilyUnsupported, match=match) as e:
        _engine(**kw)
    assert "sink" not in str(e.value)  # this family has none


def test_a_family_with_a_sink_still_hears_of_it():
    with pytest.raises(WindowFamilyUnsupported, match="differ in KV heads and the window "
                                                      "layers' kernels take their sink whole"):
        _engine(model="mimo-tiny", ep_size=2)
    with pytest.raises(WindowFamilyUnsupported, match="no window table and no sink logit"):
        _engine(model="mimo-tiny", speculative_tokens=2)


def test_named_refusals_at_the_request():
    eng, ex = _engine(R=2)
    with pytest.raises(WindowFamilyUnsupported, match="PD handoff"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(WindowFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])


def test_the_parameter_tree_has_a_replicated_rule_for_every_leaf():
    from xllm_service_tpu.parallel.mesh import build_mesh
    from xllm_service_tpu.parallel.sharding import param_shardings

    rules = param_shardings(CFG, build_mesh(tp=1))
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(rules)
    for leaf, rule in zip(jax.tree.leaves(shapes), jax.tree.leaves(rules)):
        assert len(rule.spec) == leaf.ndim and not any(rule.spec)
    assert set(shapes["attn_w"]) == {"wq", "wk", "wv", "wo", "w_ogate"}
    assert {"w_sh_gate", "w_sh_up", "w_sh_down", "router"} <= set(shapes["layers"])


# ------------------------------------------------- kernels, interpreted


def _kernel_case(seed, R, Hkv, D=128, BS=16, MB=8, N=40):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    k, v = f(N, Hkv, BS, D), f(N, Hkv, BS, D)
    bt = jnp.asarray(rng.choice(np.arange(1, N), size=(R, MB), replace=False).astype(np.int32))
    return f, k, v, bt


@pytest.mark.parametrize("window", [0, 64], ids=["full", "window-4-blocks"])
@pytest.mark.parametrize("group", [6, 3])
def test_decode_kernel_at_a_query_group_that_is_no_multiple_of_8(group, window):
    """48 query heads over 8 KV heads is a group of 6 (the tiny preset's
    3): the Pallas decode kernel pads a group to 8 sublanes; interpreted,
    against its jax.numpy twin, with a window of four blocks and no sink."""
    from xllm_service_tpu.ops.pallas.paged_attention import paged_attention_kernel

    f, k, v, bt = _kernel_case(3, R=3, Hkv=2)
    q = f(3, 2 * group, 128)
    seq_lens = jnp.asarray([5, 0, 118], jnp.int32)
    ref = attention.paged_attention_gather(q, k, v, bt, seq_lens, 0.09, window=window)
    out = paged_attention_kernel(q, k, v, bt, seq_lens, 0.09, interpret=True, window=window)
    assert out.shape == ref.shape == (3, 2 * group, 128)
    np.testing.assert_allclose(out[::2], ref[::2], atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(out[1]).max()) == 0.0  # a dead row
    if window:  # the window cuts what the long row sees
        full = attention.paged_attention_gather(q, k, v, bt, seq_lens, 0.09)
        assert float(jnp.abs(ref[2] - full[2]).max()) > 1e-3


@pytest.mark.parametrize("window", [0, 64], ids=["full", "window-4-blocks"])
@pytest.mark.parametrize("group", [6, 3])
def test_flash_kernel_at_a_query_group_that_is_no_multiple_of_8(group, window):
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    f, k, v, bt = _kernel_case(4, R=2, Hkv=2)
    q = f(2, 32, 2 * group, 128)
    start, length = jnp.asarray([80, 48], jnp.int32), jnp.asarray([32, 19], jnp.int32)
    ref = jax.vmap(lambda qi, ti, sp, tl: attention.prefill_attention_blockwise(
        qi, k, v, ti, sp, tl, 0.09, window=window))(q, bt, start, length)
    out = flash_prefill_kernel(q, k, v, bt, start, length, 0.09, interpret=True, tile_q=16,
                               window=window)
    assert out.shape == ref.shape == (2, 32, 2 * group, 128)
    np.testing.assert_allclose(out[0], ref[0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out[1, :19], ref[1, :19], atol=2e-5, rtol=2e-5)
    dense = jax.vmap(lambda qi, ti, sp, tl: attention.prefill_attention_gather(
        qi, k, v, ti, sp, tl, 0.09, window=window))(q, bt, start, length)
    np.testing.assert_allclose(ref[0], dense[0], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("rows,experts", [(16, 24), (256, 24), (16, 3)],
                         ids=["2-tiles-24-experts", "tiles-of-128", "more-tiles-than-experts"])
def test_the_grouped_product_walks_more_held_experts_than_row_tiles(rows, experts):
    """256 held experts meet 36 row tiles in the cell: the walk is `row
    tiles + held experts - 1` steps whichever is larger. Interpreted, at
    more held experts than tiles, with empty experts among them (one at
    each end and two in a row inside), against `expert_product_reference`."""
    from xllm_service_tpu.ops.pallas.moe_dispatch import group_steps, moe_grouped_kernel, tile_rows

    rng = np.random.default_rng(experts + rows)
    E, F = 128, 128
    sizes = rng.multinomial(rows - 3, np.ones(experts) / experts).astype(np.int32)
    sizes[[0, experts - 1]] = 0
    if experts > 8:
        sizes[[5, 6]] = 0
    sizes[1] += rows - 3 - int(sizes.sum())  # the empties' pairs go to a live expert
    assert sizes.sum() == rows - 3 and (sizes == 0).sum() >= 2
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    xs = jnp.asarray(rng.standard_normal((rows, E)), jnp.float32)
    wg, wu, wd = f(experts, E, F), f(experts, E, F), f(experts, F, E)
    ref = moe_ops.expert_product_reference(xs, jnp.asarray(sizes), wg, wu, wd)
    out = moe_grouped_kernel(xs, jnp.asarray(sizes), wg, wu, wd, interpret=True,
                             tile_q=8 if rows == 16 else 128)
    live = rows - 3
    np.testing.assert_allclose(out[:live], ref[:live], atol=2e-5, rtol=2e-5)
    tm = tile_rows(rows, 8 if rows == 16 else 128)
    _, g, t, n = group_steps(jnp.asarray(sizes), rows // tm, tm)
    assert g.shape == (rows // tm + experts - 1,) and int(n[0]) <= g.shape[0]
    meets = {(int(a), int(b)) for a, b in zip(g[:int(n[0])], t[:int(n[0])])}
    assert len(meets) == int(n[0]) and all(sizes[a] > 0 for a, _ in meets)  # no empty expert is met
