"""Tier-1 copy of benchmarks/tests/test_families.py (PERF.md section 7
left it for the first program PR after PR 30): every family file of the
benchmark against the program's parameter tree, and the Llama family's
draws against digests of PR 30's parent. A program PR that changes a
parameter tree, or a benchmark PR that changes a draw, fails here and not
in a cell's set-up on the chip."""
import glob
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import family as family_mod  # noqa: E402


def configurations():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def test_the_benchmark_has_both_families():
    # nine since PR 60 (the name stays: the driver counts tests by name)
    assert {c["family"] for c in configurations()} == {
        "llama", "brumby", "deepseek", "granite", "solar", "mimo", "falcon_h1", "minicpm_sala",
        "laguna"}


@pytest.mark.parametrize("config", configurations(), ids=lambda c: c["name"])
def test_make_weights_gives_the_programs_parameter_tree(config):
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu import models

    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    dtype = jnp.dtype(config["engine"]["dtype"])
    want = jax.eval_shape(lambda k: models.get_module(cfg).init_params(cfg, k, dtype),
                          jax.random.key(0))
    have = jax.eval_shape(lambda k: fam.make_weights(config, k, dtype), family_mod.seed_key(1))
    assert (jax.tree.map(lambda a: (a.shape, str(a.dtype)), have)
            == jax.tree.map(lambda a: (a.shape, str(a.dtype)), want))


@pytest.mark.parametrize("config", configurations(), ids=lambda c: c["name"])
def test_model_config_is_the_family_the_program_dispatches_on(config):
    from xllm_service_tpu import models

    cfg = family_mod.load(config).model_config(config["name"], config)
    module = models.get_module(cfg).__name__.rsplit(".", 1)[-1]
    # the six hybrids are ONE stack: models/granite.py, the layer kinds as data
    assert module == {"solar": "granite", "mimo": "granite", "falcon_h1": "granite",
                      "minicpm_sala": "granite", "laguna": "granite"}.get(
        config["family"], config["family"])
    assert cfg.is_retention == (config["family"] == "brumby")


def digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


# as benchmarks/tests/test_families.py has them: the parent of PR 30's
# jitted reference.make_weights at rehearse-tiny, on the CPU backend
PARENT_DIGESTS = {
    (21, "float32"): "cc6cc191d4c20ed61c1d25f67fa596080137626e93c0df8764e3cf024810a3ff",
    (21, "bfloat16"): "b807c8597d2e7d1bd566d41a4e632738f48fb11d4ef3378ad0fa99a155abef22",
    (2**31 + 77, "float32"): "fb38b60ad306c6df313268e1d63af372753df446bf49e181af11ab96ca60b6af",
    (2**31 + 77, "bfloat16"): "41c7825204f8ef2e5cce76c8f9a5d0e10bdae5a073e61847af9a3bb3202f9c98",
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_DIGESTS))
def test_the_llama_family_draws_what_the_parent_drew(seed, dtype):
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "rehearse-tiny.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    w = jax.jit(lambda k: fam.make_weights(config, k, jnp.dtype(dtype)))(family_mod.seed_key(seed))
    assert digest(w) == PARENT_DIGESTS[(seed, dtype)]


def test_the_brumby_family_draws_slow_decays_and_leaves_nothing_skippable():
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "rehearse-brumby-tiny.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    w = jax.jit(lambda k: fam.make_weights(config, k, jnp.float32))(family_mod.seed_key(3))
    lay = w["layers"]
    h = jax.random.normal(jax.random.key(0), (4096, config["hidden_size"]))  # unit-RMS rows
    decay = jax.nn.sigmoid(jnp.einsum("te,leh->lth", h, lay["w_ret_gate"]) + lay["b_ret_gate"][:, None])
    assert 0.993 < float(decay.min()) and float(decay.max()) < 0.99995
    assert float(jnp.mean((decay > 0.995) & (decay < 0.9999))) > 0.97
    for name in ("attn_norm", "mlp_norm", "q_head_norm", "k_head_norm"):
        assert 0.02 < float(jnp.std(lay[name])) < 0.2, name  # gains ~ N(1, 0.1), not 1
    assert float(jnp.abs(lay["b_ret_gate"]).min()) > 1.0  # the bias is not 0


def test_the_deepseek_family_holds_a_span_under_a_published_router():
    """deepseek-v2 as cut: 40 experts held under a router 160 wide, a
    quarter of the vocabulary, every published width; the tree drawn at
    the rehearsal size leaves nothing at 0 or 1 and draws each slice of a
    stacked leaf from its own key."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        big = json.load(f)
    fam = family_mod.load(big)
    cfg = fam.model_config(big["name"], big)
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size, cfg.num_layers) == (160, (0, 40), 25600, 5)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank) == (5120, 128, 512, 1536)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.mla_cache_dim) == (128, 64, 128, 640)
    assert (cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (8, 3, 6, 16.0)
    assert cfg.rope_scaling_type == "yarn" and cfg.rope_mscale_all_dim == 0.707
    shapes = fam.weight_shapes(big)
    assert shapes["layers"]["router"] == (4, 5120, 160)
    assert shapes["layers"]["w_gate"] == (4, 40, 5120, 1536)
    assert shapes["dense_layers"]["w_gate"] == (1, 5120, 12288) and shapes["lm_head"] == (5120, 25600)
    n = sum(int(np.prod(s)) for g in shapes.values() for s in (g.values() if isinstance(g, dict) else [g]))
    assert 5.16e9 < n < 5.17e9  # ISSUE 39: 5,164 M parameters

    with open(os.path.join(BENCH, "configs", "rehearse-deepseek-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    lay = w["layers"]
    assert lay["w_gate"].shape == (2, 4, 128, 64) and lay["router"].shape == (2, 128, 8)
    for name in ("attn_norm", "mlp_norm", "q_norm"):
        assert 0.02 < float(jnp.std(lay[name])) < 0.2, name  # gains ~ N(1, 0.1), not 1
    # the latent's lanes carry powers of two on their gain and the inverse on their rows of w_uk / w_uv
    lanes = np.exp2(np.round(np.log2(np.asarray(lay["kv_norm"]))))
    assert set(np.unique(np.log2(lanes))) == set(range(-fam.LANE_LOG2, fam.LANE_LOG2 + 1))
    assert 0.02 < float(np.std(np.asarray(lay["kv_norm"]) / lanes)) < 0.2
    for name in ("w_uk", "w_uv"):
        rows = np.asarray(lay[name]) * lanes[:, None, :, None]
        assert abs(float(rows.std()) * np.sqrt(40) - 1.0) < 0.05, name
    flat = np.asarray(lay["w_gate"]).reshape(8, -1)
    assert np.abs(np.corrcoef(flat)[np.triu_indices(8, 1)]).max() < 0.1  # a key a slice
    assert abs(float(jnp.std(lay["w_sh_down"])) * np.sqrt(128) - 1.0) < 0.05  # N(0, 1 / fan_in)
    # the routed part is drawn small beside the residual (families/deepseek.py says why)
    assert abs(float(jnp.std(lay["w_down"])) * np.sqrt(64) / fam.ROUTED_OUT_SCALE - 1.0) < 0.05


def test_the_granite_family_draws_a_trained_models_decays_and_leaves_nothing_skippable():
    """granite-4.0-h-small as cut: 36 experts held under a router 72 wide,
    half the vocabulary, the first period of the published pattern; and at
    the rehearsal size the draws: Mamba-2's own steps under decays that
    outlast the check's 832 tokens, a standing component on the x and B
    lanes alone, the embedding and the final gain that undo each other, no
    gain at 1, no bias at 0."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size, cfg.num_layers) == (72, (0, 36), 50176, 10)
    assert len(config["layer_types"]) == 40 and cfg.layer_types == tuple(config["layer_types"][:10])
    assert cfg.num_state_layers == 9 and cfg.num_attention_layers == 1
    shapes = fam.weight_shapes(config)
    assert shapes["layers"]["router"] == (10, 4096, 72) and shapes["layers"]["w_gate"] == (10, 36, 4096, 768)
    assert shapes["mamba"]["w_in"] == (9, 4096, 8192 + 8448 + 128) and shapes["attn"]["wk"] == (1, 4096, 1024)
    assert shapes["embed"] == (50176, 4096) and "lm_head" not in shapes

    with open(os.path.join(BENCH, "configs", "rehearse-granite-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    mam = w["mamba"]
    dt0 = jax.nn.softplus(mam["dt_bias"])  # the step at a zero projection
    assert 0.9e-3 < float(dt0.min()) and float(dt0.max()) < 1.1e-1
    A = jnp.exp(mam["A_log"])
    assert 1e-3 <= float(A.min()) and float(A.max()) <= 1e-1
    decay = jnp.exp(-A * dt0)
    assert 0.989 < float(decay.min()) and float(decay.max()) < 1.0
    assert float(jnp.mean(decay > 1 - 1 / 832)) > 0.5  # most heads remember past the check
    H, P, G, N, d_in, conv = fam.dims(tiny)
    lanes = mam["conv_b"].mean(0)  # [x | B | C]
    assert abs(float(lanes[:d_in + G * N].mean()) - 2.0) < 0.05 and abs(float(lanes[d_in + G * N:].mean())) < 0.05
    assert abs(float(w["final_norm"].mean()) * fam.EMBED_SCALE - 16.0) < 0.5
    E = tiny["hidden_size"]
    assert 0.8 < float(jnp.var(w["embed"])) * E / fam.EMBED_SCALE ** 2 < 1.25
    assert 0.7 < float(jnp.var(mam["w_out"])) * (H * P) / fam.MAMBA_OUT_SCALE ** 2 < 1.4
    assert 0.8 < float(jnp.var(w["layers"]["w_down"])) * tiny["intermediate_size"] / fam.ROUTED_OUT_SCALE ** 2 < 1.25
    # scores x attention_multiplier at unit scale: q and k entries of variance qk_gain
    u = jax.random.normal(jax.random.key(0), (2048, tiny["hidden_size"]))
    q = u @ w["attn"]["wq"][0]
    assert 0.8 < float(jnp.var(q)) / fam.qk_gain(tiny) < 1.25 and fam.qk_gain(tiny) == 1 / (0.0625 * 4)
    assert abs(fam.qk_gain(config) - 128 ** 0.5) < 1e-9
    for stack, name in (("layers", "attn_norm"), ("layers", "mlp_norm"), ("mamba", "gate_norm"),
                        ("mamba", "D")):
        assert 0.02 < float(jnp.std(w[stack][name])) < 0.2, name  # ~ N(1, 0.1), not 1
    assert float(jnp.abs(mam["conv_b"]).min()) > 1e-5 and float(jnp.std(mam["conv_b"][:, -G * N:])) > 0.05  # no bias at 0
    for name in fam.FLOAT32_LEAVES:
        assert mam[name].dtype == jnp.float32, name



def test_the_solar_family_draws_slow_channel_decays_and_leaves_nothing_skippable():
    """solar-open2-250b as cut: 20 experts held under a router 320 wide, an
    eighth of the vocabulary, two periods of the published pattern; and at
    the rehearsal size the draws: decays a channel from ten to ten thousand
    tokens, a head's sink (key lane 0: a large bias on k, q shut, the
    slowest decay), a standing component on the v lanes and none on the
    other k lanes, an untied head, no gain at 1, no bias at 0."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "solar-open2-250b.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size, cfg.num_layers) == (320, (0, 20), 24576, 8)
    assert len(config["gqa_layers"]) == 12 and cfg.layer_types == ("attention", "kda", "kda", "kda") * 2
    assert cfg.num_state_layers == 6 and cfg.num_attention_layers == 2 and cfg.state_layer_kind == "kda"
    assert (cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_conv, cfg.kda_gate_rank) == (64, 128, 4, 128)
    assert cfg.attn_gate and cfg.kda_neg_eigval and not cfg.tie_word_embeddings
    shapes = fam.weight_shapes(config)
    assert shapes["layers"]["router"] == (8, 4096, 320) and shapes["layers"]["w_gate"] == (8, 20, 4096, 1280)
    assert shapes["kda"]["wq"] == (6, 4096, 8192) and shapes["kda"]["w_f1"] == (6, 4096, 128)
    assert shapes["kda"]["w_g2"] == (6, 128, 8192) and shapes["kda"]["conv_w"] == (6, 4, 24576)
    assert shapes["attn"]["wk"] == (2, 4096, 1024) and shapes["attn"]["w_ogate"] == (2, 4096, 8192)
    assert shapes["embed"] == (24576, 4096) and shapes["lm_head"] == (4096, 24576)

    with open(os.path.join(BENCH, "configs", "rehearse-solar-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    kda = w["kda"]
    step = jax.nn.softplus(kda["dt_bias"])
    assert fam.DT_RANGE[0] * 0.99 <= float(step.min()) and float(step.max()) <= fam.DT_RANGE[1] * 1.01
    rate = jnp.exp(kda["A_log"])
    assert fam.A_RANGE[0] * 0.99 <= float(rate.min()) and float(rate.max()) <= fam.A_RANGE[1] * 1.01
    assert float(jnp.exp(-rate.max() * step.max())) > 0.9  # the fastest channel at a zero projection
    H, d = 4, 16
    sink = np.tile(np.arange(d) < fam.SINK_LANES, H)
    assert fam.SINK_LANES == 1 and int(sink.sum()) == H
    assert np.allclose(np.asarray(step)[:, sink], fam.DT_RANGE[0], rtol=1e-3)  # the slowest channel
    bias = np.asarray(kda["conv_b"]).reshape(3, 3, H * d)  # [layer, q | k | v, lanes]
    assert abs(float(bias[:, 0][:, ~sink].mean()) - fam.Q_BIAS_MEAN) < 0.05
    assert abs(float(bias[:, 1][:, ~sink].mean()) - fam.K_BIAS_MEAN) < 0.05
    assert abs(float(bias[:, 0][:, sink].mean()) - fam.Q_SINK_BIAS) < 0.1
    assert abs(float(bias[:, 1][:, sink].mean()) - fam.K_SINK_BIAS) < 0.1
    assert abs(float(bias[:, 2].mean()) - fam.V_BIAS_MEAN) < 0.05
    for leaf in jax.tree.leaves(w):  # nothing at a value that lets a path skip it
        assert float(jnp.abs(leaf.astype(jnp.float32)).min()) > 0.0 or leaf.size > 1000
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.0
    assert abs(float(jnp.std(kda["wo"])) * np.sqrt(64) / fam.KDA_OUT_SCALE - 1.0) < 0.1
    assert abs(float(jnp.std(w["layers"]["w_down"])) * np.sqrt(32) / fam.ROUTED_OUT_SCALE - 1.0) < 0.1
    assert abs(float(jnp.std(w["lm_head"])) * np.sqrt(64) - 1.0) < 0.05


def test_the_mimo_family_is_the_cut_and_draws_sinks_that_take_their_share():
    """mimo-v2-flash as cut: 16 experts held under a router 256 wide, an
    eighth of the vocabulary, published layer 0 and one period of the
    pattern (read from the published lists at `layers_held`); and at the
    rehearsal size the draws: sinks inside SINK_RANGE (a fifth to three
    quarters of a window head's mass), a selection bias a few score spacings wide,
    float32 where the family says, no gain at 1, nothing at 0."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "mimo-v2-flash.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size, cfg.num_layers) == (256, (0, 16), 19072, 7)
    assert len(config["hybrid_layer_pattern"]) == len(config["moe_layer_freq"]) == 48
    assert config["layers_held"] == [0, 6, 7, 8, 9, 10, 11]
    assert cfg.layer_types == ("attention",) + ("window",) * 5 + ("attention",)
    assert cfg.first_k_dense_replace == 1 and cfg.n_shared_experts == 0
    assert (cfg.num_kv_heads, cfg.window_kv_heads, cfg.head_dim, cfg.value_head_dim) == (4, 8, 192, 128)
    assert (cfg.rotary_dim, cfg.sliding_window, cfg.attn_value_scale) == (64, 128, 0.707)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.window_sink) == (5e6, 1e4, True)
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob) == ("sigmoid", "noaux_tc", True)
    shapes = fam.weight_shapes(config)
    assert shapes["layers"]["router"] == (6, 4096, 256) and shapes["layers"]["w_gate"] == (6, 16, 4096, 2048)
    assert shapes["dense_layers"]["w_gate"] == (1, 4096, 16384)
    assert shapes["attn"]["wk"] == (2, 4096, 4 * 192) and shapes["attn"]["wv"] == (2, 4096, 4 * 128)
    assert shapes["attn_w"]["wk"] == (5, 4096, 8 * 192) and shapes["attn_w"]["wo"] == (5, 64 * 128, 4096)
    assert shapes["attn_w"]["sink"] == (5, 64) and shapes["lm_head"] == (4096, 19072)
    sizes = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    norms = 2 * 7 * 4096 + 4096 + 5 * 64 + 6 * 256  # gains, sinks, selection bias
    assert sum(int(np.prod(s)) for s in sizes) - norms == 3_429_892_096

    with open(os.path.join(BENCH, "configs", "rehearse-mimo-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    sink = w["attn_w"]["sink"]
    assert fam.SINK_RANGE[0] <= float(sink.min()) and float(sink.max()) <= fam.SINK_RANGE[1]
    share = jnp.exp(sink) / (jnp.exp(sink) + 128 * np.exp(0.5))  # scores ~ N(0, 1) over 128 keys
    assert 0.19 < float(share.min()) and float(share.max()) < 0.76
    assert abs(float(jnp.std(w["layers"]["router_bias"])) * 8 / fam.ROUTER_BIAS_SPACINGS - 1.0) < 0.3
    for name in fam.FLOAT32_LEAVES:
        assert all(g[name].dtype == jnp.float32 for g in w.values() if isinstance(g, dict) and name in g)
    for leaf in jax.tree.leaves(w):  # nothing at a value that lets a path skip it
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.0
    assert abs(float(jnp.std(w["layers"]["w_down"])) * np.sqrt(32) / fam.ROUTED_OUT_SCALE - 1.0) < 0.1
    assert abs(float(jnp.std(w["lm_head"])) * np.sqrt(64) - 1.0) < 0.05


def test_the_laguna_family_is_the_cut_and_draws_a_gate_that_is_no_constant():
    """laguna-xs.2 as cut: published layers 0-4 of 40 and NOTHING else cut
    (all 256 experts, both head counts, the whole vocabulary; the published
    per-layer lists whole and read at `layers_held`; every number of the
    catalog row's config but the depth); and at the rehearsal size the
    draws: the gate's pre-activation ~ N(0, 1) a head (sigmoid(g) spreads,
    it is no constant), the routed experts' down matrices at
    ROUTED_OUT_SCALE, the dense and shared down matrices at MLP_OUT_GAIN,
    both output projections plain (a gain there makes the routing
    degenerate: families/laguna.py), no gain at 1, nothing at 0."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert config["reduced"] == ["num_hidden_layers"] and config["layers_held"] == [0, 1, 2, 3, 4]
    assert (config["num_hidden_layers"], config["num_hidden_layers_published"]) == (5, 40)
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert len(config[key]) == 40, key
    assert config["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size, cfg.num_layers) == (256, (0, 256), 100352, 5)
    assert cfg.layer_types == ("attention", "window", "window", "window", "attention")
    assert (cfg.first_k_dense_replace, cfg.n_shared_experts, cfg.routed_scaling_factor) == (1, 1, 2.5)
    assert (cfg.attn_heads("attention"), cfg.attn_heads("window"), cfg.num_kv_heads) == (48, 64, 8)
    assert (cfg.rotary_dim, cfg.window_rotary_dim, cfg.sliding_window) == (64, 128, 512)
    assert (cfg.rope_scaling_type, cfg.rope_scaling_factor, cfg.rope_beta_fast) == ("yarn", 64.0, 64.0)
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob) == ("sigmoid", "plain", True)
    assert cfg.attn_gate and cfg.attn_gate_per_head and not cfg.window_sink
    shapes = fam.weight_shapes(config)
    assert shapes["layers"]["router"] == (4, 2048, 256) and shapes["layers"]["w_gate"] == (4, 256, 2048, 512)
    assert shapes["layers"]["w_sh_down"] == (4, 512, 2048) and shapes["dense_layers"]["w_gate"] == (1, 2048, 8192)
    assert shapes["attn"]["wq"] == (2, 2048, 48 * 128) and shapes["attn"]["w_ogate"] == (2, 2048, 48)
    assert shapes["attn_w"]["wo"] == (3, 64 * 128, 2048) and shapes["attn_w"]["w_ogate"] == (3, 2048, 64)
    sizes = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    norms = 2 * 5 * 2048 + 2048
    assert sum(int(np.prod(s)) for s in sizes) - norms == 3_869_835_264  # 7.74 GB in bfloat16
    # the count that settles the gate: the published 33.4 B without a per-lane gate
    E, D = 2048, 128
    attn = lambda h: E * (2 * h * D + 2 * 8 * D)
    whole = (39 * (256 * 3 * E * 512 + 3 * E * 512 + E * 256) + 2 * 100352 * E + 3 * E * 8192
             + 10 * attn(48) + 30 * attn(64))
    assert round(whole / 1e9, 2) == 33.44
    assert round((whole + E * D * (10 * 48 + 30 * 64)) / 1e9, 2) == 34.07  # a gate a lane
    assert E * (10 * 48 + 30 * 64) < 5e6  # a gate a head

    with open(os.path.join(BENCH, "configs", "rehearse-laguna-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    u = jax.random.normal(jax.random.key(1), (512, 64))
    g = jax.nn.sigmoid(u @ w["attn_w"]["w_ogate"][0])
    assert 0.8 < float(jnp.std(u @ w["attn"]["w_ogate"][0])) < 1.2
    assert float(g.min()) < 0.15 and float(g.max()) > 0.85 and 0.15 < float(jnp.std(g)) < 0.25
    for leaf in jax.tree.leaves(w):  # nothing at a value that lets a path skip it
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.0
    std = lambda a, fan_in: float(jnp.std(a)) * np.sqrt(fan_in)
    assert abs(std(w["layers"]["w_down"], 32) / fam.ROUTED_OUT_SCALE - 1.0) < 0.1
    assert abs(std(w["layers"]["w_sh_down"], 32) / fam.MLP_OUT_GAIN - 1.0) < 0.1
    assert abs(std(w["dense_layers"]["w_down"], 96) / fam.MLP_OUT_GAIN - 1.0) < 0.1
    assert abs(std(w["attn"]["wo"], 96) - 1.0) < 0.1 and abs(std(w["attn_w"]["wo"], 128) - 1.0) < 0.1
    assert abs(std(w["lm_head"], 64) - 1.0) < 0.05


def test_the_falcon_h1_family_is_the_cut_and_draws_against_its_multipliers():
    """falcon-h1-34b as cut: 9 of 72 blocks, an eighth of both vocabulary
    matrices, every block the parallel kind at the published widths and
    multipliers; and at the rehearsal size the draws: every matrix behind
    a multiplier at the INVERSE of it (so that no branch vanishes and the
    scores have spread), float32 where the family says, no gain at 1,
    nothing at 0."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert (cfg.num_layers, cfg.vocab_size, cfg.layer_types) == (9, 32640, ("parallel",) * 9)
    assert (config["num_hidden_layers_published"], config["vocab_size_published"]) == (72, 261120)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rotary_dim) == (20, 4, 128, 128)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state) == (32, 128, 2, 256)
    assert cfg.ssm_multipliers == tuple(config["ssm_multipliers"]) and len(cfg.ssm_multipliers) == 5
    assert cfg.mlp_multipliers == tuple(config["mlp_multipliers"]) and not cfg.is_moe
    assert (cfg.key_multiplier, cfg.attention_out_multiplier, cfg.lm_head_multiplier) == (
        0.011048543456039804, 0.0375, 0.0078125)
    shapes = fam.weight_shapes(config)
    assert shapes["mamba"]["w_in"] == (9, 5120, 9248) and shapes["mamba"]["conv_w"] == (9, 4, 5120)
    assert shapes["attn"]["wq"] == (9, 5120, 2560) and shapes["attn"]["wk"] == (9, 5120, 512)
    assert shapes["layers"]["w_gate"] == (9, 5120, 21504) and shapes["lm_head"] == (5120, 32640)
    sizes = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    small = 9 * (2 * 5120 + 5120 + 3 * 32 + 4096) + 5120  # norms, conv bias, per-head vectors
    assert sum(int(np.prod(s)) for s in sizes) - small == 4_205_137_920
    assert fam.lane_multipliers(config).shape == (9248,)

    with open(os.path.join(BENCH, "configs", "rehearse-falcon-h1-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    for name in fam.FLOAT32_LEAVES:
        assert all(g[name].dtype == jnp.float32 for g in w.values() if isinstance(g, dict) and name in g)
    for leaf in jax.tree.leaves(w):  # nothing at a value that lets a path skip it
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.0
    std = lambda a: float(jnp.std(a))
    E, F, d_in = 64, 96, 256
    assert abs(std(w["attn"]["wo"]) * np.sqrt(96) * tiny["attention_out_multiplier"] / fam.ATTN_OUT_GAIN - 1) < 0.1
    assert abs(std(w["mamba"]["w_out"]) * np.sqrt(d_in) * tiny["ssm_out_multiplier"] / fam.MAMBA_OUT_GAIN - 1) < 0.1
    assert abs(std(w["layers"]["w_gate"]) * np.sqrt(E) * tiny["mlp_multipliers"][0] - 1) < 0.1
    assert abs(std(w["layers"]["w_down"]) * np.sqrt(F) * tiny["mlp_multipliers"][1] / fam.MLP_OUT_GAIN - 1) < 0.1
    assert abs(std(w["lm_head"]) * np.sqrt(E) * tiny["lm_head_multiplier"] - 1) < 0.1
    assert abs(std(w["embed"]) * tiny["embedding_multiplier"] - 1) < 0.1
    # each part of w_in's lanes at the inverse of ITS multiplier (z | x | B | C | dt)
    parts = np.cumsum([0, d_in, d_in, 64, 64, 16])
    for i, mult in enumerate(tiny["ssm_multipliers"]):
        lanes = w["mamba"]["w_in"][..., parts[i]:parts[i + 1]]
        assert abs(std(lanes) * np.sqrt(E) * mult * tiny["ssm_in_multiplier"] - 1) < 0.15, i
    # a score's std is SCORE_STD under unit-RMS inputs
    qk = std(w["attn"]["wq"]) * std(w["attn"]["wk"]) * E
    assert abs(qk * tiny["key_multiplier"] * tiny["attention_in_multiplier"] ** 2 / fam.SCORE_STD - 1) < 0.1


def test_the_minicpm_sala_family_is_the_cut_and_draws_a_common_embedding_row():
    """minicpm-sala as cut: published layers 9-16 of 32 at every published
    width (a sparse layer, six lightning layers, a sparse layer), the whole
    vocabulary, the selection's constants the family's published ones; and
    at the rehearsal size the draw: every embedding row shares a common
    part (EMBED_COMMON of its RMS), the sparse layers' q and k gains stand
    around sqrt(QK_GAIN), each OUT gain is its matrix's, norm gains are
    float32, nothing at 0 and no gain at 1."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "minicpm-sala.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    assert (cfg.num_layers, cfg.vocab_size) == (8, 73448) and config["reduced"] == ["num_hidden_layers"]
    assert cfg.layer_types == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert config["num_hidden_layers_published"] == 32 == len(config["mixer_types"])
    assert [i for i, k in enumerate(config["mixer_types"]) if k == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert fam.held_layers(config) == [(9, "minicpm4")] + [
        (l, "lightning-attn") for l in range(10, 16)] + [(16, "minicpm4")]
    assert config["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                       "topk": 64, "init_blocks": 1, "window_size": 2048,
                                       "dense_len": 8192}
    assert fam.residual_scale(config) == 1.4 / 32 ** 0.5 == cfg.residual_multiplier
    shapes = fam.weight_shapes(config)
    assert shapes["attn"]["wq"] == (2, 4096, 4096) and shapes["attn"]["wk"] == (2, 4096, 256)
    assert shapes["lightning"]["wk"] == (6, 4096, 4096) and shapes["lightning"]["o_norm"] == (6, 4096)
    assert shapes["layers"]["w_gate"] == (8, 4096, 16384) and shapes["lm_head"] == (4096, 73448)
    sizes = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    small = 8 * 2 * 4096 + 4096 + 2 * 2 * 128 + 6 * (2 * 128 + 4096)  # every norm gain
    assert sum(int(np.prod(s)) for s in sizes) - small == 2_820_472_832
    # the decay: slow heads of a deep layer remember hundreds of tokens
    lam = fam.decay(config, 15)
    assert lam.shape == (32,) and 0.4 < lam[0] < 0.7 and 0.997 < lam[-1] < 0.999

    with open(os.path.join(BENCH, "configs", "rehearse-minicpm-sala-tiny.json")) as f:
        tiny = json.load(f)
    w = jax.jit(lambda k: fam.make_weights(tiny, k, jnp.float32))(family_mod.seed_key(3))
    for leaf in jax.tree.leaves(w):  # nothing at a value that lets a path skip it
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.0
    std = lambda a: float(jnp.std(a))
    E, F = 64, 96
    emb = w["embed"] * tiny["scale_emb"]
    common = emb.mean(axis=0)
    assert abs(float(jnp.sqrt(jnp.mean(common ** 2))) / fam.EMBED_COMMON - 1) < 0.25
    assert abs(float(jnp.sqrt(jnp.mean(emb ** 2))) - 1) < 0.15  # h0 has unit RMS
    assert abs(float(w["attn"]["q_norm"].mean()) ** 2 / fam.QK_GAIN - 1) < 0.1
    assert abs(float(w["lightning"]["q_norm"].mean()) - 1) < 0.1
    assert abs(std(w["attn"]["wo"]) * np.sqrt(E) / fam.ATTN_OUT_GAIN - 1) < 0.1
    assert abs(std(w["lightning"]["wo"]) * np.sqrt(E) / fam.LIGHTNING_OUT_GAIN - 1) < 0.1
    assert abs(std(w["layers"]["w_down"]) * np.sqrt(F) / fam.MLP_OUT_GAIN - 1) < 0.1
    assert abs(std(w["lm_head"]) * np.sqrt(E) / (E / tiny["dim_model_base"]) - 1) < 0.1
    assert all(w[g][k].dtype == jnp.float32 for g in ("attn", "lightning", "layers")
               for k in w[g] if k.endswith("norm"))
