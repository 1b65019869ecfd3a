"""The MiMo-V2-Flash family (HF `model_type: mimo_v2_flash`): window layers
(the last 128 positions, a learned sink logit a head, 8 KV heads) beside
full GQA layers (4 KV heads) in one stack, key heads of 192 lanes and
value heads of 128, rotary on a third of a head, a dense first layer and
then 256 sigmoid-scored experts (top 8, no shared one), as one family file
of the benchmark (the five names of benchmarks/harness/family.py; see
families/llama.py).

What is computed, with `rms` RMSNorm (eps `layernorm_epsilon`, a learned
gain) and `t` the layer's kind (`hybrid_layer_pattern`: 0 full, 1 window):

    u  = rms_1(h)
    q  = u Wq          -> [64, 192]         (no bias)
    k  = u Wk_t        -> [Hkv_t, 192]      Hkv_full = 4, Hkv_window = 8
    v  = 0.707 * (u Wv_t) -> [Hkv_t, 128]   (attention_value_scale)
    q, k: RoPE on lanes 0..63 of every head (rotary_dim = int(0.334 * 192) = 64),
          theta_full = 5e6, theta_window = 1e4; lanes 64..191 pass
    s_ij = q_i . k_j / sqrt(192),  j <= i          (window: and j > i - 128)
    full:    p_ij = exp(s_ij) / sum_j' exp(s_ij')
    window:  p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(b_head))      b [64] learned; the sink's mass is dropped
    h <- h + concat_heads(sum_j p_ij v_j) Wo       Wo [64 * 128, 4096]
    u2 = rms_2(h)
    layer 0:      h <- h + W_down(silu(W_gate u2) * W_up u2)            width 16384
    layers 1..:   sc = sigmoid(u2 W_r) [256]; chosen = top-8 of (sc + b_r) (b_r for selection only)
                  w = sc[chosen] / (sum sc[chosen] + 1e-20)
                  h <- h + sum over chosen e HELD HERE of w_e * FFN_e(u2)   (SwiGLU, width 2048; no shared expert; no scaling)
    logits = rms_f(h_L) W_head                                          (untied)

Departures and readings, each under `assumed` in the configuration file:
`attention_value_scale` multiplies the values (the same result as scaling
the attention output, sink or not); the rotary pairing inside the 64 lanes
is split-half (lane i with lane i + 32: random weights cannot see it);
`attention_chunk_size` 128 enters no equation; the 3 multi-token-prediction
layers of the model card are not in the config and are left out; the
softmax scale is 192 ** -0.5.

The configuration is one holder's share of a deployment (its file's
`deployment`): the published layers `layers_held`, `experts_held` [first,
count] of the published experts and a slice of the vocabulary. What the
absent experts would add to a layer is LEFT OUT, here and in the program
alike, and that partial result goes on to the next layer: the reference is
this holder's function.

No cache, no pool, no table, no kernel: every position's scores are
materialised against the whole sequence, a block of QUERY_BLOCK query rows
and one KV head's group at a time (so that 16k tokens fit), and a window
is a mask. The program serves chunks of 512 tokens through two paged pools
whose window blocks are freed behind the sequence; that they agree is what
`correct` checks. float32, matmul precision "highest", plain jax.numpy,
one expert at a time, the head in vocabulary blocks. Nothing is imported
from the program but ModelConfig (in `model_config`)."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell mimo-v2-flash.longmix-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 4.5e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "mimo-v2-flash (7 of 48 layers, experts 0-15 of 256, 19,072 of 152,576 vocabulary "
    "rows) bf16 weights and both K/V pools, TPU v5 lite, PR 49 (my chip runs, calls 1-11, "
    "the cell's own size, 512 served tokens a run). Sound: 69 runs on 69 seeds, 8.41e-5 to "
    "1.771e-4 (66 runs of the cell at nine rates and three schedules, 3 of control_mimo.py "
    "--mode sound 1.01e-4 to 1.44e-4), 497 to 508 of 512 argmaxes the reference's, "
    "deficit_max at most 0.103; --mode long (one 15,360-token prompt through 30 chunks, "
    "window blocks freed behind it, then 64 greedy tokens) 2.17e-4, deficit 0.074. Controls, "
    "judged by check.judge on the chip at the seed whose sound run read 1.39e-4, smallest "
    "first: int8 weights 1.134e-3; every held pair through the next held expert's matrices "
    "4.36e-3; attention_value_scale left out 0.167; the window layers attending every "
    "position their table still names 0.254; no sink 0.904; the two thetas exchanged 0.917; "
    "a window block freed one block early and its entry left in the table 1.008; all 192 "
    "lanes rotated 2.04: 8 of 8 not correct. logprob_mse 4.5e-4 is the geometric mean of "
    "the sound largest and the smallest control: 2.5x over the sound largest (2.1x over the "
    "long run), 2.5x under int8 weights (the runs were judged at 4.0e-4; no verdict "
    "changes). NOT separable on the chip, and so no control there: the selection bias used "
    "as a weight (bias-weight 1.39e-4, a sound run's number: the bias is N(0, 0.005) at 256 "
    "experts, a hundredth of a chosen score, and the routed part is drawn at 0.3; the "
    "float32 tests on the CPU hold it: tests/test_mimo.py, benchmarks/tests/test_mimo.py). "
    "deficit_max: sound at most 0.103; int8 weights 0.090 passes it, wrong-expert 0.263 and "
    "the rest (1.3 to 3.8) do not: 0.25 is a gross-error guard (the Llama family's), "
    "logprob_mse alone refuses every control."
)

# --- the draw ---------------------------------------------------------------
# As families/granite.py and families/solar.py: each constant is a draw made
# so that a control separates (PERF.md section 2 has the readings).
#
# The routed experts' down matrices are drawn at ROUTED_OUT_SCALE of the
# plain draw. 16 of 256 are held, so a token brings half a pair a layer and
# a flip at the top-8 boundary lands on a held expert once in sixteen: the
# scale keeps the held pairs a readable share of the stream (`wrong-expert`)
# without a flip setting a sound run's number (families/solar.py: 0.3).
ROUTED_OUT_SCALE = 0.3
# The sink logits are drawn uniform in SINK_RANGE. A window head's scores
# are about N(0, 1) over at most 128 keys, so sum exp(s) is about 128 e^0.5
# = 211: a sink of 4.0 takes a fifth of the head's mass and one of 6.4
# three quarters. A sink of about 0 (a plain draw) would take half a
# percent, and `no-sink` could not be told from a sound run.
SINK_RANGE = (4.0, 6.4)
# The router's selection bias ~ N(0, ROUTER_BIAS_SPACINGS / published
# experts): a few times the distance between neighbouring scores at the
# top-k boundary, which shrinks as the experts grow in number (256 experts:
# the top 8 sigmoid scores of a normed row lie above about 0.87, a hundredth
# or two apart, and the bias is N(0, 0.005); 8 experts: N(0, 0.16)). That
# moves the choice at the boundary for a good share of the tokens (a bias
# left out, or used as a WEIGHT, is then seen by the float32 tests) and no
# more. It was N(0, 0.2) at every size in this PR's first runs on the chip:
# an expert with a bias of +0.2 is chosen eight times as often as one
# without, so the share of the pairs that fell to the SIXTEEN held experts
# swung from 0.30 to 0.68 a token and layer with the run's seed (0.5 is the
# model's number), the expert product is a quarter of a step's device time,
# and `tpot_p90_ms` followed it run for run (13.88 at 0.30, 15.52 at 0.68:
# a spread of 6.5 % over six seeds, over half the bound; PERF.md section 6).
ROUTER_BIAS_SPACINGS = 1.28

QUERY_BLOCK = 256  # query rows of one attention block


def held_experts(m: Mapping):
    """(first, count) of the routed experts this configuration holds, and
    the published count the router is as wide as."""
    published = int(m.get("n_routed_experts_published", m["n_routed_experts"]))
    first, count = m.get("experts_held", (0, published))
    if int(count) != int(m["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts disagree")
    return int(first), int(count), published


def layers_held(m: Mapping) -> tuple:
    """The published layers this configuration runs, in order."""
    held = tuple(m.get("layers_held", range(m["num_hidden_layers"])))
    if len(held) != m["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    return held


def layer_types(m: Mapping) -> tuple:
    """The mixers of the layers HELD: `hybrid_layer_pattern` (kept whole
    as published) at `layers_held`."""
    pattern = m["hybrid_layer_pattern"]
    return tuple("window" if pattern[l] else "attention" for l in layers_held(m))


def dense_layers(m: Mapping) -> int:
    """How many of the layers held have the dense MLP: `moe_layer_freq` 0;
    they come first."""
    freq = [m["moe_layer_freq"][l] for l in layers_held(m)]
    kd = freq.index(1) if 1 in freq else len(freq)
    if any(f != 1 for f in freq[kd:]):
        raise ValueError("this family: the dense layers are a prefix of the layers held")
    return kd


def rotary_dim(m: Mapping) -> int:
    return int(float(m["partial_rotary_factor"]) * m["head_dim"])


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "window_kv_heads" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/mimo.py: this program's ModelConfig has no `window_kv_heads`: it cannot "
            "run a stack of window and full attention layers (the configuration needs the "
            "program of PR 49 or later)"
        )
    if m.get("tie_word_embeddings") or m.get("attention_bias") or m.get("n_shared_experts") \
            or m.get("routed_scaling_factor") or not m.get("norm_topk_prob") \
            or m.get("add_full_attention_sink_bias") or not m.get("add_swa_attention_sink_bias") \
            or m["scoring_func"] != "sigmoid" or m["topk_method"] != "noaux_tc" \
            or m["n_group"] != 1 or m["swa_head_dim"] != m["head_dim"] \
            or m["swa_v_head_dim"] != m["v_head_dim"] \
            or m["swa_num_attention_heads"] != m["num_attention_heads"] \
            or m["sliding_window"] != m["sliding_window_size"]:
        raise ValueError("this family: an untied head, no bias, no shared expert, no scaling "
                         "factor, renormalised sigmoid top-k in one group, a sink on the window "
                         "layers alone, and both kinds of layer of one head count and widths")
    first, count, published = held_experts(m)
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["layernorm_epsilon"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        num_experts=published,
        experts_held=(first, count),
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        norm_topk_prob=True,
        first_k_dense_replace=dense_layers(m),
        layer_types=layer_types(m),
        sliding_window=m["sliding_window"],
        window_kv_heads=m["swa_num_key_value_heads"],
        window_rope_theta=float(m["swa_rope_theta"]),
        window_sink=True,
        attn_v_head_dim=m["v_head_dim"],
        rotary_dim=rotary_dim(m),
        attn_value_scale=float(m["attention_value_scale"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    kinds = layer_types(m)
    La, Lw, kd = kinds.count("attention"), kinds.count("window"), dense_layers(m)
    Hq, D, Dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    _, held, published = held_experts(m)
    Fm, F, Lm = m["moe_intermediate_size"], m["intermediate_size"], L - kd

    def gqa(layers, kv):
        return {"wq": (layers, E, Hq * D), "wk": (layers, E, kv * D),
                "wv": (layers, E, kv * Dv), "wo": (layers, Hq * Dv, E)}

    return {
        "embed": (V, E), "lm_head": (E, V), "final_norm": (E,),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E), "router": (Lm, E, published),
            "router_bias": (Lm, published),
            "w_gate": (Lm, held, E, Fm), "w_up": (Lm, held, E, Fm), "w_down": (Lm, held, Fm, E),
        },
        "dense_layers": {"w_gate": (kd, E, F), "w_up": (kd, E, F), "w_down": (kd, F, E)},
        "attn": gqa(La, m["num_key_value_heads"]),
        "attn_w": {**gqa(Lw, m["swa_num_key_value_heads"]), "sink": (Lw, Hq)},
    }


GROUPS = ("layers", "dense_layers", "attn", "attn_w")
FLOAT32_LEAVES = ("sink", "router_bias")  # and every norm gain


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: the norms of every layer and what a routed layer
    has; `dense_layers`: the dense prefix; `attn`, `attn_w`: the two kinds of
    mixer); traceable. Matrices ~ N(0, 1/fan_in) (the routed experts' down
    matrices ROUTED_OUT_SCALE of that); norm gains ~ N(1, 0.1), the sinks
    uniform in SINK_RANGE and the router's selection bias ~ N(0,
    ROUTER_BIAS_SPACINGS / experts), in float32. Nothing is left at a value (0 or 1) that
    would let a path skip it. A leaf is drawn one leading slice at a time
    (one layer; one expert of a layer), so the float32 normals of the
    expert matrices never stand whole."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    names = [(g, k) for g in GROUPS for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in GROUPS]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    f32 = jnp.float32

    def draw(name, shape):
        k, leaf = keys[name], name[1]
        if leaf.endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, f32)
        if leaf == "sink":
            return jax.random.uniform(k, shape, f32, *SINK_RANGE)
        if leaf == "router_bias":
            return ROUTER_BIAS_SPACINGS / shape[-1] * jax.random.normal(k, shape, f32)
        fan_in = shape[-1] if leaf == "embed" else shape[-2]
        if name == ("layers", "w_down"):
            fan_in = fan_in / ROUTED_OUT_SCALE ** 2
        lead = shape[:-2] if name[0] else ()
        n = int(np.prod(lead, dtype=np.int64))

        def one(kk):
            z = jax.random.normal(kk, shape[len(lead):], f32)
            return (z / np.sqrt(fan_in)).astype(dtype)

        if not lead:
            return one(k)
        return jax.lax.map(one, jax.random.split(k, n)).reshape(shape)

    out = {k: draw((None, k), s) for k, s in shapes.items() if k not in GROUPS}
    for g in GROUPS:
        out[g] = {k: draw((g, k), s) for k, s in shapes[g].items()}
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float, lanes: int):
    """x [T, H, D] at positions 0..T-1: lanes [0, lanes) rotate (pairs
    (i, i + lanes / 2), frequency theta ** (-2 i / lanes)), the rest pass."""
    import jax.numpy as jnp

    T, half = x.shape[0], lanes // 2
    inv = 1.0 / theta ** (np.arange(0, lanes, 2, dtype=np.float32) / lanes)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., lanes:]], axis=-1)


def attention(u, lp, m: Mapping, window: bool):
    """The GQA mixer's output [T, E] of a full or a window layer:
    materialised scores against the whole sequence, QUERY_BLOCK query rows
    of one KV head's group at a time."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, D, Dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    Hkv = m["swa_num_key_value_heads"] if window else m["num_key_value_heads"]
    theta = float(m["swa_rope_theta"] if window else m["rope_theta"])
    g, rd = Hq // Hkv, rotary_dim(m)
    q = rope((u @ lp["wq"]).reshape(T, Hq, D), theta, rd).reshape(T, Hkv, g, D)
    k = rope((u @ lp["wk"]).reshape(T, Hkv, D), theta, rd)
    v = float(m["attention_value_scale"]) * (u @ lp["wv"]).reshape(T, Hkv, Dv)
    qb = min(QUERY_BLOCK, T)
    pad = -T % qb
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, qb, Hkv, g, D)
    cols = jnp.arange(T)
    sink = lp["sink"].reshape(Hkv, g) if window else None

    def block(args):  # one block of query rows, every head
        qi, rows = args  # [qb, Hkv, g, D], [qb] positions
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen = seen & (cols[None, :] > rows[:, None] - m["sliding_window"])

        def kv_head(i):
            s = jnp.einsum("qgd,kd->gqk", qi[:, i], k[:, i]) / np.sqrt(D)
            s = jnp.where(seen[None], s, -jnp.inf)
            if window:  # one logit more, its mass dropped
                b = jnp.broadcast_to(sink[i][:, None, None], (g, qb, 1))
                p = jax.nn.softmax(jnp.concatenate([s, b], axis=-1), axis=-1)[..., :-1]
            else:
                p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, v[:, i])

        o = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, qb, g, Dv]
        return jnp.moveaxis(o, 0, 1).reshape(qb, Hq * Dv)

    rows = jnp.arange(T + pad).reshape(-1, qb)
    o = jax.lax.map(block, (q, rows)).reshape(-1, Hq * Dv)[:T]
    return o @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(u, router, bias, m: Mapping):
    """Combine weights [T, published]: the chosen experts' sigmoid scores
    over their sum (+ 1e-20), 0 elsewhere; chosen by score + bias."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    sc = jax.nn.sigmoid(u @ router)
    _, top_i = jax.lax.top_k(sc + bias, m["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, top_i, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(sc).at[jnp.arange(T)[:, None], top_i].set(w)


def expert_layer(u, leaves, layer: int, m: Mapping, span=None):
    """The expert block's output [T, E] for normed hidden u, as THIS
    holder computes it: the chosen experts it holds. `leaves` the stacked
    `layers` leaves as stored (any dtype; one expert is upcast at a time),
    `layer` the routed layer's entry. `span` (first, count), inside the
    held span, replaces it (the holders' test)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    stored_first, held, _ = held_experts(m)  # the leaves hold this span
    first = stored_first
    if span is not None:
        first, held = span
    w = route(u, leaves["router"][layer].astype(f32), leaves["router_bias"][layer].astype(f32),
              m)[:, first:first + held]

    def one(e, acc):
        wg, wu, wd = (leaves[k][layer, first - stored_first + e].astype(f32)
                      for k in ("w_gate", "w_up", "w_down"))
        return acc + w[:, e, None] * _swiglu(u, wg, wu, wd)

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position: every mixer is causal), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(m["layernorm_epsilon"])
    kinds, kd = layer_types(m), dense_layers(m)
    common = weights["layers"]
    of_kind = {"attention": 0, "window": 0}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32)
        for l, kind in enumerate(kinds):
            stack = weights["attn_w" if kind == "window" else "attn"]
            lp = {k: v[of_kind[kind]].astype(f32) for k, v in stack.items()}
            of_kind[kind] += 1
            u = _rms_norm(x, common["attn_norm"][l].astype(f32), eps)
            x = x + attention(u, lp, m, kind == "window")
            u = _rms_norm(x, common["mlp_norm"][l].astype(f32), eps)
            if l < kd:
                x = x + _swiglu(u, *(weights["dense_layers"][k][l].astype(f32)
                                     for k in ("w_gate", "w_up", "w_down")))
            else:
                x = x + expert_layer(u, common, l - kd, m)
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        head = weights["lm_head"]
        V = head.shape[1]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the head in vocabulary blocks
            cols = jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1)
            return h @ cols.astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)
