"""The Laguna family (HF `model_type: laguna`, poolside): window layers (the
last 512 positions) beside full GQA layers that differ in QUERY heads (64
against 48, both over 8 KV heads of 128: query groups of 8 and 6), in
rotary LANES (all 128 against the first 64) and in the rotary TABLE (plain
theta 1e4 against YaRN x 64 over theta 5e5 with an attention factor), a
sigmoid gate a HEAD on every layer's attention output, a dense first layer
and then 256 sigmoid-scored experts of width 512 (top 8, renormalised,
times 2.5) beside one shared expert, as one family file of the benchmark
(the five names of benchmarks/harness/family.py; see families/llama.py).

What is computed, with `rms` RMSNorm (eps `rms_norm_eps`, a learned gain),
`t` the layer's kind (`layer_types` at the layers held) and `Hq_t` its
query heads (`num_attention_heads_per_layer`):

    h0 = E[token]
    u  = rms_1(h)
    q  = u Wq_t -> [Hq_t, 128];  k = u Wk -> [8, 128];  v = u Wv -> [8, 128]     (no bias)
    full:    q, k: rotary on lanes 0..63 of every head (partial_rotary_factor 0.5), lanes 64..127 pass;
             frequencies YaRN's over dim 64 (`yarn_inv_freq`: HF's _compute_yarn_parameters, the
             correction range floored and ceiled); cos and sin times attention_factor
             (the rotated lanes of q AND k carry it, the others do not)
    window:  q, k: rotary on all 128 lanes, theta 1e4, no scaling
    s_ij = q_i . k_j / sqrt(128),  j <= i   (window: and j > i - 512);  p = softmax_j(s);  no sink
    o_head = sum_j p_ij v_j       (query head a reads KV head a // (Hq_t / 8))
    g = sigmoid(u Wg_t) -> [Hq_t];   h <- h + concat_heads(g_head * o_head) Wo_t
    u2 = rms_2(h)
    dense layer:  h <- h + W_down(silu(W_gate u2) * W_up u2)                       width 8192
    sparse layer: sc = sigmoid(u2 W_r) [256];  chosen = top-8 of sc
                  w = moe_routed_scaling_factor * sc[chosen] / sum sc[chosen]
                  h <- h + sum over chosen e of w_e * FFN_e(u2) + FFN_shared(u2)   (SwiGLU, width 512 each)
    logits = rms_f(h_L) W_head                                                     (untied)

Readings the published config does not settle by a key, each under
`assumed` in the configuration file with its reason: the gate per HEAD and
its sigmoid (the published parameter total is met without a per-lane gate);
sigmoid scores, top 8 over all experts with no groups and no selection
bias, renormalised then scaled (the DeepSeek-V3 convention
`moe_routed_scaling_factor` comes from); the shared expert added
unweighted; no QK-norm; split-half rotary pairs; the window as j > i - 512.

The configuration is one pipeline stage of a deployment (its file's
`deployment`): the published layers `layers_held`, every expert, head and
vocabulary row of them.

No cache, no pool, no table, no kernel: every position's scores are
materialised against the whole sequence, a block of QUERY_BLOCK query rows
and one KV head's group at a time, and a window is a mask. The program
serves chunks of 512 tokens through two paged pools whose window blocks
are freed behind the sequence; that they agree is what `correct` checks.
float32, matmul precision "highest", plain jax.numpy, one expert at a
time, the head in vocabulary blocks, YaRN's frequencies computed here from
the configuration's numbers. Nothing is imported from the program but
ModelConfig (in `model_config`)."""

from __future__ import annotations

import math
from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell laguna-xs.2.agent-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 1.6e-3, "deficit_max": 0.5}
LIMITS_READINGS = (
    "laguna-xs.2 (published layers 0-4 of 40, all 256 experts, both head counts, the whole "
    "vocabulary) bf16 weights and both K/V pools, TPU v5 lite, PR 60 (my chip runs, calls 4-11, "
    "the cell's own size, 512 served tokens a run), the draw as committed: plain output "
    "projections, ROUTED_OUT_SCALE 0.05. Sound: 45 runs on 45 seeds, 6.28e-4 to 9.52e-4 (2 of control_laguna.py --mode sound 7.2e-4 "
    "and 7.4e-4, 43 of the cell at eight rates and three schedules), 469 to 489 of 512 argmaxes "
    "the reference's, deficit_max 0.066 to 0.240. Controls at this draw, judged by "
    "check.judge on the chip at seed 6000000501, whose sound run read 7.2e-4, smallest first: "
    "the chosen experts weighed by softmax probabilities 2.66e-3; int8 weights 4.86e-3; "
    "moe_routed_scaling_factor left out 5.17e-3; and at seed 6000000901 (calls 8 and 10, the "
    "files git would commit): every pair through the next expert's matrices 0.0278; the "
    "window layers attending every position their table still names 0.0656; a window block "
    "freed one block early 0.0851; a window of 128 for 512 0.907; head 0's gate on every "
    "head 3.08; the gate left out 5.10; the shared expert left out 5.71; the attention "
    "factor left out 6.18; YaRN left out (plain theta 5e5) 9.20; the two kinds' tables "
    "exchanged 12.7; their rotary lanes exchanged 15.5: 14 of 14 not correct, and a "
    "32,768-token prompt through 64 chunks 6.36e-4 (sound). All fourteen were read on this "
    "PR's FIRST draw too (call 2: a gain of 4 on both output projections, ROUTED_OUT_SCALE "
    "0.13, sound 9.95e-5 to 1.46e-4 over 12 seeds): 8.09e-4 to 2.867 beside that draw's "
    "limit of 3.4e-4, in another order: the controls that change attention or whole "
    "branches read larger on the committed draw, which makes them a larger share of the "
    "stream (the "
    "attention-factor control reads 6.18 where it read 0.559). logprob_mse 1.6e-3 is the "
    "geometric mean of the sound largest (9.52e-4) and the smallest control (2.66e-3): 1.7x "
    "over the one, 1.7x under the other. The draw decides it: a sound run is "
    "3.4e-4 of bfloat16 rounding plus 0.156 x ROUTED_OUT_SCALE^2 of top-8 flips (7.2e-4 to "
    "7.4e-4 at 0.05, 1.69e-3 to 2.27e-3 at 0.1: every flip lands on a held expert), "
    "softmax-scores 8e-5 + 1.03 x scale^2 (2.66e-3, 1.04e-2), int8 weights 4.86e-3 and "
    "8.90e-3: at 0.05 the flips are half of a sound run's number and the controls stand 3.6x "
    "over it. deficit_max: sound 0.066 to 0.240 (two runs of "
    "forty-five over 0.182); softmax-scores 0.188, int8 weights 0.240 and no-scale 0.348 pass "
    "0.5, stale-block 1.12, no-attn-factor 4.97 and the other gross controls (0.61 to 7.2) "
    "do not: 0.5 is a gross-error guard at twice the sound largest (the Llama family's "
    "0.25 would fail a sound run in a few dozen), logprob_mse alone refuses every control."
)

# --- the draw ---------------------------------------------------------------
# As families/mimo.py and families/falcon_h1.py: each constant is a draw made
# so that a control separates (PERF.md section 2 has the readings).
#
# The routed experts' down matrices are drawn at ROUTED_OUT_SCALE of the
# plain draw. ALL 256 experts are held, so a token brings 8 pairs a layer
# and EVERY flip at the top-8 boundary (sigmoid scores a hundredth apart,
# moved by bfloat16 noise in the normed row) lands on a held expert, sixteen
# times as often as in families/mimo.py at 0.3: the scale keeps a flip from
# setting a sound run's number while `wrong-expert` and `no-scale` (a change
# of the WHOLE routed sum) stay far over the limit. Read on the chip at 0.05
# and 0.1 (LIMITS_READINGS): a sound run is 3.4e-4 + 0.156 scale^2.
ROUTED_OUT_SCALE = 0.05
# The dense MLP's and the shared expert's down matrices at MLP_OUT_GAIN of the
# plain draw: a plain MLP adds 0.6 a lane to the stream while a head that
# averages hundreds of value rows, halved by its gate, adds 0.1 to 0.2, so
# with plain MLPs attention is a tenth of the stream; at 0.5 it is a quarter
# to a third and every control of the rotary tables, the gate and the window
# reads orders over the limit. The output projections are PLAIN draws: this
# PR's first draw gave them a gain of 4, and the mean of a layer's attention
# output (the same average of the same value rows for every query of a long
# context) became 0.83 to 0.93 of a normed row: every token's router scores
# shared one offset and a 512-row chunk touched 40 to 90 of the 256 experts
# where a plain draw touches 255 (benchmarks/tests/study_laguna.py reads it
# with no engine; on the chip the cell's touched share read 27 %).
MLP_OUT_GAIN = 0.5

QUERY_BLOCK = 256  # query rows of one attention block

KINDS = {"full_attention": "attention", "sliding_attention": "window"}


def layers_held(m: Mapping) -> tuple:
    """The published layers this configuration runs, in order."""
    held = tuple(m.get("layers_held", range(m["num_hidden_layers"])))
    if len(held) != m["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    return held


def layer_types(m: Mapping) -> tuple:
    """The mixers of the layers HELD: `layer_types` (kept whole as
    published) at `layers_held`, in the program's names."""
    return tuple(KINDS[m["layer_types"][l]] for l in layers_held(m))


def dense_layers(m: Mapping) -> int:
    """How many of the layers held have the dense MLP (`mlp_layer_types`
    "dense"); they come first."""
    kinds = [m["mlp_layer_types"][l] for l in layers_held(m)]
    kd = kinds.index("sparse") if "sparse" in kinds else len(kinds)
    if any(k != "sparse" for k in kinds[kd:]):
        raise ValueError("this family: the dense layers are a prefix of the layers held")
    return kd


def query_heads(m: Mapping, kind: str) -> int:
    """Query heads of the held layers of `kind`
    (`num_attention_heads_per_layer` at `layers_held`: one count a kind)."""
    counts = {m["num_attention_heads_per_layer"][l]
              for l, k in zip(layers_held(m), layer_types(m)) if k == kind}
    if len(counts) != 1:
        raise ValueError(f"this family: one query-head count a layer kind, {kind}: {counts}")
    return counts.pop()


def rope_of(m: Mapping, kind: str) -> Mapping:
    key = "full_attention" if kind == "attention" else "sliding_attention"
    return m["rope_parameters"][key]


def rotary_lanes(m: Mapping, kind: str) -> int:
    return int(float(rope_of(m, kind)["partial_rotary_factor"]) * m["head_dim"])


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies [dim / 2] (HF `_compute_yarn_parameters`,
    arXiv:2309.00071): pair i keeps theta ** (-2 i / dim) below the
    correction range's low end (fast pairs: extrapolated), takes it over
    `factor` above the high end (slow pairs: interpolated), and a linear
    ramp between; the ends are the pairs that turn beta_fast and beta_slow
    times over `original` positions, floored and ceiled."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp  # 1: the pair keeps its own frequency
    return ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolated)
            + (1.0 / pos_freqs) * extrapolated).astype(np.float32)


def rotary_table(m: Mapping, kind: str):
    """(lanes, inv_freq [lanes / 2], what cos and sin are multiplied by)."""
    r, lanes = rope_of(m, kind), rotary_lanes(m, kind)
    theta = float(r["rope_theta"])
    if r["rope_type"] == "default":
        inv = 1.0 / theta ** (np.arange(0, lanes, 2, dtype=np.float64) / lanes)
        return lanes, inv.astype(np.float32), 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"this family: rope_type default or yarn, not {r['rope_type']!r}")
    inv = yarn_inv_freq(lanes, theta, float(r["factor"]),
                        int(r["original_max_position_embeddings"]),
                        float(r["beta_fast"]), float(r["beta_slow"]))
    return lanes, inv, float(r["attention_factor"])


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "window_num_heads" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/laguna.py: this program's ModelConfig has no `window_num_heads`: it "
            "cannot run window and full attention layers of different query heads, rotary "
            "lanes and rotary tables (the configuration needs the program of PR 60 or later)"
        )
    full, window = rope_of(m, "attention"), rope_of(m, "window")
    if m.get("tie_word_embeddings") or m.get("attention_bias") or not m.get("gating") \
            or m.get("moe_apply_router_weight_on_input") \
            or m["shared_expert_intermediate_size"] != m["moe_intermediate_size"] \
            or full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError("this family: an untied head, no bias, a gate on every layer, router "
                         "weights on the output, one shared expert of the routed width, YaRN "
                         "on the full layers and a plain table on the window layers")
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=query_heads(m, "attention"),
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(full["rope_theta"]),
        rope_scaling_type="yarn",
        rope_scaling_factor=float(full["factor"]),
        rope_original_max_position=int(full["original_max_position_embeddings"]),
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        num_experts=m["num_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_shared_experts=1,
        scoring_func="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=float(m["moe_routed_scaling_factor"]),
        first_k_dense_replace=dense_layers(m),
        layer_types=layer_types(m),
        sliding_window=m["sliding_window"],
        window_kv_heads=m["num_key_value_heads"],
        window_num_heads=query_heads(m, "window"),
        window_rope_theta=float(window["rope_theta"]),
        rotary_dim=rotary_lanes(m, "attention"),
        window_rotary_dim=rotary_lanes(m, "window"),
        attn_gate=True,
        attn_gate_per_head=True,
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    kinds = layer_types(m)
    La, Lw, kd = kinds.count("attention"), kinds.count("window"), dense_layers(m)
    Hkv, D, X = m["num_key_value_heads"], m["head_dim"], m["num_experts"]
    Fm, Fs, F, Lm = (m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
                     m["intermediate_size"], L - kd)

    def gqa(layers, heads):
        return {"wq": (layers, E, heads * D), "wk": (layers, E, Hkv * D),
                "wv": (layers, E, Hkv * D), "wo": (layers, heads * D, E),
                "w_ogate": (layers, E, heads)}

    return {
        "embed": (V, E), "lm_head": (E, V), "final_norm": (E,),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E), "router": (Lm, E, X),
            "w_gate": (Lm, X, E, Fm), "w_up": (Lm, X, E, Fm), "w_down": (Lm, X, Fm, E),
            "w_sh_gate": (Lm, E, Fs), "w_sh_up": (Lm, E, Fs), "w_sh_down": (Lm, Fs, E),
        },
        "dense_layers": {"w_gate": (kd, E, F), "w_up": (kd, E, F), "w_down": (kd, F, E)},
        "attn": gqa(La, query_heads(m, "attention")),
        "attn_w": gqa(Lw, query_heads(m, "window")),
    }


GROUPS = ("layers", "dense_layers", "attn", "attn_w")
# a leaf's draw is the plain one times this (the draw, above)
GAINS = {
    ("layers", "w_down"): ROUTED_OUT_SCALE, ("layers", "w_sh_down"): MLP_OUT_GAIN,
    ("dense_layers", "w_down"): MLP_OUT_GAIN,
}


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: the norms of every layer and what a routed layer
    has; `dense_layers`: the dense prefix; `attn`, `attn_w`: the two kinds
    of mixer, each with its gate); traceable. Matrices ~ N(0, 1/fan_in)
    times their GAINS entry; norm gains ~ N(1, 0.1) in float32. The gate's
    matrix is a plain draw: a normed row gives g ~ N(0, 1) a head, so
    sigmoid(g) spreads over (0.1, 0.9) and is no constant. Nothing is left
    at a value (0 or 1) that would let a path skip it. A leaf is drawn one
    leading slice at a time (one layer; one expert of a layer), so the
    float32 normals of the expert matrices never stand whole."""
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
        fan_in = shape[-1] if leaf == "embed" else shape[-2]
        std = GAINS.get(name, 1.0) / np.sqrt(fan_in)
        lead = shape[:-2] if name[0] else ()
        n = int(np.prod(lead, dtype=np.int64))

        def one(kk):
            return (jax.random.normal(kk, shape[len(lead):], f32) * std).astype(dtype)

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


def rope(x, lanes: int, inv_freq, factor: float):
    """x [T, H, D] at positions 0..T-1: lanes [0, lanes) rotate (pairs
    (i, i + lanes / 2) by position * inv_freq[i], cos and sin times
    `factor`), the rest pass."""
    import jax.numpy as jnp

    T, half = x.shape[0], lanes // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = factor * jnp.cos(ang)[:, None, :], factor * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., lanes:]], axis=-1)


def attention(u, lp, m: Mapping, kind: str):
    """The gated GQA mixer's output [T, E] of a full or a window layer:
    materialised scores against the whole sequence, QUERY_BLOCK query rows
    of one KV head's group at a time; each head's output times its gate."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, Hkv, D = query_heads(m, kind), m["num_key_value_heads"], m["head_dim"]
    g, table = Hq // Hkv, rotary_table(m, kind)
    window = kind == "window"
    q = rope((u @ lp["wq"]).reshape(T, Hq, D), *table).reshape(T, Hkv, g, D)
    k = rope((u @ lp["wk"]).reshape(T, Hkv, D), *table)
    v = (u @ lp["wv"]).reshape(T, Hkv, D)
    gate = jax.nn.sigmoid(u @ lp["w_ogate"])  # [T, Hq]
    qb = min(QUERY_BLOCK, T)
    pad = -T % qb
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, qb, Hkv, g, D)
    cols = jnp.arange(T)

    def block(args):  # one block of query rows, every head
        qi, rows = args  # [qb, Hkv, g, D], [qb] positions
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen = seen & (cols[None, :] > rows[:, None] - m["sliding_window"])

        def kv_head(i):
            s = jnp.einsum("qgd,kd->gqk", qi[:, i], k[:, i]) / np.sqrt(D)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, v[:, i])

        o = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, qb, g, D]
        return jnp.moveaxis(o, 0, 1).reshape(qb, Hq, D)

    rows = jnp.arange(T + pad).reshape(-1, qb)
    o = jax.lax.map(block, (q, rows)).reshape(-1, Hq, D)[:T]
    return (gate[:, :, None] * o).reshape(T, Hq * D) @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(u, router, m: Mapping):
    """Combine weights [T, experts]: the chosen experts' sigmoid scores
    over their sum, times moe_routed_scaling_factor; 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    sc = jax.nn.sigmoid(u @ router)
    w, top_i = jax.lax.top_k(sc, m["num_experts_per_tok"])
    w = float(m["moe_routed_scaling_factor"]) * w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(sc).at[jnp.arange(T)[:, None], top_i].set(w)


def expert_layer(u, leaves, layer: int, m: Mapping):
    """The expert block's output [T, E] for normed hidden u: the chosen
    experts, weighted, plus the shared expert. `leaves` the stacked
    `layers` leaves as stored (any dtype; one expert is upcast at a time),
    `layer` the routed layer's entry."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = route(u, leaves["router"][layer].astype(f32), m)

    def one(e, acc):
        wg, wu, wd = (leaves[k][layer, e].astype(f32) for k in ("w_gate", "w_up", "w_down"))
        return acc + w[:, e, None] * _swiglu(u, wg, wu, wd)

    routed = jax.lax.fori_loop(0, m["num_experts"], one, jnp.zeros_like(u))
    return routed + _swiglu(u, *(leaves[k][layer].astype(f32)
                                 for k in ("w_sh_gate", "w_sh_up", "w_sh_down")))


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position: every mixer is causal), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(m["rms_norm_eps"])
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
            x = x + attention(u, lp, m, kind)
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
