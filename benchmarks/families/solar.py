"""The Solar-Open2 family (HF `model_type: solar_open2`): Kimi Delta
Attention layers (a gated delta rule with a decay per channel, Kimi
Linear, arXiv:2510.26692) and gated NoPE GQA layers in one stack, a top-k
expert block beside a shared expert in every layer, as one family file of
the benchmark (the five names of benchmarks/harness/family.py; see
families/llama.py).

What is computed, with `h` the residual stream, `rms` RMSNorm with a
learned gain and `eps = rms_norm_eps`:

    h0 = E[token]
    every layer l:  h <- h + mixer_l(rms_1(h))
                    u  = rms_2(h)
                    h <- h + experts(u) + shared(u)
    logits = rms_f(h_L) @ W_head                              (untied head)

Layer l is "attention" where l is in `gqa_layers`, else "kda" (H heads,
key and value width d, K taps), with x = rms_1(h):

    [q~ | k~ | v~] = [W_q x | W_k x | W_v x]            (H d each), no bias
    c_t = silu(sum_{j<K} w_j * [q~|k~|v~]_{t-K+1+j} + b)   depthwise, causal, zero history
    q = l2norm(c_q) / sqrt(d);  k = l2norm(c_k);  v = c_v        per head
    g = -exp(A_log_h) * softplus(W_f2 (W_f1 x) + dt_bias)   in R^d per head: PER CHANNEL
    beta = 2 sigmoid(w_b,h . x)               (kda_allow_neg_eigval: the factor 2)
    S <- Diag(exp(g)) S;  S <- S - beta k (k^T S) + beta k v^T;  o = S^T q     (S [d, d], from 0)
    out = W_o [ sigmoid(W_g2 (W_g1 x)) * rms_o(o) ]       rms_o over a head's d lanes, one gain

`l2norm(x) = x / sqrt(sum x^2 + 1e-6)`.

"attention": GQA, no bias, NO rotary (`use_rope: false`), scores
`q.k / sqrt(head_dim)`, causal softmax in float32, and the output gated
per lane before the out matrix, `W_o [ sigmoid(W_gate x) * attn ]`
(`use_gqa_gate`).

Experts: `g = W_r u` over ALL published experts; the `num_experts_per_tok`
largest; weights = softmax over those chosen logits (`norm_topk_prob`),
times `routed_scaling_factor`; expert e is `W_down,e (silu(W_gate,e u) *
W_up,e u)`; the shared expert the same form at `n_shared_experts x
moe_intermediate_size`, always on, unweighted.

The configuration is one holder's share of a deployment (its file's
`deployment`): `experts_held` [first, count] of the published experts and
a slice of the vocabulary. What the absent experts would add to a layer is
LEFT OUT, here and in the program alike, and that partial result goes on
to the next layer: the reference is this holder's function.

This is the RECURRENT definition, token by token: no chunks, no carried
pool, no cache, no kernel. The program serves chunks of 512 tokens (8
chunks of 64 of the delta rule's chunk form) through a state pool and a
paged cache; that they agree is what `correct` checks. float32, matmul
precision "highest", plain jax.numpy, one expert at a time, the head in
vocabulary blocks. Nothing is imported from the program but ModelConfig
(in `model_config`)."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell solar-open2-250b.think-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 4.8e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "solar-open2-250b (8 of 48 layers, experts 0-19 of 320, 24,576 of 196,608 "
    "vocabulary rows) bf16 weights and K/V, float32 state, TPU v5 lite, PR 46's review "
    "round (my chip runs, calls G and H, the cell's own size, 512 served tokens a run, "
    "the draw as committed below: a sink lane a head). Sound: 6 runs on 6 seeds 1.036e-4 to 1.907e-4 (3 of control_solar.py --mode sound 1.036e-4, 1.233e-4, 1.457e-4; 3 runs of the cell on schedule_seed 13: 1.493e-4 traced at 4.8 req/s, 1.907e-4 at 6, 1.342e-4 at 7), every argmax but 3 to 20 of 512 the reference's, deficit_max at most 0.036. Controls, judged "
    "by check.judge on the chip, smallest first: int8 weights 1.19e-3; every held pair "
    "through the next held expert's matrices 1.95e-3; THE STATE POOLS HELD IN BFLOAT16 "
    "2.37e-3, 2.63e-3, 6.72e-3 on the three seeds whose sound runs read 1.23e-4, "
    "1.04e-4, 1.46e-4 (19x, 25x, 46x seed by seed; the smallest 16x the sound "
    "largest); the delta-rule state dropped at every chunk boundary 8.02e-2: 6 of 6 control runs not correct. logprob_mse 4.8e-4 is the geometric mean of the sound largest and "
    "the smallest control: 2.5x over the sound largest, 2.5x under int8 weights, 4.9x under the bfloat16 state's smallest (the runs were judged at 2.6e-4 and at 4.2e-4; the limit moved when the cell's run at 6 req/s read 1.907e-4, and no verdict changes). The first round's draw (no sink, a standing value "
    "of 8, A a decade lower) read a bfloat16 state at 1.02e-4 to 1.62e-4 INSIDE its "
    "sound band of 6.76e-5 to 1.372e-4 (30 runs) and passed it: after 64 decode steps "
    "the state's roundings are a random walk of 2**-9 a step beside bfloat16 "
    "activations' own 2**-9 a rounding; what tells them apart is the delta rule's "
    "prediction of a standing value, exact in float32 and 8 bits in bfloat16 (the "
    "draw's comment below). The six controls not read again on the chip with this "
    "draw (scalar-decay, beta-1x, no-delta, no-conv-carry, no-gate, no-shared: the "
    "round had 40 chip-minutes) read, with the first one, 1.30e-2 (scalar-decay) to "
    "0.806 (no-shared) there, and with this one on the CPU (a 4-layer cut at hidden "
    "256 through control_solar.py --rehearse, 3 seeds each; no device number) 7.9e-2 "
    "(beta-1x) to 0.58 (no-shared) beside sound runs of 6.3e-5 to 9.2e-5 there. deficit_max: sound at "
    "most 0.036; state-bf16 0.185 to 0.286, int8 weights 0.209 and wrong-expert 0.126 "
    "straddle it: 0.25 is a gross-error guard (the Llama family's), logprob_mse "
    "alone refuses every control."
)

# --- the draw ---------------------------------------------------------------
# As families/granite.py: each constant is a draw made so that a control
# separates (PERF.md section 2 has the readings).
#
# The logits. The head is untied and the embedding plain, N(0, 1 / hidden):
# a token's row has an RMS of 1 / sqrt(hidden) beside layer outputs of
# about 1, so the LAYERS carry the stream and the logits (std about 1 under
# a final gain of about 1).
#
# The routed experts' out matrices are drawn at ROUTED_OUT_SCALE of the plain
# draw. 20 of 320 are held, so a token brings half a pair a layer and a
# routing flip at the top-8 boundary lands on a held expert once in sixteen:
# the scale is what makes the held pairs a readable share of the stream
# (`wrong-expert`) without a flip setting a sound run's number.
ROUTED_OUT_SCALE = 0.3

# The delta rule, and what holds its state to float32 (`state-bf16`). A
# bfloat16 state is rounded by 2**-9 of each ENTRY at every step; a sound
# run carries 2**-9 of every bfloat16 ACTIVATION. While what the state holds
# and what a token brings are of one size, the two are one kind of noise and
# 64 decode steps cannot tell them apart (PERF.md section 2: some fifty
# draws read 1.0x to 1.7x, and a random walk of sqrt(64) roundings is all a
# mild draw can show). What tells them apart is the one place where float32
# is far finer than any activation: the delta rule's own PREDICTION,
# `v_t - k_t^T S`, the difference of two large things that float32 knows to
# 2**-24 because the large part comes from float32 leaves (the convolution's
# bias), not from a bfloat16 row. So each head has a SINK: key lane 0 carries
# a bias of K_SINK_BIAS (after the l2 norm that lane is 0.99 of the key,
# the same for every token to a part in 1e4), the values carry a bias of
# V_BIAS_MEAN on every lane (v = V_BIAS_MEAN + the token's own part of
# about 1), and the sink's row of the state learns that standing value
# within a few tokens. From then on `v_t - k_t^T S` is the token's OWN part,
# exact in float32, and that residual is what the other 127 rows store under
# the token's key and what q reads: q's lane 0 has a bias of Q_SINK_BIAS, so
# q never reads the sink's row and the output is the residuals' memory, not
# the standing value (a bfloat16 activation could not carry the one beside
# the other). Held in bfloat16, the sink's row is V_BIAS_MEAN to 8 bits:
# every prediction is off by about V_BIAS_MEAN * 2**-9 = 0.1 on a token's
# own part of about 1, the rows store that, and the read-out is wrong by a
# tenth where a sound run is wrong by a few parts in a thousand. The sink's
# row decays slowest of the head's channels (DT_RANGE's low end); the other
# channels' decays span A dt = 1e-4 to 1e-1 a token (memories of ten to ten
# thousand tokens: the decay PER CHANNEL is what `scalar-decay` breaks), so
# that what the first tokens wrote before the sink had learnt (a residual of
# V_BIAS_MEAN, not of 1) is gone from the quick channels by the check's
# first served token and no more than the residuals' own sum in the slow
# ones (with A a decade lower, one seed in three of a CPU study read a sound
# run at 5e-4). Sinks are a thing trained models have (a first token
# or a lane with massive activations that attention parks on); here it is a
# draw made so that a control separates, as families/granite.py's are. The
# KDA mixers' out matrices are drawn at KDA_OUT_SCALE of the plain draw, so
# that what the state layers say is most of the stream.
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1e-1, 1.0)
Q_BIAS_MEAN = -3.0
K_BIAS_MEAN = 0.0
V_BIAS_MEAN = 50.0
SINK_LANES = 1  # key lanes of a head, from lane 0, that are its sink
K_SINK_BIAS = 40.0
Q_SINK_BIAS = -30.0
BETA_GAIN = 1.0
KDA_OUT_SCALE = 3.0
L2_EPS = 1e-6


def held_experts(m: Mapping):
    """(first, count) of the routed experts this configuration holds, and
    the published count the router is as wide as."""
    published = int(m.get("n_routed_experts_published", m["n_routed_experts"]))
    first, count = m.get("experts_held", (0, published))
    if int(count) != int(m["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts disagree")
    return int(first), int(count), published


def layer_types(m: Mapping) -> tuple:
    """The mixers of the layers HELD: the first `num_hidden_layers` of the
    published pattern (`gqa_layers` is kept whole)."""
    gqa = set(m["gqa_layers"])
    return tuple("attention" if l in gqa else "kda" for l in range(m["num_hidden_layers"]))


def dims(m: Mapping):
    la = m["linear_attn_config"]
    H, d = la["num_heads"], la["head_dim"]
    return H, d, la["short_conv_kernel_size"], int(m.get("kda_gate_rank", d))


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "kda_n_heads" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/solar.py: this program's ModelConfig has no `kda_n_heads`: it cannot run "
            "a stack of KDA and attention layers (the configuration needs the program of "
            "PR 46 or later)"
        )
    if m.get("use_rope") or m.get("tie_word_embeddings") or m.get("first_k_dense_replace") \
            or m.get("kda_use_full_proj") or not m.get("norm_topk_prob"):
        raise ValueError("this family: NoPE, an untied head, experts in every layer, low-rank "
                         "gate projections, renormalised top-k weights")
    first, count, published = held_experts(m)
    H, d, K, rank = dims(m)
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["moe_intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        num_experts=published,
        experts_held=(first, count),
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_shared_experts=m["n_shared_experts"],
        norm_topk_prob=True,
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        layer_types=layer_types(m),
        kda_n_heads=H, kda_d_head=d, kda_d_conv=K, kda_gate_rank=rank,
        kda_neg_eigval=bool(m["kda_allow_neg_eigval"]),
        attn_gate=bool(m["use_gqa_gate"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    kinds = layer_types(m)
    Lk, La = kinds.count("kda"), kinds.count("attention")
    H, d, K, r = dims(m)
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    _, held, published = held_experts(m)
    Fm = m["moe_intermediate_size"]
    Fs = m["n_shared_experts"] * Fm
    attn = {"wq": (La, E, Hq * D), "wk": (La, E, Hkv * D), "wv": (La, E, Hkv * D),
            "wo": (La, Hq * D, E)}
    if m["use_gqa_gate"]:
        attn["w_ogate"] = (La, E, Hq * D)
    return {
        "embed": (V, E), "lm_head": (E, V), "final_norm": (E,),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E), "router": (L, E, published),
            "w_gate": (L, held, E, Fm), "w_up": (L, held, E, Fm), "w_down": (L, held, Fm, E),
            "w_sh_gate": (L, E, Fs), "w_sh_up": (L, E, Fs), "w_sh_down": (L, Fs, E),
        },
        "kda": {
            "wq": (Lk, E, H * d), "wk": (Lk, E, H * d), "wv": (Lk, E, H * d),
            "conv_w": (Lk, K, 3 * H * d), "conv_b": (Lk, 3 * H * d),
            "w_f1": (Lk, E, r), "w_f2": (Lk, r, H * d), "dt_bias": (Lk, H * d), "A_log": (Lk, H),
            "w_beta": (Lk, E, H), "w_g1": (Lk, E, r), "w_g2": (Lk, r, H * d),
            "o_norm": (Lk, d), "wo": (Lk, H * d, E),
        },
        "attn": attn,
    }


FLOAT32_LEAVES = ("conv_w", "conv_b", "dt_bias", "A_log")  # and every norm gain


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: what every layer has; `kda`, `attn`: the mixers);
    traceable. Matrices ~ N(0, 1/fan_in) (the routed experts' out matrices
    ROUTED_OUT_SCALE and the KDA mixers' KDA_OUT_SCALE of that); norm gains
    ~ N(1, 0.1) and the convolution's weights ~ N(0, 1/K), its bias
    ~ N(mean, 0.1) with the means of the draw above (q, k, v lanes; a
    head's sink lanes their own), in float32; dt_bias and A_log as
    DT_RANGE and A_RANGE say, a sink lane at DT_RANGE's low end. Nothing is left at a
    value (0 or 1) that would let a path skip it. A leaf is drawn one
    leading slice at a time (one layer; one expert of a layer), so the
    float32 normals of the expert matrices never stand whole."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    groups = ("layers", "kda", "attn")
    names = [(g, k) for g in groups for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in groups]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    f32 = jnp.float32
    H, d, _, _ = dims(m)

    def draw(name, shape):
        k, leaf = keys[name], name[1]
        if leaf.endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, f32)
        if leaf == "conv_w":
            return jax.random.normal(k, shape, f32) / np.sqrt(shape[-2])
        if leaf == "conv_b":  # lanes [q | k | v]
            means = np.repeat(np.asarray((Q_BIAS_MEAN, K_BIAS_MEAN, V_BIAS_MEAN), np.float32), H * d)
            sink = np.tile(np.arange(d) < SINK_LANES, H)
            means[:H * d][sink] = Q_SINK_BIAS
            means[H * d:2 * H * d][sink] = K_SINK_BIAS
            return means + 0.1 * jax.random.normal(k, shape, f32)
        if leaf == "dt_bias":  # softplus^-1 of a log-uniform step
            lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
            dt = jnp.exp(jax.random.uniform(k, shape, f32, lo, hi))
            dt = jnp.where(np.tile(np.arange(d) < SINK_LANES, H), DT_RANGE[0], dt)
            return dt + jnp.log(-jnp.expm1(-dt))
        if leaf == "A_log":
            lo, hi = np.log(A_RANGE[0]), np.log(A_RANGE[1])
            return jax.random.uniform(k, shape, f32, lo, hi)
        fan_in = shape[-2]
        if leaf == "embed":
            fan_in = shape[-1]
        if name == ("kda", "wo"):
            fan_in = fan_in / KDA_OUT_SCALE ** 2
        if name == ("kda", "w_beta"):
            fan_in = fan_in / BETA_GAIN ** 2
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

    out = {k: draw((None, k), s) for k, s in shapes.items() if k not in groups}
    for g in groups:
        out[g] = {k: draw((g, k), s) for k, s in shapes[g].items()}
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kda_mixer(u, lp, m: Mapping):
    """The KDA mixer's output [T, E] for normed hidden u [T, E]; `lp` this
    layer's float32 leaves. The recurrence, token by token."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, d, K, _ = dims(m)
    qkv = jnp.concatenate([u @ lp["wq"], u @ lp["wk"], u @ lp["wv"]], axis=-1)
    hist = jnp.pad(qkv, ((K - 1, 0), (0, 0)))  # zero history before the first token
    c = lp["conv_b"] + sum(lp["conv_w"][j] * hist[j:j + T] for j in range(K))
    c = jax.nn.silu(c)
    q, k, v = (c[:, i * H * d:(i + 1) * H * d].reshape(T, H, d) for i in range(3))
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    q, k = l2(q) / np.sqrt(d), l2(k)
    g = jax.nn.softplus((u @ lp["w_f1"]) @ lp["w_f2"] + lp["dt_bias"]).reshape(T, H, d)
    g = -jnp.exp(lp["A_log"])[:, None] * g
    factor = 2.0 if m["kda_allow_neg_eigval"] else 1.0
    beta = factor * jax.nn.sigmoid(u @ lp["w_beta"])  # [T, H]

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S  # [H, d(key), d(value)]
        seen = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + float(m["rms_norm_eps"]))
    gate = jax.nn.sigmoid((u @ lp["w_g1"]) @ lp["w_g2"]).reshape(T, H, d)
    return (gate * o * lp["o_norm"]).reshape(T, H * d) @ lp["wo"]


def attention(u, lp, m: Mapping):
    """The gated GQA mixer's output [T, E]: materialised scores, one KV
    head's group of query heads at a time."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    g = Hq // Hkv
    q = (u @ lp["wq"]).reshape(T, Hkv, g, D)
    k = (u @ lp["wk"]).reshape(T, Hkv, D)
    v = (u @ lp["wv"]).reshape(T, Hkv, D)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    def kv_head(i):
        s = jnp.einsum("qgd,kd->gqk", q[:, i], k[:, i]) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v[:, i])

    o = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, T, g, D]
    o = jnp.moveaxis(o, 0, 1).reshape(T, Hq * D)
    if m["use_gqa_gate"]:
        o = jax.nn.sigmoid(u @ lp["w_ogate"]) * o
    return o @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(u, router, m: Mapping):
    """Combine weights [T, published]: routed_scaling_factor x the softmax
    over the chosen logits on the chosen experts, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    logits = u @ router
    top_v, top_i = jax.lax.top_k(logits, m["num_experts_per_tok"])
    w = jax.nn.softmax(top_v, axis=-1) * float(m["routed_scaling_factor"])
    return jnp.zeros_like(logits).at[jnp.arange(T)[:, None], top_i].set(w)


def expert_layer(u, leaves, layer: int, m: Mapping, shared: bool = True, span=None):
    """The expert block's output [T, E] for normed hidden u, as THIS
    holder computes it: the chosen experts it holds, and the shared
    expert. `leaves` the stacked `layers` leaves as stored (any dtype; one
    expert is upcast at a time). `span` (first, count), inside the held
    span, replaces it and `shared` False leaves the shared expert out (the
    sixteen-holder test)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    stored_first, held, _ = held_experts(m)  # the leaves hold this span
    first = stored_first
    if span is not None:
        first, held = span
    w = route(u, leaves["router"][layer].astype(f32), m)[:, first:first + held]

    def one(e, acc):
        wg, wu, wd = (leaves[k][layer, first - stored_first + e].astype(f32)
                      for k in ("w_gate", "w_up", "w_down"))
        return acc + w[:, e, None] * _swiglu(u, wg, wu, wd)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
    if shared:
        y = y + _swiglu(u, *(leaves[k][layer].astype(f32)
                             for k in ("w_sh_gate", "w_sh_up", "w_sh_down")))
    return y


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position: every mixer is causal), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(m["rms_norm_eps"])
    mixers = {"kda": ("kda", kda_mixer), "attention": ("attn", attention)}
    kinds = layer_types(m)
    # the published pattern repeats (`A K K K`): one period's layers are
    # written out and the periods are a loop, so the compiled reference
    # holds each kind of layer once a period and not once a layer held
    n = next(n for n in range(1, len(kinds) + 1)
             if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n))
    common = weights["layers"]

    def period(p, x):
        for j, kind in enumerate(kinds[:n]):
            l = p * n + j
            of_kind = p * kinds[:n].count(kind) + kinds[:j].count(kind)
            stack, mixer = mixers[kind]
            lp = {k: v[of_kind].astype(f32) for k, v in weights[stack].items()}
            x = x + mixer(_rms_norm(x, common["attn_norm"][l].astype(f32), eps), lp, m)
            u = _rms_norm(x, common["mlp_norm"][l].astype(f32), eps)
            x = x + expert_layer(u, common, l, m)
        return x

    with jax.default_matmul_precision("highest"):
        x = jax.lax.fori_loop(0, len(kinds) // n, period, weights["embed"][tokens].astype(f32))
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        head = weights["lm_head"]
        V = head.shape[1]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the head in vocabulary blocks
            cols = jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1)
            return h @ cols.astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)
