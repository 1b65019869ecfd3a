"""The Granite 4.0-H family (HF `model_type: granitemoehybrid`): Mamba-2
state-space layers and NoPE GQA layers in one stack, a top-k expert block
beside a shared MLP in every layer, as one family file of the benchmark
(the five names of benchmarks/harness/family.py; see families/llama.py).

What is computed, with `h` the residual stream, `rms` RMSNorm with a
learned gain and `eps = rms_norm_eps`:

    h0 = embedding_multiplier * E[token]
    every layer l:  h <- h + residual_multiplier * mixer_l(rms_1(h))
                    u  = rms_2(h)
                    h <- h + residual_multiplier * (experts(u) + shared(u))
    logits = (rms_f(h_L) @ E^T) / logits_scaling            (tied head)

`layer_types[l] == "mamba"` (H heads of P lanes, G groups, state N, K taps):

    [z | xBC | dt] = W_in u              (H P | H P + 2 G N | H), no bias
    xBC'_t = silu(sum_{j<K} w_j * xBC_{t-K+1+j} + b)    depthwise, causal, zero history
    [x | B | C] = xBC'
    dt_h = softplus(dt_h + dt_bias_h);   a_h = exp(-exp(A_log_h) dt_h)
    S_h <- a_h S_h + dt_h x_h B^T;       y_h = S_h C + D_h x_h       (S_h [P, N], from 0)
    y = rms_g(y * silu(z))   gate THEN norm, over each group's H P / G lanes
    out = W_out y

`"attention"`: GQA, no bias, NO rotary (`position_embedding_type: nope`),
scores scaled by `attention_multiplier` (NOT head_dim**-0.5), causal
softmax in float32.

Experts: `g = W_r u` over ALL published experts; the `num_experts_per_tok`
largest; weights = softmax over those chosen logits; expert e is
`W_out,e (silu(a) * b)`, `[a | b] = W_in,e u`; the shared MLP the same form
at `shared_intermediate_size`, always on.

The configuration is one holder's share of a deployment (its file's
`deployment`): `experts_held` [first, count] of the published experts and
a slice of the vocabulary. What the absent experts would add to a layer is
LEFT OUT, here and in the program alike, and that partial result goes on
to the next layer: the reference is this holder's function.

Departures from the released checkpoint, none of which random weights can
see: an expert's `input_linear` is two matrices here (`w_gate`, `w_up`),
`in_proj` one; the convolution weight is `[K, lanes]`.

This is the RECURRENT definition, token by token: no chunks, no carried
pool, no cache, no kernel. The program serves chunks of 256 tokens through
a state pool and a paged cache; that they agree is what `correct` checks.
float32, matmul precision "highest", plain jax.numpy, one expert at a
time, the head in vocabulary blocks. Nothing is imported from the program
but ModelConfig (in `model_config`)."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell granite-4.0-h-small.assist-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 1.4e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "granite-4.0-h-small (10 of 40 layers, experts 0-35 of 72, 50,176 of 100,352 "
    "vocabulary rows) bf16 weights and K/V, float32 state, TPU v5 lite, PR 42's "
    "review round (my chip runs, calls A1, A2 and B, the cell's own size, 512 "
    "served tokens a run, the draw as committed below). Sound: 9 runs on 9 seeds "
    "8.58e-5 to 1.012e-4 (3 of control_granite.py --mode sound 9.16e-5 to 9.31e-5, "
    "5 untraced runs of the cell 8.83e-5 to 1.012e-4, the long run below 8.58e-5), "
    "every argmax but 4 to 18 of 512 the reference's. Controls, smallest first, "
    "every mode judged by check.judge at the committed limits on the chip (call A2: "
    "9 of 9 control runs not correct; call A1's 5 ran under the old limit of 1.0e-5 "
    "and read above this one too): the state pools held in bfloat16 2.29e-4, "
    "2.36e-4, 2.45e-4, 2.69e-4, 3.56e-4 (5 seeds: 2.26x the sound largest at the "
    "least); int8 weights 6.81e-4, 7.74e-4, 8.31e-4; every held pair through the "
    "next held expert's matrices 1.84e-3; scores scaled by head_dim**-0.5 1.36e-2; "
    "the shared MLP left out 0.147; the convolution's carried rows never read "
    "0.159; the SSM state dropped at every chunk boundary 0.381. logprob_mse 1.4e-4 "
    "lies 1.38x over the sound largest and 1.64x under the bfloat16 state's "
    "smallest: under the 3x the contract asks for on both sides, because the two "
    "readings are only 2.26x apart; the sound band is 1.18x wide (sd 4.6 % of its "
    "mean), so the room below is eight of those, and the thin side is the "
    "control's. deficit_max: sound at most 0.035; the bfloat16 state 0.028 to "
    "0.116, int8 weights 0.076 to 0.100, wrong-expert 0.209 all pass it; attn-scale "
    "0.288 and the four gross faults 1.04 to 3.52 do not: 0.25 is a gross-error "
    "guard (the Llama family's), logprob_mse alone refuses every control. At sizes "
    "the check does not reach (--mode long | long-bf16: one 2048-token prompt "
    "through 8 chunks, then 256 greedy tokens, seed 89) a sound run reads 8.58e-5 "
    "and a bfloat16 state 2.21e-3, 26x. With the FIRST round's draw (plain "
    "embedding, ROUTED_OUT_SCALE 1, steps 1e-4..1e-2 under A 1..16, no standing "
    "component) every served token repeated the prompt's last one, sound runs "
    "spread 1.68e-6 to 5.24e-6 and a bfloat16 state read 3.38e-6 and 4.33e-6, "
    "inside them. PERF.md section 2."
)

# --- the draw ---------------------------------------------------------------
# Random weights make a model whose check reads little of what the family is
# for, unless the draw is made for it. Each constant below is a draw made so
# that a control separates; PERF.md section 2 says what each costs. The study
# behind them: a 6-layer cut at hidden 1024 served in bfloat16 on the CPU
# through control_granite.py's patches, 3 seeds a draw (PR 42's review round;
# no device number), then the chip at the cell's own size.
#
# The logits. With the plain draw, a tied head and embedding_multiplier 12 a
# token's own embedding stood 17 sigma over the other logits, which
# logits_scaling 16 flattened to a std of 0.06: every served token repeated
# the prompt's last one, `deficit_max` read 0.0 in sound and broken runs
# alike, and the 64 tokens of a prompt were one sample 64 times (sound runs
# spread 3x). The embedding's rows are drawn at EMBED_SCALE of the plain draw,
# so that the layers carry the stream (the bump is 12 e / rms(h), about one
# sigma), and the final norm's gain about FINAL_NORM_GAIN = 16 / EMBED_SCALE,
# so that the logits have a std of about 1, as the other families' have.
EMBED_SCALE = 1.0 / 16.0
FINAL_NORM_GAIN = 256.0

# The routed experts' out matrices are drawn at ROUTED_OUT_SCALE of the plain
# draw (families/deepseek.py: 0.1). Top-10 of 72 flips at its boundary under
# bfloat16 noise; with the logits the layers' those flips were four fifths
# of a sound run's `logprob_mse` and heavy-tailed (study: 1.0e-3 to 1.5e-3,
# deficits to 0.73, at a scale of 1; 1.9e-4 to 2.2e-4 at 0.1). At 0.3 a flip
# weighs a tenth of that and `wrong-expert` still reads 38x the sound runs
# (study; 20x on the chip).
ROUTED_OUT_SCALE = 0.3

# The scan. The configuration states a float32 SSM state; what separates it
# from a bfloat16 one in 64 decode steps is not rounding noise (a random walk
# of 2**-9 a step: 1.2x the sound runs on the chip, 1.0-2.0x over ten draws in
# the study, whatever the steps' size) but STAGNATION: a state that integrates a
# standing input over n tokens grows by 1/n a token, under half of bfloat16's
# last place from n = 512 on, so a bfloat16 state stops integrating while
# the float32 one goes on. So the x and B lanes of the convolution carry a
# standing component (CONV_BIAS_MEANS, x | B | C lanes: silu(2 + N(0, 1)) has
# a mean of 2 and a std of 1; C keeps a zero bias), the steps are Mamba-2's own
# (arXiv:2405.21060: dt log-uniform in 1e-3..1e-1 at a zero projection) and A
# is drawn two decades under its init's 1..16, so that A dt is
# 1e-6..1e-2 and nearly every head remembers past the check's 832 tokens;
# the Mamba mixers' out matrices are drawn at MAMBA_OUT_SCALE of the plain
# draw, so that what the state layers say is most of the stream. D about 1.
# The state dropped at chunk boundaries (`zero-carry`) reads 400x and more
# (study; 4000x on the chip).
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1e-3, 1e-1)
CONV_BIAS_MEANS = (2.0, 2.0, 0.0)
MAMBA_OUT_SCALE = 3.0


def qk_gain(m: Mapping) -> float:
    """Variance of an entry of q and of k under unit-RMS inputs: such that
    attention_multiplier x q.k has unit variance, 1 / (multiplier x
    sqrt(head_dim)): sqrt(128) at the published 1/128, and 1 where the
    multiplier is head_dim**-0.5. The multiplier is the muP one (1 / d, not
    1 / sqrt d): it expects q and k that training has aligned, and under a
    plain N(0, 1 / fan_in) draw its scores have a std of 0.09, the softmax is
    flat, the GQA layer an average of V, and a fault in it all but
    invisible (scores scaled by head_dim**-0.5 read 2.1x the sound runs; my
    chip runs, PR 42, call 2)."""
    D = m["hidden_size"] // m["num_attention_heads"]
    return 1.0 / (float(m["attention_multiplier"]) * D ** 0.5)


def held_experts(m: Mapping):
    """(first, count) of the routed experts this configuration holds, and
    the published count the router is as wide as."""
    published = int(m.get("num_local_experts_published", m["num_local_experts"]))
    first, count = m.get("experts_held", (0, published))
    if int(count) != int(m["num_local_experts"]):
        raise ValueError("experts_held and num_local_experts disagree")
    return int(first), int(count), published


def layer_types(m: Mapping) -> tuple:
    """The mixers of the layers HELD: the first `num_hidden_layers` of the
    published pattern (the file keeps the published list whole)."""
    return tuple(m["layer_types"][: m["num_hidden_layers"]])


def dims(m: Mapping):
    H, P = m["mamba_n_heads"], m["mamba_d_head"]
    G, N = m["mamba_n_groups"], m["mamba_d_state"]
    if H * P != m["mamba_expand"] * m["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    return H, P, G, N, H * P, H * P + 2 * G * N


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "layer_types" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/granite.py: this program's ModelConfig has no `layer_types`: it cannot run "
            "a stack of Mamba-2 and attention layers (the configuration needs the program of "
            "PR 42 or later)"
        )
    if m.get("position_embedding_type") != "nope" or m.get("attention_bias") \
            or m.get("mamba_proj_bias") or not m.get("mamba_conv_bias") \
            or not m.get("tie_word_embeddings"):
        raise ValueError("this family: NoPE, no projection bias, a convolution bias, a tied head")
    if m["shared_intermediate_size"] % m["intermediate_size"]:
        raise ValueError("shared_intermediate_size is not a multiple of intermediate_size")
    first, count, published = held_experts(m)
    H, P, G, N, _, _ = dims(m)
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=True,
        num_experts=published,
        experts_held=(first, count),
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["intermediate_size"],
        n_shared_experts=m["shared_intermediate_size"] // m["intermediate_size"],
        layer_types=layer_types(m),
        mamba_d_state=N, mamba_d_conv=m["mamba_d_conv"], mamba_n_heads=H,
        mamba_d_head=P, mamba_n_groups=G,
        embedding_multiplier=float(m["embedding_multiplier"]),
        attention_multiplier=float(m["attention_multiplier"]),
        residual_multiplier=float(m["residual_multiplier"]),
        logits_scaling=float(m["logits_scaling"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    kinds = layer_types(m)
    Lm, La = kinds.count("mamba"), kinds.count("attention")
    H, _, _, _, d_in, conv = dims(m)
    Hq, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = E // Hq
    _, held, published = held_experts(m)
    Fm, Fs, K = m["intermediate_size"], m["shared_intermediate_size"], m["mamba_d_conv"]
    return {
        "embed": (V, E), "final_norm": (E,),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E), "router": (L, E, published),
            "w_gate": (L, held, E, Fm), "w_up": (L, held, E, Fm), "w_down": (L, held, Fm, E),
            "w_sh_gate": (L, E, Fs), "w_sh_up": (L, E, Fs), "w_sh_down": (L, Fs, E),
        },
        "mamba": {
            "w_in": (Lm, E, d_in + conv + H), "conv_w": (Lm, K, conv), "conv_b": (Lm, conv),
            "dt_bias": (Lm, H), "A_log": (Lm, H), "D": (Lm, H), "gate_norm": (Lm, d_in),
            "w_out": (Lm, d_in, E),
        },
        "attn": {
            "wq": (La, E, Hq * D), "wk": (La, E, Hkv * D), "wv": (La, E, Hkv * D),
            "wo": (La, Hq * D, E),
        },
    }


FLOAT32_LEAVES = ("conv_w", "conv_b", "dt_bias", "A_log", "D")  # and every norm gain


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: what every layer has; `mamba`, `attn`: the mixers);
    traceable. Matrices ~ N(0, 1/fan_in) (the embedding EMBED_SCALE, the
    routed experts' out matrices ROUTED_OUT_SCALE and the Mamba mixers'
    MAMBA_OUT_SCALE of that, `wq` and `wk` with `qk_gain`); norm gains
    ~ N(1, 0.1) (the final norm's FINAL_NORM_GAIN times that) and the
    convolution's weights ~ N(0, 1/K), its bias ~ N(CONV_BIAS_MEANS, 0.1), in
    float32; dt_bias, A_log and D as DT_RANGE and A_RANGE say. Nothing is left at a
    value (0 or 1) that would let a path skip it. A leaf is drawn one
    leading slice at a time (one layer; one expert of a layer), so the
    float32 normals of the expert matrices never stand whole."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    groups = ("layers", "mamba", "attn")
    names = [(g, k) for g in groups for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in groups]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    f32 = jnp.float32

    def draw(name, shape):
        k, leaf = keys[name], name[1]
        if name == (None, "final_norm"):
            return FINAL_NORM_GAIN * (1.0 + 0.1 * jax.random.normal(k, shape, f32))
        if leaf.endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, f32)
        if leaf == "conv_w":
            return jax.random.normal(k, shape, f32) / np.sqrt(shape[-2])
        if leaf == "conv_b":  # lanes [x | B | C]
            _, _, G, N, d_in, _ = dims(m)
            means = np.repeat(np.asarray(CONV_BIAS_MEANS, np.float32), (d_in, G * N, G * N))
            return means + 0.1 * jax.random.normal(k, shape, f32)
        if leaf == "dt_bias":  # softplus^-1 of a log-uniform step
            lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
            dt = jnp.exp(jax.random.uniform(k, shape, f32, lo, hi))
            return dt + jnp.log(-jnp.expm1(-dt))
        if leaf == "A_log":
            return jnp.log(jax.random.uniform(k, shape, f32, *A_RANGE))
        if leaf == "D":
            return 1.0 + 0.1 * jax.random.normal(k, shape, f32)
        fan_in = shape[-1] / EMBED_SCALE ** 2 if leaf == "embed" else shape[-2]
        if name == ("mamba", "w_out"):
            fan_in = fan_in / MAMBA_OUT_SCALE ** 2
        if name == ("layers", "w_down"):
            fan_in = fan_in / ROUTED_OUT_SCALE ** 2
        if name in (("attn", "wq"), ("attn", "wk")):
            fan_in = fan_in / qk_gain(m)
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


def mamba_mixer(u, lp, m: Mapping):
    """The Mamba-2 mixer's output [T, E] for normed hidden u [T, E]; `lp`
    this layer's float32 leaves. The recurrence, token by token."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, G, N, d_in, conv = dims(m)
    K = m["mamba_d_conv"]
    zxd = u @ lp["w_in"]
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + conv], zxd[:, d_in + conv:]
    hist = jnp.pad(xbc, ((K - 1, 0), (0, 0)))  # zero history before the first token
    c = lp["conv_b"] + sum(lp["conv_w"][j] * hist[j:j + T] for j in range(K))
    c = jax.nn.silu(c)
    x = c[:, :d_in].reshape(T, H, P)
    B = jnp.repeat(c[:, d_in:d_in + G * N].reshape(T, G, N), H // G, axis=1)  # [T, H, N]
    C = jnp.repeat(c[:, d_in + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # [T, H]
    a = jnp.exp(-jnp.exp(lp["A_log"]) * dt)

    def step(S, t):
        x_t, B_t, C_t, dt_t, a_t = t
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + lp["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, B, C, dt, a))
    g = (y.reshape(T, d_in) * jax.nn.silu(z)).reshape(T, G, d_in // G)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + float(m["rms_norm_eps"]))
    return (g.reshape(T, d_in) * lp["gate_norm"]) @ lp["w_out"]


def attention(u, lp, m: Mapping, scale=None):
    """The GQA mixer's output [T, E]: materialised scores, one KV head's
    group of query heads at a time. `scale` replaces attention_multiplier
    (a control of benchmarks/tests/control_granite.py)."""
    import jax
    import jax.numpy as jnp

    T, E = u.shape
    Hq, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    D, g = E // Hq, Hq // Hkv
    scale = float(m["attention_multiplier"]) if scale is None else scale
    q = (u @ lp["wq"]).reshape(T, Hkv, g, D)
    k = (u @ lp["wk"]).reshape(T, Hkv, D)
    v = (u @ lp["wv"]).reshape(T, Hkv, D)
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    def kv_head(i):
        s = jnp.einsum("qgd,kd->gqk", q[:, i], k[:, i]) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v[:, i])

    o = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, T, g, D]
    return jnp.moveaxis(o, 0, 1).reshape(T, Hq * D) @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(u, router, m: Mapping):
    """Combine weights [T, published]: the softmax over the chosen
    logits on the chosen experts, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    logits = u @ router
    top_v, top_i = jax.lax.top_k(logits, m["num_experts_per_tok"])
    w = jax.nn.softmax(top_v, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(T)[:, None], top_i].set(w)


def expert_layer(u, leaves, layer: int, m: Mapping, shared: bool = True, span=None):
    """The expert block's output [T, E] for normed hidden u, as THIS
    holder computes it: the chosen experts it holds, and the shared MLP.
    `leaves` the stacked `layers` leaves as stored (any dtype; one expert
    is upcast at a time). `span` (first, count), inside the held span, replaces it
    and `shared` False leaves the shared MLP out (the two-holder test)."""
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
    eps, rm = float(m["rms_norm_eps"]), float(m["residual_multiplier"])
    mixers = {"mamba": ("mamba", mamba_mixer), "attention": ("attn", attention)}
    seen = {"mamba": 0, "attention": 0}

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32) * float(m["embedding_multiplier"])
        common = weights["layers"]
        for l, kind in enumerate(layer_types(m)):
            stack, mixer = mixers[kind]
            lp = {k: v[seen[kind]].astype(f32) for k, v in weights[stack].items()}
            seen[kind] += 1
            x = x + rm * mixer(_rms_norm(x, common["attn_norm"][l].astype(f32), eps), lp, m)
            u = _rms_norm(x, common["mlp_norm"][l].astype(f32), eps)
            x = x + rm * expert_layer(u, common, l, m)
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        table = weights["embed"]
        V = table.shape[0]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the tied head in vocabulary blocks
            rows = jax.lax.dynamic_slice_in_dim(table, i * (V // nb), V // nb, axis=0)
            return h @ rows.astype(f32).T

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V) / float(m["logits_scaling"])
