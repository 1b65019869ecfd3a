"""The Falcon-H1 family (HF `model_type: falcon_h1`): EVERY block runs a
GQA attention mixer and a Mamba-2 mixer IN PARALLEL on one normed input
and adds both to the residual stream at once, then a dense SwiGLU; muP
multipliers inside the projections; as one family file of the benchmark
(the five names of benchmarks/harness/family.py; see families/llama.py).

What is computed, with `h` the residual stream, `rms` RMSNorm with a
learned gain, `eps = rms_norm_eps`, and the twelve multipliers as published:

    h0 = embedding_multiplier * E[token]
    every block:   u = rms_1(h)
      a = attention_out_multiplier * Attn(attention_in_multiplier * u)
            q, k, v = W_q x, key_multiplier * W_k x, W_v x      (no bias)
            rotary on ALL head_dim lanes of q and k (pairs (i, i + D/2),
            frequency rope_theta ** (-2 i / D), theta 1e11)
            causal softmax(q k^T / sqrt(head_dim)) v;  W_o;  Hq query heads
            over Hkv KV heads
      s = ssm_out_multiplier * Mamba(ssm_in_multiplier * u)
            [z | x | B | C | dt] = (W_in x) * mup,  mup = ssm_multipliers[0..4]
            on the z (H P), x (H P), B (G N), C (G N) and dt (H) lanes
            xBC'_t = silu(sum_{j<K} w_j * xBC_{t-K+1+j} + b)    depthwise, causal
            dt_h = softplus(dt_h + dt_bias_h);  a_h = exp(-exp(A_log_h) dt_h)
            S_h <- a_h S_h + dt_h x_h B_g^T;  y_h = S_h C_g + D_h x_h,  g = h // (H / G)
            out = W_out [ gate_norm * rms_group(y * silu(z)) ]   gate THEN norm
            (mamba_norm_before_gate false), rms over each group's H P / G lanes
      h <- h + a + s                                   ONE residual add
      v = rms_2(h)
      h <- h + mlp_multipliers[1] * W_down( silu(mlp_multipliers[0] * W_gate v) * W_up v )
    logits = lm_head_multiplier * (rms_f(h_L) @ W_head)              (untied head)

The configuration is one holder's share of a deployment (its file's
`deployment`): `num_hidden_layers` of the published blocks and rows
[0, vocab_size) of both vocabulary matrices.

This is the RECURRENT definition, token by token, with every multiplier
where the equations put it (UNFOLDED: the program applies some of them
elsewhere, models/granite.py says where): no chunks, no carried pool, no
cache, no kernel. The program serves chunks of 256 tokens through a state
pool and a paged cache; that they agree is what `correct` checks. float32,
matmul precision "highest", plain jax.numpy, attention materialised a
block of QUERY_BLOCK query rows at a time, the head in vocabulary blocks.
Nothing is imported from the program but ModelConfig (in `model_config`)."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell falcon-h1-34b.dialog-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 1.3e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "falcon-h1-34b (9 of 72 blocks, 32,640 of 261,120 vocabulary rows) bf16 weights "
    "and K/V, float32 state, TPU v5 lite, PR 53 (my chip runs, call 99, the cell's own "
    "size, 512 served tokens a run, the draw as committed below). Sound: 9 runs on 9 "
    "seeds 7.10e-5 to 8.96e-5 (3 of control_falcon_h1.py --mode sound 7.10e-5, 7.76e-5, "
    "8.96e-5; 6 untraced runs of the cell at its rate 7.41e-5 to 8.65e-5), every argmax "
    "but 9 to 13 of 512 the reference's, deficit_max at most 0.025. Controls, smallest "
    "first, every mode judged by check.judge on the chip, 16 of 16 control runs not "
    "correct: the state pools held in bfloat16 1.841e-4, 1.860e-4, 2.277e-4 (3 seeds; "
    "2.06x the sound largest at the least, 2.1x to 3.2x their own seeds' sound runs); "
    "int8 weights 6.05e-4, 6.35e-4; group 1's heads reading group 0's B and C 1.02e-3, "
    "4.85e-3; a stale K/V half block 1.11e-2; mlp_multipliers swapped 1.38e-2; the SSM "
    "state dropped at every chunk boundary 5.8e-2; the attention branch left out 0.416; "
    "the state branch left out 15.3; the convolution's carried rows never read 18.1; "
    "ssm_multipliers[2] and [3] swapped 21.7. logprob_mse 1.3e-4 lies 1.45x over the "
    "sound largest and 1.42x under the bfloat16 state's smallest: under the 3x the "
    "contract asks for on both sides, because the two readings are 2.06x apart (the "
    "Granite family's 2.26x, for the same reason: 64 decode steps are few for a state "
    "to stagnate in); the sound band is 1.26x wide. deficit_max: the bfloat16 state "
    "0.041-0.046, int8 weights 0.065-0.082 and one group-swap seed 0.136 pass it; "
    "mlp-swap 0.434, stale-block 0.456 and the gross faults 1.55 to 7.6 do not: 0.25 "
    "is a gross-error guard (the Llama family's), logprob_mse alone refuses every "
    "control. At the timed sizes (--mode long | long-bf16: ONE 3,072-token prompt "
    "through 12 chunks, then 64 greedy tokens, seed 5300000301) a sound run reads "
    "5.34e-5 and a bfloat16 state 1.34e-4, 2.5x. What the draw's gains give "
    "(--mode shares, RMS over one 832-token sequence, blocks 0 to 8): the stream 1.00 "
    "to 6.16; the attention branch 0.30 to 0.89, the state branch 2.01 to 2.03, the "
    "MLP 0.30 a block: no branch vanishes (dropping the smaller one reads 0.416, "
    "3,200x the limit). With the FIRST draw of this PR (gains 2.0 / 0.6 / 1.0, C bias "
    "0.0: shares 0.40-1.49 / 0.60 / 0.60 of a stream of 1.00-3.99; calls 91-95, 20 "
    "sound runs 5.78e-5 to 7.86e-5) a bfloat16 state read 7.93e-5, 9.64e-5, 1.148e-4, "
    "INSIDE and just over the sound band, and the swapped groups 1.76e-4, 2.2x: the "
    "study under 'the draw' below is why both constants moved. PERF.md section 2."
)

# --- the draw ---------------------------------------------------------------
# Random weights make a model whose check reads little of what the family is
# for, unless the draw is made for it (families/granite.py has the long form
# of the argument; the scan's constants below are its, for its reasons).
#
# The multipliers. They are muP's: they expect weights that TRAINING has
# scaled. Under a plain N(0, 1 / fan_in) draw the attention branch would
# enter the stream at 0.0375 of the scale of its input and the state branch
# at 0.088, the scores would have a std of key_multiplier = 0.011 (a flat
# softmax: attention an average of V), the convolution would see its bias
# alone (x, B and C arrive scaled by 0.06, 0.044 and 0.125) and the gate's
# pre-activation 0.18: a program that dropped a branch, swapped two
# multipliers or read a stale K/V block would pass. So every matrix that
# stands behind a multiplier is drawn at the INVERSE of that multiplier
# times the plain draw (the published multipliers stay as published, in the
# reference and in the program): what the model computes is then what a
# plain draw computes with every multiplier 1, times the gains below, and a
# multiplier moved to its neighbour is off by their ratio (1.4x to 16x).
#
#   embed              1 / embedding_multiplier: h0 has unit RMS
#   wq, wk             (SCORE_STD / (key_multiplier * attention_in^2)) ** 0.5 each
#                      (a score's std is then SCORE_STD: a softmax with spread)
#   wv                 1 / attention_in_multiplier
#   wo                 ATTN_OUT_GAIN / attention_out_multiplier
#   w_in's lanes       1 / (ssm_in_multiplier * ssm_multipliers[lane's part])
#   w_out              MAMBA_OUT_GAIN / ssm_out_multiplier
#   w_gate             1 / mlp_multipliers[0]
#   w_down             MLP_OUT_GAIN / mlp_multipliers[1]
#   lm_head            1 / lm_head_multiplier: logits with a std of about 1
#
# The gains decide what a block adds to the stream (the measured shares are
# in LIMITS_READINGS): attention's output is an average of unit values (RMS
# 0.2-0.7 before W_o, growing with depth), the gated norm's is of unit RMS,
# the SwiGLU's about 0.6. The STATE branch is drawn the largest of the three
# (2.0 a block beside attention's 0.3-1.1 and the MLP's 0.3), as Granite's
# MAMBA_OUT_SCALE does: what separates a bfloat16 state from the float32 one
# is the state branch's share of the logits. With the first draw of PR 53
# (gains 2.0 / 0.6 / 1.0: three comparable shares) a bfloat16 state read
# 1.14x, 1.59x and 1.65x its seeds' sound runs on the chip, INSIDE the sound
# band; a study at hidden 1024 and 6 blocks on the CPU (control_falcon_h1.py
# over a scratch configuration, bfloat16, 2-10 seeds a draw; no device
# number) read 1.1-1.3x there and 2.5-6.0x with these gains.
SCORE_STD = 1.5
ATTN_OUT_GAIN = 1.5
MAMBA_OUT_GAIN = 2.0
MLP_OUT_GAIN = 0.5

# The scan (families/granite.py: a float32 state is told from a bfloat16
# one by STAGNATION, so the x and B lanes carry a standing component, the
# steps are Mamba-2's own and A two decades under its init, and nearly
# every head remembers past the check's 832 tokens: a state dropped at a
# chunk boundary is then a gross fault). The C lanes' bias is what this
# family adds to that: with Granite's 0.0 the read-out's inner product
# B_s . C_t over 256 lanes is its STANDING part (256 x 1.8 x 0.21 = 95, the
# same for both groups) give or take a fifth, the gated norm divides the
# standing part out, and group 1's heads reading group 0's B and C read
# 2.5x a sound run on the chip (1.4x in the study). A zero-mean C
# (bias -0.5) makes the product all fluctuation and the check reads the
# groups 240x, but the gated norm then divides by sums near zero: sound
# runs read 7e-3 to 8e-2 with deficits over 0.8 (the study; -0.35, -0.25 and
# -0.2 still threw a sound run in eight to 1.4e-4 and beyond). At -0.15 the
# standing part is 60 give or take 17 (3.5 sigma from zero): ten sound
# seeds of the study read 6.0e-5 to 6.7e-5, as tight as at 0.0, and the
# swapped groups 7x.
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1e-3, 1e-1)
CONV_BIAS_MEANS = (2.0, 2.0, -0.15)

QUERY_BLOCK = 256  # query rows of one attention block


def dims(m: Mapping):
    """(H, P, G, N, d_inner, convolution lanes) of the Mamba-2 mixer."""
    H, P = m["mamba_n_heads"], m["mamba_d_head"]
    G, N = m["mamba_n_groups"], m["mamba_d_state"]
    if H * P != m["mamba_d_ssm"] or H % G:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_d_ssm, or groups do not divide")
    return H, P, G, N, H * P, H * P + 2 * G * N


def lane_multipliers(m: Mapping) -> np.ndarray:
    """`mup`: ssm_multipliers[0..4] along the lanes of `W_in`'s result."""
    H, _, G, N, d_in, _ = dims(m)
    return np.repeat(
        np.asarray(m["ssm_multipliers"], np.float64), (d_in, d_in, G * N, G * N, H)
    ).astype(np.float32)


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "ssm_multipliers" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/falcon_h1.py: this program's ModelConfig has no `ssm_multipliers`: it "
            "cannot run a block with a Mamba-2 mixer and an attention mixer in parallel (the "
            "configuration needs the program of PR 53 or later)"
        )
    if m.get("attention_bias") or m.get("mamba_proj_bias") or m.get("mlp_bias") \
            or m.get("projectors_bias") or not m.get("mamba_conv_bias") \
            or m.get("tie_word_embeddings") or m.get("mamba_norm_before_gate") \
            or not m.get("mamba_rms_norm") or m.get("rope_scaling") \
            or m.get("attn_layer_indices") is not None:
        raise ValueError("this family: no projection bias, a convolution bias, an untied head, "
                         "the gate before the gated norm, plain rotary, attention in every block")
    H, P, G, N, _, _ = dims(m)
    L = m["num_hidden_layers"]
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=L,
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        layer_types=("parallel",) * L,
        rotary_dim=m["head_dim"],
        mamba_d_state=N, mamba_d_conv=m["mamba_d_conv"], mamba_n_heads=H,
        mamba_d_head=P, mamba_n_groups=G,
        embedding_multiplier=float(m["embedding_multiplier"]),
        lm_head_multiplier=float(m["lm_head_multiplier"]),
        attention_in_multiplier=float(m["attention_in_multiplier"]),
        attention_out_multiplier=float(m["attention_out_multiplier"]),
        key_multiplier=float(m["key_multiplier"]),
        ssm_in_multiplier=float(m["ssm_in_multiplier"]),
        ssm_out_multiplier=float(m["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(x) for x in m["ssm_multipliers"]),
        mlp_multipliers=tuple(float(x) for x in m["mlp_multipliers"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    H, _, _, _, d_in, conv = dims(m)
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    F, K = m["intermediate_size"], m["mamba_d_conv"]
    return {
        "embed": (V, E), "final_norm": (E,), "lm_head": (E, V),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E),
            "w_gate": (L, E, F), "w_up": (L, E, F), "w_down": (L, F, E),
        },
        "mamba": {
            "w_in": (L, E, d_in + conv + H), "conv_w": (L, K, conv), "conv_b": (L, conv),
            "dt_bias": (L, H), "A_log": (L, H), "D": (L, H), "gate_norm": (L, d_in),
            "w_out": (L, d_in, E),
        },
        "attn": {
            "wq": (L, E, Hq * D), "wk": (L, E, Hkv * D), "wv": (L, E, Hkv * D),
            "wo": (L, Hq * D, E),
        },
    }


FLOAT32_LEAVES = ("conv_w", "conv_b", "dt_bias", "A_log", "D")  # and every norm gain


def draw_gains(m: Mapping) -> Dict:
    """What each matrix is drawn at, of the plain N(0, 1 / fan_in) draw
    (the table above): a scalar a leaf, a vector along `w_in`'s lanes."""
    a_in, km = float(m["attention_in_multiplier"]), float(m["key_multiplier"])
    qk = (SCORE_STD / (km * a_in * a_in)) ** 0.5
    g0, g1 = (float(x) for x in m["mlp_multipliers"])
    return {
        ("attn", "wq"): qk, ("attn", "wk"): qk, ("attn", "wv"): 1.0 / a_in,
        ("attn", "wo"): ATTN_OUT_GAIN / float(m["attention_out_multiplier"]),
        ("mamba", "w_in"): 1.0 / (float(m["ssm_in_multiplier"]) * lane_multipliers(m)),
        ("mamba", "w_out"): MAMBA_OUT_GAIN / float(m["ssm_out_multiplier"]),
        ("layers", "w_gate"): 1.0 / g0, ("layers", "w_down"): MLP_OUT_GAIN / g1,
        (None, "embed"): 1.0 / float(m["embedding_multiplier"]),
        (None, "lm_head"): 1.0 / float(m["lm_head_multiplier"]),
    }


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: the norms and the dense MLP; `mamba`, `attn`: the two
    mixers of every block); traceable. Matrices ~ N(0, 1 / fan_in) times
    `draw_gains` (the embedding's rows ~ N(0, 1) times its gain); norm
    gains ~ N(1, 0.1) and the convolution's weights ~ N(0, 1/K), its bias
    ~ N(CONV_BIAS_MEANS, 0.1), in float32; dt_bias, A_log and D as DT_RANGE
    and A_RANGE say. Nothing is left at a value (0 or 1) that would let a
    path skip it. A leaf is drawn one layer at a time, so the float32
    normals of the MLP's matrices never stand whole."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    gains = draw_gains(m)
    groups = ("layers", "mamba", "attn")
    names = [(g, k) for g in groups for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in groups]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    f32 = jnp.float32

    def draw(name, shape):
        k, leaf = keys[name], name[1]
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
        fan_in = 1.0 if leaf == "embed" else shape[-2]
        gain = jnp.asarray(gains.get(name, 1.0), f32) / np.sqrt(fan_in)
        lead = shape[:-2] if name[0] else ()

        def one(kk):
            return (jax.random.normal(kk, shape[len(lead):], f32) * gain).astype(dtype)

        if not lead:
            return one(k)
        n = int(np.prod(lead, dtype=np.int64))
        return jax.lax.map(one, jax.random.split(k, n)).reshape(shape)

    out = {k: draw((None, k), s) for k, s in shapes.items() if k not in groups}
    for g in groups:
        out[g] = {k: draw((g, k), s) for k, s in shapes[g].items()}
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba_mixer(x_in, lp, m: Mapping):
    """Mamba(x_in) [T, E] (before ssm_out_multiplier) for the mixer's input
    x_in = ssm_in_multiplier * u; `lp` this block's float32 leaves. The
    recurrence, token by token."""
    import jax
    import jax.numpy as jnp

    T = x_in.shape[0]
    H, P, G, N, d_in, conv = dims(m)
    K = m["mamba_d_conv"]
    zxd = (x_in @ lp["w_in"]) * lane_multipliers(m)
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


def rope(x, theta: float):
    """x [T, H, D] at positions 0..T-1: every lane rotates (pairs
    (i, i + D / 2), frequency theta ** (-2 i / D)); angles in float32."""
    import jax.numpy as jnp

    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, D / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x_in, lp, m: Mapping):
    """Attn(x_in) [T, E] (before attention_out_multiplier) for the mixer's
    input x_in = attention_in_multiplier * u: materialised scores against
    the whole sequence, QUERY_BLOCK query rows of one KV head's group at a
    time."""
    import jax
    import jax.numpy as jnp

    T = x_in.shape[0]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    g, theta = Hq // Hkv, float(m["rope_theta"])
    q = rope((x_in @ lp["wq"]).reshape(T, Hq, D), theta).reshape(T, Hkv, g, D)
    k = rope(float(m["key_multiplier"]) * (x_in @ lp["wk"]).reshape(T, Hkv, D), theta)
    v = (x_in @ lp["wv"]).reshape(T, Hkv, D)
    qb = min(QUERY_BLOCK, T)
    pad = -T % qb
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, qb, Hkv, g, D)
    cols = jnp.arange(T)

    def block(args):  # one block of query rows, every head
        qi, rows = args  # [qb, Hkv, g, D], [qb] positions
        seen = cols[None, :] <= rows[:, None]

        def kv_head(i):
            s = jnp.einsum("qgd,kd->gqk", qi[:, i], k[:, i]) / np.sqrt(D)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, v[:, i])

        o = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, qb, g, D]
        return jnp.moveaxis(o, 0, 1).reshape(qb, Hq * D)

    rows = jnp.arange(T + pad).reshape(-1, qb)
    o = jax.lax.map(block, (q, rows)).reshape(-1, Hq * D)[:T]
    return o @ lp["wo"]


def mlp(v, lp, m: Mapping):
    """The dense SwiGLU's output [T, E] with both of its multipliers."""
    import jax

    g0, g1 = (float(x) for x in m["mlp_multipliers"])
    return g1 * ((jax.nn.silu(g0 * (v @ lp["w_gate"])) * (v @ lp["w_up"])) @ lp["w_down"])


def block_terms(x, weights, layer: int, m: Mapping):
    """(a, s, f, h'): what block `layer` adds to the stream x [T, E]: the
    attention branch, the state branch (both of the same normed input),
    then the MLP of the stream with both added; and the stream after it."""
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(m["rms_norm_eps"])
    at = lambda stack: {k: v[layer].astype(f32) for k, v in weights[stack].items()}
    common = at("layers")
    u = _rms_norm(x, common["attn_norm"], eps)
    a = float(m["attention_out_multiplier"]) * attention(
        float(m["attention_in_multiplier"]) * u, at("attn"), m)
    s = float(m["ssm_out_multiplier"]) * mamba_mixer(
        float(m["ssm_in_multiplier"]) * u, at("mamba"), m)
    x = x + a + s
    f = mlp(_rms_norm(x, common["mlp_norm"], eps), common, m)
    return a, s, f, x + f


def branch_shares(weights, m: Mapping, tokens):
    """RMS of the stream into each block and of the three things the block
    adds (attention branch, state branch, MLP), [L, 4] float32: what the
    draw's gains are read by (LIMITS_READINGS)."""
    import jax
    import jax.numpy as jnp

    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    rows = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32) * float(m["embedding_multiplier"])
        for l in range(m["num_hidden_layers"]):
            h = x
            a, s, f, x = block_terms(x, weights, l, m)
            rows.append(jnp.stack([rms(h), rms(a), rms(s), rms(f)]))
    return jnp.stack(rows)


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position: every mixer is causal), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32) * float(m["embedding_multiplier"])
        for l in range(m["num_hidden_layers"]):
            x = block_terms(x, weights, l, m)[3]
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), float(m["rms_norm_eps"]))
        head = weights["lm_head"]
        V = head.shape[1]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the head in vocabulary blocks
            cols = jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1)
            return h @ cols.astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V) * float(m["lm_head_multiplier"])
