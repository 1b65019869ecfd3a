"""The MiniCPM-SALA family (HF `model_type: minicpm_sala`): `minicpm4` layers
(InfLLM-V2 trainable block-sparse attention, arXiv:2509.24663, MiniCPM4
arXiv:2506.07900) among `lightning-attn` layers (Lightning linear attention
with rotary, arXiv:2401.04658, arXiv:2501.08313), a dense SwiGLU in every
layer, MiniCPM's three muP scalars; as one family file of the benchmark (the
five names of benchmarks/harness/family.py; see families/llama.py).

What is computed, with `h` the residual stream, `rms` RMSNorm with a learned
gain, `eps = rms_norm_eps`, `d = 128` a head's lanes:

    h0 = scale_emb * E[token]
    every layer:  h <- h + r * mixer(rms_1(h)),   r = scale_depth / sqrt(PUBLISHED depth)
                  h <- h + r * W_down[silu(W_gate v) * W_up v],  v = rms_2(h)
    logits = (rms_f(h) / (hidden_size / dim_model_base)) @ W_head       (untied)

  lightning-attn (published layer l of L, 32 heads):
    q = rope(rms_q(W_q u)),  k = rope(rms_k(W_k u)),  v = W_v u   per head; rotary
        on ALL d lanes (pairs (i, i + d/2), theta 10,000, float32 angles)
    S_t = lambda S_{t-1} + k_t^T v_t,   o_t = (q_t S_t) / sqrt(d)     S [d, d] float32
    lambda = exp(-s_h (1 - l / (L - 1) + 1e-5)),   s_h = 2^(-8 (h + 1) / 32)
    out = W_o [ sigmoid(W_g u) * rms_o(o) ]     rms_o over a head's d lanes, a gain
                                                over all 32 d lanes
  minicpm4 (32 query heads over 2 KV heads, no rotary):
    q = rms_q(W_q u),  k = rms_k(W_k u),  v = W_v u;   scores * d**-0.5
    query at position p (context p + 1), KV head g and its 16 query heads:
      p + 1 <= dense_len: causal softmax attention over the whole context
      else: c_j = mean(k[stride j : stride j + kernel]), visible when its last
            token is <= p;  a = softmax_j(q . c_j / sqrt(d)) a head, exactly;
            A_j = sum of a over the 16 heads;  block b (block_size tokens) scores
            max(A_j : c_j overlaps b);  the first init_blocks blocks and the
            window_size / block_size blocks that end at p's own are read always;
            the best others fill the selection to topk blocks;  causal softmax
            attention over the tokens of the selected blocks
    out = W_o [ sigmoid(W_g u) * attn ]

The configuration is one holder's share of a deployment (its file's
`deployment`): the published layers `layer_ids` of `mixer_types`, with the
residual scale and the decays of the PUBLISHED depth and indices.

This is the plain definition: the recurrence token by token, materialised
scores a block of QUERY_BLOCK query rows at a time, the selection of every
query position by a plain `top_k` over masked block scores and a mask over
the keys; no chunks, no pools, no gather of pages, no kernel. float32,
matmul precision "highest", plain jax.numpy. Nothing is imported from the
program but ModelConfig (in `model_config`). The departures from the
released code (the exact softmax of stage 1, the switch per query
position) are in the configuration's `assumed`."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell minicpm-sala.longdoc-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 4.0e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "minicpm-sala (published layers 9-16 of 32, the whole vocabulary) bf16 weights and K/V, "
    "float32 state and compressed keys, TPU v5 lite, PR 56 (my chip runs, calls 126-128, the "
    "cell's own size, 512 served tokens a run: prompts of 4,096, 8,192 and 12,288 tokens, the "
    "draw as committed below). Sound: 19 runs on 19 seeds 1.25e-4 to 1.97e-4 (2 of "
    "control_minicpm_sala.py --mode sound 1.39e-4, 1.55e-4; the sweep's five 1.25e-4 to "
    "1.76e-4; 12 untraced runs of the cell at its rate 1.28e-4 to 1.97e-4), deficit_max at "
    "most 0.045. Controls, every mode judged by check.judge on the chip, 10 of 10 runs of six "
    "modes not correct: the lightning state rounded to bfloat16 wherever it is stored "
    "8.28e-4, 9.19e-4, 9.28e-4 (3 seeds; 4.2x the sound largest at the least, 5.3-6.6x their "
    "own seeds' sound runs); the dense launch in the selected rows' place 1.04e-3, 1.19e-3, "
    "1.25e-3 (3 seeds); a selection without the 31 other local blocks 1.04e-3; int8 weights "
    "2.08e-3; the compressed keys never written 2.11e-3; no rotary in the lightning layers "
    "2.29. logprob_mse 4.0e-4 lies 2.0x over the sound largest and 2.1x under the bfloat16 "
    "state's smallest: under the 3x the contract asks for on both sides, because the two "
    "readings are 4.2x apart. deficit_max: the controls read 0.11 to 0.19 (no-rope 3.5): 0.25 "
    "is a gross-error guard (the Llama family's), logprob_mse alone refuses every control. NOT "
    "separated by this check: the lightning state dropped at every chunk boundary "
    "(zero-carry) 2.01e-4, correct: the memories at layers 10-15 are 2 to 430 tokens and a "
    "chunk is 4,096, so the state has been rebuilt 3,600 tokens before the compared "
    "positions; tests/test_minicpm_sala.py holds it on the CPU at chunks of 32. At the timed "
    "sizes (--mode long: ONE 24,576-token prompt, then 64 greedy tokens, seed 5600000103) a "
    "sound run reads 1.56e-4. Earlier draws of this PR: every head at one gain, ATTN_OUT_GAIN "
    "3, LIGHTNING_OUT_GAIN 2: sound 8.3e-5 to 8.9e-5 (3 runs); ATTN_OUT_GAIN 8: sound "
    "2.06e-4, dense 3.52e-3. The first state-bf16 control rounded through a convert pair "
    "that XLA elides and read 1.04x on both: void. PERF.md section 2."
)

# --- the draw ---------------------------------------------------------------
# A top-k choice among near-equal scores flips on bfloat16 noise, and a
# flipped block changes the logits: the check would read the draw's ties and
# not the arithmetic (ROADMAP Queue B). What the draw can and cannot do about
# it was read with benchmarks/tests/study_minicpm_sala.py on the chip (PR 56;
# LIMITS_READINGS has the numbers):
#
# * Under random token ids the keys of a block do NOT resemble each other,
#   at either sparse layer (the first stands on the embeddings alone; at the
#   second the lightning layers' read-outs follow the token's own query, not
#   a slow topic: a COMMON embedding row, EMBED_COMMON, was drawn to make
#   one and does not; it stays because the lightning states then integrate
#   a standing part, which is what a bfloat16 store loses first). Attention
#   is near-flat (a third of the dense mass lies in the 64 of ~190 blocks
#   selected, the uniform share), the selection of a fifth of the rows
#   changes under 0.5 % of noise in the layer's input, a changed selection
#   moves that row's attention output by 0.9 % and the dense launch in its
#   place by 4 %. Both are averages over blocks, so their ratio is
#   combinatorics (one block of 64 against two thirds of them): the draw
#   cannot separate them further, it can only SIZE the branch. ATTN_OUT_GAIN
#   is chosen so that the flips stay beside the bfloat16 floor (sound runs
#   1.4e-4 to 1.6e-4 where a draw with a small attention branch reads
#   8.6e-5) and the `dense` control reads 8x a sound run. QK_GAIN, the
#   product of the sparse layers' q and k gains' means, is a score's std.
# * The lightning decay is a constant of the architecture: the draw cannot
#   lengthen a memory, which is how the other state families tell a float32
#   state from a bfloat16 one. So the heads whose state integrates the
#   longest, and loses the most to a bfloat16 store (the last SLOW_HEADS of
#   a layer: memories of 100 to 430 tokens at layers 10-15), speak at
#   SLOW_HEAD_GAIN through the output norm's gain and the others at
#   FAST_HEAD_GAIN, and the lightning branch is the largest of the three
#   (LIGHTNING_OUT_GAIN): with these a bfloat16 state reads 5.3x to 6.6x a
#   sound run. (What it reads with every head at one gain was NOT read: the
#   control first rounded through a float32 -> bfloat16 -> float32 convert
#   pair, which XLA elides, and read 1.04x of nothing; it rounds through
#   `lax.reduce_precision` now.)
EMBED_COMMON = 0.6
QK_GAIN = 1.5
ATTN_OUT_GAIN = 5.0
LIGHTNING_OUT_GAIN = 3.0
MLP_OUT_GAIN = 0.5
SLOW_HEADS = 8
SLOW_HEAD_GAIN = 2.0
FAST_HEAD_GAIN = 0.25

QUERY_BLOCK = 128  # query rows of one attention block


def held_layers(m: Mapping):
    """(published index, mixer type) of every layer held."""
    ids = m.get("layer_ids") or list(range(m["num_hidden_layers"]))
    if len(ids) != m["num_hidden_layers"]:
        raise ValueError("layer_ids: one published index a layer held")
    return [(int(i), m["mixer_types"][int(i)]) for i in ids]


def published_depth(m: Mapping) -> int:
    return int(m.get("num_hidden_layers_published") or len(m["mixer_types"]))


def residual_scale(m: Mapping) -> float:
    return float(m["scale_depth"]) / published_depth(m) ** 0.5


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "sparse_topk" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/minicpm_sala.py: this program's ModelConfig has no `sparse_topk`: it "
            "cannot run a layer whose queries select their pages, nor a lightning layer (the "
            "configuration needs the program of PR 56 or later)"
        )
    if m.get("attention_bias") or m.get("attn_use_rope") or not m.get("lightning_use_rope") \
            or not m.get("qk_norm") or m.get("tie_word_embeddings") \
            or not m.get("use_output_gate") or not m.get("use_output_norm") \
            or not m.get("attn_use_output_gate") or m.get("lightning_scale") != "1/sqrt(d)" \
            or m["lightning_nh"] != m["lightning_nkv"]:
        raise ValueError("this family: no bias, NoPE sparse layers, rotary lightning layers with "
                         "as many key heads as heads, QK-norm, both gates, the output norm, an "
                         "untied head")
    kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    held = held_layers(m)
    sp = m["sparse_config"]
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
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        qk_norm=True,
        attn_gate=True,
        layer_types=tuple(kinds[k] for _, k in held),
        lightning_n_heads=m["lightning_nh"],
        lightning_d_head=m["lightning_head_dim"],
        layer_ids=tuple(i for i, _ in held),
        published_layers=published_depth(m),
        sparse_block_size=sp["block_size"], sparse_topk=sp["topk"],
        sparse_kernel_size=sp["kernel_size"], sparse_kernel_stride=sp["kernel_stride"],
        sparse_init_blocks=sp["init_blocks"], sparse_window=sp["window_size"],
        sparse_dense_len=sp["dense_len"],
        embedding_multiplier=float(m["scale_emb"]),
        residual_multiplier=residual_scale(m),
        logits_scaling=m["hidden_size"] / m["dim_model_base"],
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    kinds = [k for _, k in held_layers(m)]
    La, Ls = kinds.count("minicpm4"), kinds.count("lightning-attn")
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    H, d, F = m["lightning_nh"], m["lightning_head_dim"], m["intermediate_size"]
    return {
        "embed": (V, E), "final_norm": (E,), "lm_head": (E, V),
        "layers": {
            "attn_norm": (L, E), "mlp_norm": (L, E),
            "w_gate": (L, E, F), "w_up": (L, E, F), "w_down": (L, F, E),
        },
        "lightning": {
            "wq": (Ls, E, H * d), "wk": (Ls, E, H * d), "wv": (Ls, E, H * d),
            "w_ogate": (Ls, E, H * d), "q_norm": (Ls, d), "k_norm": (Ls, d),
            "o_norm": (Ls, H * d), "wo": (Ls, H * d, E),
        },
        "attn": {
            "wq": (La, E, Hq * D), "wk": (La, E, Hkv * D), "wv": (La, E, Hkv * D),
            "wo": (La, Hq * D, E), "w_ogate": (La, E, Hq * D),
            "q_norm": (La, D), "k_norm": (La, D),
        },
    }


def draw_gains(m: Mapping) -> Dict:
    """What each matrix is drawn at, of the plain N(0, 1 / fan_in) draw."""
    return {
        ("attn", "wo"): ATTN_OUT_GAIN, ("lightning", "wo"): LIGHTNING_OUT_GAIN,
        ("layers", "w_down"): MLP_OUT_GAIN,
        (None, "lm_head"): m["hidden_size"] / m["dim_model_base"],
    }


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`layers`: the norms and the dense MLP; `lightning`, `attn`:
    the two mixers' stacks); traceable. Matrices ~ N(0, 1 / fan_in) times
    `draw_gains`; the embedding's rows a common row (EMBED_COMMON of the
    RMS) plus the token's own, at 1 / scale_emb (h0 has unit RMS); norm
    gains ~ N(1, 0.1) in float32, the sparse layers' q and k gains around
    sqrt(QK_GAIN). Nothing is left at a value (0 or 1) that would let a
    path skip it. A leaf is drawn one layer at a time, so the float32
    normals of the MLP's matrices never stand whole."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    gains = draw_gains(m)
    groups = ("layers", "lightning", "attn")
    names = [(g, k) for g in groups for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in groups]
    keys = dict(zip(names, jax.random.split(key, len(names))))
    f32 = jnp.float32

    def draw(name, shape):
        k, leaf = keys[name], name[1]
        if leaf.endswith("norm"):
            mean = QK_GAIN ** 0.5 if name[0] == "attn" else 1.0
            if leaf == "o_norm":  # [Ls, H d]: a head's lanes at its head's gain
                H, d = m["lightning_nh"], m["lightning_head_dim"]
                mean = np.repeat(np.where(np.arange(H) >= H - SLOW_HEADS, SLOW_HEAD_GAIN,
                                          FAST_HEAD_GAIN), d).astype(np.float32)
            return mean * (1.0 + 0.1 * jax.random.normal(k, shape, f32))
        if leaf == "embed":  # in row blocks: the float32 normals never stand whole
            k0, k1 = jax.random.split(k)
            common = EMBED_COMMON * jax.random.normal(k0, (1, shape[1]), f32)
            own = (1.0 - EMBED_COMMON ** 2) ** 0.5
            nb = next(n for n in (8, 4, 2, 1) if shape[0] % n == 0)

            def rows(kk):
                z = jax.random.normal(kk, (shape[0] // nb, shape[1]), f32)
                return ((common + own * z) / float(m["scale_emb"])).astype(dtype)

            return jax.lax.map(rows, jax.random.split(k1, nb)).reshape(shape)
        gain = jnp.asarray(gains.get(name, 1.0), f32) / np.sqrt(shape[-2])
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


def decay(m: Mapping, published_layer: int) -> np.ndarray:
    """lambda [H] of a lightning layer at its PUBLISHED index."""
    H = m["lightning_nh"]
    slope = 2.0 ** (-8.0 * np.arange(1, H + 1, dtype=np.float64) / H)
    return np.exp(-slope * (1.0 - published_layer / (published_depth(m) - 1) + 1e-5)).astype(
        np.float32)


def lightning_mixer(u, lp, m: Mapping, published_layer: int, state_dtype=None):
    """The lightning layer's output [T, E] for normed rows u: the
    recurrence, token by token, from an empty state. `state_dtype` (the
    controls): the carried state rounded to it after every token."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, d, eps = m["lightning_nh"], m["lightning_head_dim"], float(m["rms_norm_eps"])
    theta = float(m["rope_theta"])
    q = rope(_rms_norm((u @ lp["wq"]).reshape(T, H, d), lp["q_norm"], eps), theta)
    k = rope(_rms_norm((u @ lp["wk"]).reshape(T, H, d), lp["k_norm"], eps), theta)
    v = (u @ lp["wv"]).reshape(T, H, d)
    lam = jnp.asarray(decay(m, published_layer))[:, None, None]

    def step(S, t):
        q_t, k_t, v_t = t
        S = lam * S + k_t[:, :, None] * v_t[:, None, :]
        if state_dtype is not None:  # (a convert pair would be elided: excess precision is allowed)
            info = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, exponent_bits=info.nexp, mantissa_bits=info.nmant)
        return S, jnp.einsum("hk,hkv->hv", q_t, S) / np.sqrt(d)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    y = jax.nn.sigmoid(u @ lp["w_ogate"]) * (o.reshape(T, H * d) * lp["o_norm"])
    return y @ lp["wo"]


def compressed_keys(k, sp: Mapping):
    """c_j = mean(k[stride j : stride j + kernel]) for every j whose tokens
    lie inside k [T, Hkv, D] -> [J, Hkv, D]."""
    T = k.shape[0]
    ks, st = sp["kernel_size"], sp["kernel_stride"]
    J = (T - ks) // st + 1
    rows = np.arange(J)[:, None] * st + np.arange(ks)[None, :]
    return k[rows].mean(axis=1)


def selected_blocks(qi, c, rows, sp: Mapping, n_blocks: int, scale: float):
    """Which blocks each query row of one KV head's group reads past
    dense_len: qi [qb, g, D], c [J, D] the head's compressed keys, rows
    [qb] positions -> [qb, n_blocks] bool (meaningless on rows at or
    under dense_len)."""
    import jax
    import jax.numpy as jnp

    ks, st, B = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    J = c.shape[0]
    j = np.arange(J)
    s1 = jnp.einsum("qgd,jd->gqj", qi, c) * scale
    visible = (j[None, :] * st + ks - 1 <= rows[:, None])[None]
    a = jax.nn.softmax(jnp.where(visible, s1, -jnp.inf), axis=-1)
    A = jnp.where(visible[0], a.sum(axis=0), 0.0)  # [qb, J]; a row that sees none: zeros
    b = np.arange(n_blocks)
    overlap = (j[None, :] * st < (b[:, None] + 1) * B) & (j[None, :] * st + ks > b[:, None] * B)
    score = jnp.max(jnp.where(overlap[None], A[:, None, :], 0.0), axis=-1)  # [qb, NB]
    own = (rows // B)[:, None]
    forced = (b[None, :] < sp["init_blocks"]) | (
        (b[None, :] > own - sp["window_size"] // B) & (b[None, :] <= own))
    ranked = jnp.where(forced, jnp.inf, jnp.where(b[None, :] > own, -jnp.inf, score))
    _, idx = jax.lax.top_k(ranked, sp["topk"])
    return jnp.zeros(ranked.shape, bool).at[jnp.arange(ranked.shape[0])[:, None], idx].set(True)


def sparse_mixer(u, lp, m: Mapping, always_dense: bool = False, return_blocks: bool = False):
    """The minicpm4 layer's output [T, E] for normed rows u (with
    `return_blocks`: the selected-block masks [T, Hkv, NB] instead);
    `always_dense` (the controls): no row selects."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    g, eps, sp = Hq // Hkv, float(m["rms_norm_eps"]), m["sparse_config"]
    B, scale = sp["block_size"], D ** -0.5
    q = _rms_norm((u @ lp["wq"]).reshape(T, Hq, D), lp["q_norm"], eps).reshape(T, Hkv, g, D)
    k = _rms_norm((u @ lp["wk"]).reshape(T, Hkv, D), lp["k_norm"], eps)
    v = (u @ lp["wv"]).reshape(T, Hkv, D)
    NB = -(-T // B)
    selects = not always_dense and T > sp["dense_len"] and NB >= sp["topk"]
    c = compressed_keys(k, sp) if selects else None
    qb = min(QUERY_BLOCK, T)
    pad = -T % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, qb, Hkv, g, D)
    cols = jnp.arange(T)

    def block(args):  # one block of query rows, every head
        qi, rows = args  # [qb, Hkv, g, D], [qb] positions
        causal = cols[None, :] <= rows[:, None]

        def kv_head(i):
            qh, kh, vh = qi[:, i], jnp.take(k, i, axis=1), jnp.take(v, i, axis=1)
            seen, chosen = causal, jnp.zeros((qb, NB), bool)
            if selects:
                chosen = selected_blocks(qh, jnp.take(c, i, axis=1), rows, sp, NB, scale)
                picked = jnp.take(chosen, cols // B, axis=1) & causal
                seen = jnp.where((rows + 1 > sp["dense_len"])[:, None], picked, causal)
            s = jnp.einsum("qgd,kd->gqk", qh, kh) * scale
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vh), chosen

        o, chosen = jax.lax.map(kv_head, jnp.arange(Hkv))  # [Hkv, qb, g, D], [Hkv, qb, NB]
        return jnp.moveaxis(o, 0, 1).reshape(qb, Hq * D), jnp.moveaxis(chosen, 0, 1)

    rows = jnp.arange(T + pad).reshape(-1, qb)
    o, chosen = jax.lax.map(block, (qp, rows))
    if return_blocks:
        return chosen.reshape(-1, Hkv, NB)[:T]
    o = o.reshape(-1, Hq * D)[:T]
    return (jax.nn.sigmoid(u @ lp["w_ogate"]) * o) @ lp["wo"]


def mlp(v, lp):
    import jax

    return (jax.nn.silu(v @ lp["w_gate"]) * (v @ lp["w_up"])) @ lp["w_down"]


def layer_terms(x, weights, layer: int, m: Mapping, **how):
    """(a, f, h'): what held layer `layer` adds to the stream x [T, E] (its
    mixer, then its MLP, each times the residual scale) and the stream
    after it. `how`: the controls' switches (`always_dense`, `state_dtype`)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    eps, r = float(m["rms_norm_eps"]), residual_scale(m)
    held = held_layers(m)
    published, kind = held[layer]
    stack = "attn" if kind == "minicpm4" else "lightning"
    of_kind = sum(1 for _, k in held[:layer] if k == kind)
    common = {k: w[layer].astype(f32) for k, w in weights["layers"].items()}
    lp = {k: w[of_kind].astype(f32) for k, w in weights[stack].items()}
    u = _rms_norm(x, common["attn_norm"], eps)
    if kind == "minicpm4":
        a = r * sparse_mixer(u, lp, m, always_dense=how.get("always_dense", False))
    else:
        a = r * lightning_mixer(u, lp, m, published, state_dtype=how.get("state_dtype"))
    x = x + a
    f = r * mlp(_rms_norm(x, common["mlp_norm"], eps), common)
    return a, f, x + f


def branch_shares(weights, m: Mapping, tokens):
    """RMS of the stream into each held layer and of the two things the
    layer adds (its mixer, its MLP), [L, 3] float32: what the draw's gains
    are read by."""
    import jax
    import jax.numpy as jnp

    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    rows = []
    with jax.default_matmul_precision("highest"):
        x = embed(weights, m, tokens)
        for l in range(m["num_hidden_layers"]):
            h = x
            a, f, x = layer_terms(x, weights, l, m)
            rows.append(jnp.stack([rms(h), rms(a), rms(f)]))
    return jnp.stack(rows)


def embed(weights, m: Mapping, tokens):
    import jax.numpy as jnp

    return weights["embed"][tokens].astype(jnp.float32) * float(m["scale_emb"])


def forward_logits(weights, m: Mapping, tokens, idx, **how):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position: every mixer is causal), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        x = embed(weights, m, tokens)
        for l in range(m["num_hidden_layers"]):
            x = layer_terms(x, weights, l, m, **how)[2]
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), float(m["rms_norm_eps"]))
        h = h / (m["hidden_size"] / m["dim_model_base"])
        head = weights["lm_head"]
        V = head.shape[1]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the head in vocabulary blocks
            cols = jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1)
            return h @ cols.astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)
