"""The DeepSeek-V2 family (arXiv:2405.04434; HF `model_type: deepseek_v2`):
multi-head latent attention (MLA) and an expert layer with shared experts,
as one family file of the benchmark (the five names of
benchmarks/harness/family.py; see benchmarks/families/llama.py).

What is computed, per layer, for the hidden h after RMSNorm, H heads,
dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim:

    c_q            = RMSNorm(h W_dq);  q = c_q W_uq -> per head [q_nope (dn), q_pe (dr)]
    [c_kv, k_pe]   = h W_dkv;  c_kv <- RMSNorm(c_kv)          (kv_lora_rank, dr)
    k_nope, v      = c_kv W_uk, c_kv W_uv                      per head
    q_pe, k_pe     <- RoPE (YaRN frequencies; cos and sin scaled by
                      mscale / mscale_all_dim's ratio); ONE k_pe for all heads
    scores         = (q_nope . k_nope + q_pe . k_pe) (dn + dr)^-0.5 m^2,
                     m = 0.1 mscale_all_dim ln(factor) + 1
    attention      = causal softmax in float32;  out = concat_h(p v) W_o
    layer < first_k_dense_replace: SwiGLU of intermediate_size
    else:  s = softmax_float32(h W_r) over ALL published experts; the
           n_group groups are scored by their largest s, the best topk_group
           kept, the top num_experts_per_tok experts of those taken; weight
           = s_e x routed_scaling_factor (norm_topk_prob false);
           y = sum over the chosen experts HELD HERE of weight x SwiGLU_e(h)
               + SwiGLU_shared(h)
    final RMSNorm; untied head over the vocabulary rows held.

The configuration is one holder's share of a deployment (its file's
`deployment`): `experts_held` [first, count] of the published experts and
a slice of the vocabulary. What the absent experts would add to a layer is
LEFT OUT, here and in the program alike, and that partial result goes on
to the next layer: the reference is this holder's function, not the whole
model's.

Departures from the released checkpoint, none of which random weights can
see: RoPE is rotate-half (the release interleaves pairs: a column
permutation of W_uq and W_dkv); W_dkv ("kv_a_proj_with_mqa") and W_uq are
one matrix each, W_uk and W_uv ("kv_b_proj") two.

This is the MATERIALISED form: per-head keys and values, no latent cache,
no absorption, no kernel. The program serves the absorbed form through a
paged latent cache; that they agree is what `correct` checks. float32,
matmul precision "highest", plain jax.numpy, computed in blocks (heads and
query rows of the attention, one expert at a time, the head in vocabulary
blocks) so that it fits beside the engine. Nothing is imported from the
program but ModelConfig (in `model_config`)."""

from __future__ import annotations

import math
from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py, from chip readings of
# the cell deepseek-v2.doc-steady (PERF.md section 2).
LIMITS = {"logprob_mse": 0.0025, "deficit_max": 0.3}
LIMITS_READINGS = (
    "deepseek-v2 (5 of 60 layers, experts 0-39 of 160, 25,600 of 102,400 "
    "vocabulary rows) bf16 weights, bf16 latent cache, TPU v5 lite, PR 39 (my "
    "chip runs, the cell's own size): 24 sound runs on 23 seeds 5.69e-4 to "
    "6.94e-4 (deficit_max at most 0.172); controls, smallest first: a chosen "
    "expert's weight not scaled by 16 9.34e-3 (deficit 0.503), int8 latent "
    "cache 1.95, the shared expert left out 4.88, int8 weights 7.24 (deficits "
    "3.86 to 5.87). logprob_mse 2.5e-3 is the geometric mean of the sound and "
    "the control readings taken together (6.86e-4, 9.34e-3): 3.6x over the "
    "sound largest, 3.7x under the smallest control; deficit_max 0.3 is the "
    "geometric mean of 0.172 and 0.503, 1.7x of room on each side: a guard "
    "only, logprob_mse alone refuses every control. PERF.md section 2."
)

# The routed experts' down matrices are drawn ROUTED_OUT_SCALE times smaller
# than every other matrix (N(0, ROUTED_OUT_SCALE^2 / F) beside N(0, 1/fan_in)).
# With 1 a chosen expert carries a weight of 0.2-0.9 (a flat softmax over 160,
# x 16) and an output as large as the residual, so wherever bfloat16 noise in
# the hidden state flips the choice at a boundary (the third against the
# fourth group, the sixth against the seventh expert: about 1 % of tokens a
# layer, whatever the router's scale) that token's hidden state moves by a
# third of its norm: a SOUND bfloat16 program then read logprob_mse
# 0.024-0.077 against the float32 reference, above its int8 controls, and a
# peaked router (std 8: weights up to 16) read 0.14-0.42 (my chip runs, PR
# 39; each kernel swapped for its plain-XLA twin moved the number anywhere in
# its band). A trained model's layer adds a small part of its residual; this
# draw gives the routed part that size, so that the check reads the
# arithmetic and not the coin flips. The shared expert, the dense layer and
# the attention keep the plain draw.
ROUTED_OUT_SCALE = 0.1

# Each lane of the normed latent c_kv carries a power of two, 2**n with n in
# -LANE_LOG2..LANE_LOG2, on its gain of `kv_norm`, and the inverse on its row
# of `w_uk` and `w_uv` (after families/llama.py's `k_lane_scales`): exact in
# float32 and in bfloat16, so the model and a sound program's rounding are
# what they were, and a cache format whose scale is shared between lanes
# loses the small ones, as a trained checkpoint's outlier lanes make it. With
# every lane at one size the int8 latent cache read only 1.6-1.7x the sound
# runs (my chip runs, PR 39).
LANE_LOG2 = 4
LANE_KEY = 0x4C


def latent_lane_scales(key, shape):
    """`shape` float32 powers of two, one a layer and lane of c_kv."""
    import jax
    import jax.numpy as jnp

    return jnp.exp2(jax.random.randint(key, shape, -LANE_LOG2, LANE_LOG2 + 1).astype(jnp.float32))


QUERY_BLOCK = 512  # query rows of one attention block
HEAD_BLOCK = 16  # heads of one attention block


def held_experts(m: Mapping):
    """(first, count) of the routed experts this configuration holds, and
    the published count the router is as wide as."""
    published = int(m.get("n_routed_experts_published", m["n_routed_experts"]))
    first, count = m.get("experts_held", (0, published))
    if int(count) != int(m["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts disagree")
    return int(first), int(count), published


def model_config(name: str, m: Mapping):
    import dataclasses

    from xllm_service_tpu.models.configs import ModelConfig

    if "experts_held" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise SystemExit(
            "families/deepseek.py: this program's ModelConfig has no `experts_held`: it cannot "
            "hold a span of a layer's experts under a router as wide as the published count "
            "(the configuration needs the program of PR 39 or later)"
        )
    rs = m.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise ValueError("this family's rope_scaling is yarn or none")
    if m.get("attention_bias") or m.get("tie_word_embeddings"):
        raise ValueError("this family has no attention bias and an untied head")
    first, count, published = held_experts(m)
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"],
        q_lora_rank=m["q_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"],
        rope_theta=float(m["rope_theta"]),
        rope_scaling_type="yarn" if rs else "",
        rope_scaling_factor=float(rs.get("factor", 1.0)),
        rope_original_max_position=int(rs.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(rs.get("beta_fast", 32.0)),
        rope_beta_slow=float(rs.get("beta_slow", 1.0)),
        rope_mscale=float(rs.get("mscale", 0.0)),
        rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=False,
        num_experts=published,
        experts_held=(first, count),
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_shared_experts=m["n_shared_experts"],
        first_k_dense_replace=m["first_k_dense_replace"],
        scoring_func=m["scoring_func"],
        topk_method=m["topk_method"],
        n_group=m["n_group"],
        topk_group=m["topk_group"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling_factor=float(m["routed_scaling_factor"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    H, kvr, qr = m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    kd = m["first_k_dense_replace"]
    _, held, published = held_experts(m)
    Fm, Fs = m["moe_intermediate_size"], m["n_shared_experts"] * m["moe_intermediate_size"]

    def stack(n, mlp):
        return {
            "attn_norm": (n, E), "mlp_norm": (n, E), "kv_norm": (n, kvr), "q_norm": (n, qr),
            "w_dkv": (n, E, kvr + dr), "w_uk": (n, H, kvr, dn), "w_uv": (n, H, kvr, dv),
            "wo": (n, H * dv, E), "w_dq": (n, E, qr), "w_uq": (n, qr, H * (dn + dr)),
            **mlp,
        }

    F = m["intermediate_size"]
    dense = {"w_gate": (kd, E, F), "w_up": (kd, E, F), "w_down": (kd, F, E)}
    n = L - kd
    experts = {
        "router": (n, E, published),
        "w_gate": (n, held, E, Fm), "w_up": (n, held, E, Fm), "w_down": (n, held, Fm, E),
        "w_sh_gate": (n, E, Fs), "w_sh_up": (n, E, Fs), "w_sh_down": (n, Fs, E),
    }
    return {
        "embed": (V, E), "lm_head": (E, V), "final_norm": (E,),
        "dense_layers": stack(kd, dense), "layers": stack(n, experts),
    }


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family (`dense_layers`, `layers` with the held experts and the
    published router); traceable. Matrices ~ N(0, 1/fan_in) (the routed
    experts' down matrices ROUTED_OUT_SCALE of that); every norm gain ~
    N(1, 0.1) in float32; the latent's lanes carry `latent_lane_scales`.
    Nothing is left at a value (0 or 1) that
    would let a path skip it. A leaf is drawn one leading slice at a time
    (one layer; one expert of a layer), so the float32 normals of the
    4 x 40 expert matrices never stand whole beside the weights."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    groups = ("dense_layers", "layers")
    names = [(g, k) for g in groups for k in sorted(shapes[g])]
    names += [(None, k) for k in sorted(shapes) if k not in groups]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def draw(name, shape):
        k = keys[name]
        if name[1].endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)  # float32, as served
        fan_in = shape[-1] if name[1] == "embed" else shape[-2]
        if name == ("layers", "w_down"):
            fan_in = fan_in / ROUTED_OUT_SCALE ** 2
        lead = shape[:-2] if name[0] else ()
        n = int(np.prod(lead, dtype=np.int64))

        def one(kk):
            z = jax.random.normal(kk, shape[len(lead):], jnp.float32)
            return (z / np.sqrt(fan_in)).astype(dtype)

        if not lead:
            return one(k)
        return jax.lax.map(one, jax.random.split(k, n)).reshape(shape)

    out = {k: draw((None, k), s) for k, s in shapes.items() if k not in groups}
    for i, g in enumerate(groups):
        out[g] = {k: draw((g, k), s) for k, s in shapes[g].items()}
        lanes = latent_lane_scales(jax.random.fold_in(key, LANE_KEY + i), shapes[g]["kv_norm"])
        out[g]["kv_norm"] = out[g]["kv_norm"] * lanes
        for k in ("w_uk", "w_uv"):  # [n, H, kvr, d]: powers of two, exact in any float
            out[g][k] = (out[g][k].astype(jnp.float32) / lanes[:, None, :, None]).astype(dtype)
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(m: Mapping) -> np.ndarray:
    """YaRN's frequencies for the rope dims (HF _compute_yarn_parameters):
    interpolated (1 / factor) below the correction range, extrapolated
    (unchanged) above it, a linear ramp between."""
    dim, base = m["qk_rope_head_dim"], float(m["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = m.get("rope_scaling") or {}
    if not rs:
        return (1.0 / pos_freqs).astype(np.float32)
    factor, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrapolated) + (1.0 / pos_freqs) * extrapolated
    return inv.astype(np.float32)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_scales(m: Mapping):
    """(what cos and sin are multiplied by, what the softmax scale is)."""
    rs = m.get("rope_scaling") or {}
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if not rs:
        return 1.0, scale
    factor = float(rs["factor"])
    all_dim = float(rs.get("mscale_all_dim", 0.0))
    cos_sin = _mscale(factor, float(rs.get("mscale", 1.0))) / _mscale(factor, all_dim) if all_dim \
        else _mscale(factor, 1.0)
    if all_dim:
        scale *= _mscale(factor, all_dim) ** 2
    return cos_sin, scale


def _rope(x, positions, inv_freq, cos_sin_scale):
    """Rotate-half RoPE. x [T, H, D], positions [T]."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * cos_sin_scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * cos_sin_scale
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def attention(h, lp, m: Mapping):
    """The layer's attention output [T, E] for normed hidden h [T, E];
    `lp` this layer's float32 attention leaves. Materialised keys and
    values, in blocks of HEAD_BLOCK heads and QUERY_BLOCK query rows."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    H, kvr = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps = float(m["rms_norm_eps"])
    inv_freq = yarn_inv_freq(m)
    cos_sin, scale = rope_scales(m)
    pos = jnp.arange(T, dtype=jnp.int32)

    c_q = _rms_norm(h @ lp["w_dq"], lp["q_norm"], eps)
    q = (c_q @ lp["w_uq"]).reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv_freq, cos_sin)
    ckv = h @ lp["w_dkv"]
    c_kv = _rms_norm(ckv[:, :kvr], lp["kv_norm"], eps)
    k_pe = _rope(ckv[:, None, kvr:], pos, inv_freq, cos_sin)[:, 0]  # [T, dr], one for all heads

    hb = math.gcd(H, HEAD_BLOCK)
    qb = min(QUERY_BLOCK, T)
    n_q = -(-T // qb)
    pad = n_q * qb - T

    def head_block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * hb, hb, axis=1)
        k_nope = jnp.einsum("tc,hcd->thd", c_kv, jax.lax.dynamic_slice_in_dim(lp["w_uk"], i * hb, hb, 0))
        v = jnp.einsum("tc,hcd->thd", c_kv, jax.lax.dynamic_slice_in_dim(lp["w_uv"], i * hb, hb, 0))
        qn = jnp.pad(sl(q_nope), ((0, pad), (0, 0), (0, 0)))
        qp = jnp.pad(sl(q_pe), ((0, pad), (0, 0), (0, 0)))

        def query_block(j):
            rows = j * qb + jnp.arange(qb, dtype=jnp.int32)
            a = jax.lax.dynamic_slice_in_dim(qn, j * qb, qb, axis=0)
            b = jax.lax.dynamic_slice_in_dim(qp, j * qb, qb, axis=0)
            s = (jnp.einsum("qhd,khd->hqk", a, k_nope) + jnp.einsum("qhd,kd->hqk", b, k_pe)) * scale
            s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        return jax.lax.map(query_block, jnp.arange(n_q)).reshape(n_q * qb, hb, dv)[:T]

    out = jax.lax.map(head_block, jnp.arange(H // hb))  # [H/hb, T, hb, dv]
    return jnp.moveaxis(out, 0, 1).reshape(T, H * dv) @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(h, router, m: Mapping):
    """Combine weights [T, published]: s_e x routed_scaling_factor on the
    chosen experts, 0 elsewhere (group-limited greedy top-k)."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    s = jax.nn.softmax(h @ router, axis=-1)  # float32, over ALL published experts
    X, G = s.shape[1], m["n_group"]
    sel = s
    if G > 1:
        best = jax.lax.top_k(s.reshape(T, G, X // G).max(-1), m["topk_group"])[1]
        keep = jnp.zeros((T, G), s.dtype).at[jnp.arange(T)[:, None], best].set(1.0)
        sel = (s.reshape(T, G, X // G) * keep[..., None]).reshape(T, X)
    top = jax.lax.top_k(sel, m["num_experts_per_tok"])[1]
    chosen = jnp.zeros((T, X), s.dtype).at[jnp.arange(T)[:, None], top].set(1.0)
    w = s * chosen
    if m["norm_topk_prob"]:
        return w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * float(m["routed_scaling_factor"])


def expert_layer(h, leaves, layer: int, m: Mapping, shared: bool = True, weight_scale=None):
    """The expert layer's output [T, E] for normed hidden h, as THIS holder
    computes it: the chosen experts it holds, and the shared expert.
    `leaves` the stacked `layers` leaves as stored (any dtype; one expert is
    upcast at a time), `layer` the index into them. `shared` False leaves
    the shared expert out and `weight_scale` replaces routed_scaling_factor
    (the mechanism controls of benchmarks/tests/control_deepseek.py)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    first, held, _ = held_experts(m)
    w = route(h, leaves["router"][layer].astype(f32), m)
    if weight_scale is not None:
        w = w * (weight_scale / float(m["routed_scaling_factor"]))
    w = w[:, first:first + held]  # the experts absent here add nothing

    def one(e, acc):
        wg, wu, wd = (leaves[k][layer, e].astype(f32) for k in ("w_gate", "w_up", "w_down"))
        return acc + w[:, e, None] * _swiglu(h, wg, wu, wd)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(h))
    if shared and m["n_shared_experts"]:
        y = y + _swiglu(h, *(leaves[k][layer].astype(f32) for k in ("w_sh_gate", "w_sh_up", "w_sh_down")))
    return y


def _dense_mlp(h, leaves, layer: int, blocks: int = 4):
    """SwiGLU of intermediate_size, in column blocks of the hidden width."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    F = leaves["w_gate"].shape[-1]
    nb = next(n for n in (blocks, 2, 1) if F % n == 0)

    def block(i, acc):
        cols = lambda a, ax: jax.lax.dynamic_slice_in_dim(a[layer], i * (F // nb), F // nb, ax).astype(f32)
        return acc + _swiglu(h, cols(leaves["w_gate"], 1), cols(leaves["w_up"], 1), cols(leaves["w_down"], 0))

    return jax.lax.fori_loop(0, nb, block, jnp.zeros_like(h))


ATTENTION_LEAVES = ("w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position through the causal mask), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(m["rms_norm_eps"])
    kd = m["first_k_dense_replace"]

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32)  # [T, E]
        for layer in range(m["num_hidden_layers"]):
            leaves, l = (weights["dense_layers"], layer) if layer < kd else (weights["layers"], layer - kd)
            lp = {k: leaves[k][l].astype(f32) for k in ATTENTION_LEAVES + ("attn_norm", "mlp_norm")}
            x = x + attention(_rms_norm(x, lp["attn_norm"], eps), lp, m)
            h = _rms_norm(x, lp["mlp_norm"], eps)
            x = x + (_dense_mlp(h, leaves, l) if layer < kd else expert_layer(h, leaves, l, m))
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        head = weights["lm_head"]
        V = head.shape[1]
        nb = next(n for n in (8, 4, 2, 1) if V % n == 0)

        def block(i):  # the head in vocabulary blocks
            return h @ jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1).astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)
