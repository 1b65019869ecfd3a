"""The Llama family: GQA decoders with optional QKV bias and a tied or
untied head (Llama, Qwen2, Mistral), as one family file of the benchmark.

A configuration file names its family (`"family": "llama"`), and the
harness (benchmarks/harness/family.py) loads this file by path and uses
five names of it and nothing else:

  * `model_config(name, m)`: the configuration's HF keys -> the program's
    ModelConfig; the only import from xllm_service_tpu here;
  * `make_weights(m, key, dtype)`: every parameter drawn from the seed,
    in the program's parameter tree; nothing the program made is read;
  * `forward_logits(weights, m, tokens, idx)`: the architecture's forward
    pass in straightforward float32 jax.numpy with matmul precision
    "highest": no cache, no kernels, no batching. Equations follow the HF
    Qwen2/Mistral modeling files (pre-norm residual blocks, RMSNorm,
    rotate-half RoPE on q and k, causal softmax attention with grouped KV
    heads, SwiGLU); the weights are stored as [in, out] matrices stacked
    over layers, the one departure from HF's [out, in] Linear layout;
  * `LIMITS` and `LIMITS_READINGS`: what check.compare holds a served
    sample to, and the chip readings the limits were set from (PERF.md).

`m` is the configuration file as parsed. This was
benchmarks/harness/reference.py plus stack.model_config until PR 30:
moved, not rewritten, so one seed draws the same weights bit for bit."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits (set by steps 4 and 5 of "How correct is decided") -----------
# logprob_mse: mean over all served tokens (512 of them) of the square of
#   (served logprob of the chosen token - reference log-softmax at that
#   token), in nats^2. Independent roundings add in variance, so this is
#   the scale on which a path that adds rounding stands apart: the bf16
#   served path itself carries about 2.1e-4 of it against the float32
#   reference, int8 weights add 16e-4 to 43e-4 on top, and an int8 KV
#   cache 180e-4 and more.
# deficit_max: the largest (reference max logit - reference logit of the
#   served greedy token). bf16 ties give a few hundredths; a token from a
#   wrong path (stale KV, wrong block, wrong mask) sits units below. A
#   gross-error guard: int8 weights stay under it.
LIMITS = {"logprob_mse": 6.4e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "qwen2.5-3b bf16, TPU v5 lite, PR 27 (my chip runs), 512 tokens a run: "
    "59 sound runs on 41 seeds logprob_mse 1.76e-4 to 2.50e-4 (rms 0.0133 "
    "to 0.0158), deficit_max at most 0.0607; the program's int8 weights "
    "(ops/quant.py), 8 runs on 5 seeds, 17.7e-4 to 45.0e-4, deficit_max at "
    "most 0.208; the program's int8 KV cache (kv_cache_dtype) on 5 seeds "
    "181e-4 to 409e-4, deficit_max 0.53 to 0.83. Limit 6.4e-4, near the "
    "geometric mean of the sound largest and the smaller control's "
    "smallest: 2.6x of room below, 2.8x above. Without the key-lane scales "
    "of make_weights the int8 KV cache read 2.40e-4 to 2.79e-4 on 3 seeds, "
    "inside the sound runs' band. PR 30 (my chip runs, the same cells after "
    "the move): 49 sound runs on 25 seeds 1.78e-4 to 2.65e-4 (one seed twice "
    "at 2.65e-4, the rest at most 2.27e-4), deficit_max at most 0.0852; int8 "
    "weights on 4 seeds 21.2e-4 to 29.8e-4, deficit_max at most 0.241; int8 "
    "KV cache on 3 seeds 192e-4 to 590e-4, deficit_max 0.526 to 0.927. The "
    "limits stand: logprob_mse has 2.4x of room below now and 2.8x above; "
    "deficit_max 2.9x below, and stays a gross-error guard (int8 weights "
    "reach 0.241 under it)."
)


def head_dim(m: Mapping) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def model_config(name: str, m: Mapping):
    from xllm_service_tpu.models.configs import ModelConfig

    if m.get("sliding_window") and m.get("use_sliding_window", True):
        raise ValueError("sliding-window configurations are not wired here")
    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=head_dim(m),
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
        attn_bias=bool(m.get("attention_bias", False)),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, F, L, V = (
        m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"],
        m["vocab_size"],
    )
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    layers = {
        "attn_norm": (L, E), "mlp_norm": (L, E),
        "wq": (L, E, Hq * D), "wk": (L, E, Hkv * D), "wv": (L, E, Hkv * D),
        "wo": (L, Hq * D, E),
        "w_gate": (L, E, F), "w_up": (L, E, F), "w_down": (L, F, E),
    }
    if m.get("attention_bias"):
        layers.update(bq=(L, Hq * D), bk=(L, Hkv * D), bv=(L, Hkv * D))
    out = {"embed": (V, E), "final_norm": (E,), "layers": layers}
    if not m.get("tie_word_embeddings"):
        out["lm_head"] = (E, V)
    return out


# Key channels of a trained checkpoint do not share one scale: a few carry
# magnitudes tens to hundreds of times the rest (Qwen2's k bias is the
# known case; KIVI and KVQuant quantize keys per channel for it). Weights
# drawn N(0, 1/fan_in) have none of that, and a KV cache stored in a format
# with a scale shared between channels (the program's int8: one scale per 16
# lanes) then reads as accurate as bf16. So each RoPE pair of key lanes
# (c, c + D/2) of each KV head and layer gets a scale 2**n, n uniform in
# -K_LANE_LOG2..K_LANE_LOG2, on its column of wk and entry of bk, and the
# inverse on the matching lanes of wq and bq of the group's query heads.
# q.k is unchanged in exact arithmetic, and a power of two changes no
# float's mantissa, so float32 and bfloat16 paths compute bit for bit what
# they did without the scales; a shared-scale format loses the small lanes.
K_LANE_LOG2 = 4
K_LANE_KEY = 0x4B


def k_lane_scales(m: Mapping, key):
    """[L, Hkv, D] float32 powers of two, equal on lanes c and c + D/2."""
    import jax
    import jax.numpy as jnp

    L, Hkv, D = m["num_hidden_layers"], m["num_key_value_heads"], head_dim(m)
    n = jax.random.randint(key, (L, Hkv, D // 2), -K_LANE_LOG2, K_LANE_LOG2 + 1)
    return jnp.exp2(jnp.concatenate([n, n], axis=-1).astype(jnp.float32))


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`; traceable, so the caller jits it with the
    shardings it wants and the whole model is made on the device in one
    call, in the dtype it is served in. Matrices ~ N(0, 1/fan_in) (logits
    come out with sigma about 1, as a trained model's), norm gains ~
    N(1, 0.1) in float32, biases ~ N(0, 0.1): nothing is left at a value
    (0 or 1) that would let a path skip it unnoticed. Key lanes carry the
    power-of-two scales of `k_lane_scales`, query lanes their inverse."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    names = sorted(shapes["layers"]) + sorted(k for k in shapes if k != "layers")
    keys = dict(zip(names, jax.random.split(key, len(names))))
    lane = k_lane_scales(m, jax.random.fold_in(key, K_LANE_KEY))  # [L, Hkv, D]
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    lane_of = {
        "wk": lane, "bk": lane,
        "wq": 1.0 / jnp.repeat(lane, group, axis=1),
        "bq": 1.0 / jnp.repeat(lane, group, axis=1),
    }

    def draw(name, shape):
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name.endswith("norm"):
            return 1.0 + 0.1 * z  # float32, as served
        if name in lane_of:  # the same scale down a whole column of wq/wk
            z = z * lane_of[name].reshape((shape[0],) + (1,) * (len(shape) - 2) + (-1,))
        if name in ("bq", "bk", "bv"):
            return (0.1 * z).astype(dtype)
        fan_in = shape[-1] if name == "embed" else shape[-2]
        return (z / np.sqrt(fan_in)).astype(dtype)

    out = {k: draw(k, s) for k, s in shapes.items() if k != "layers"}
    out["layers"] = {k: draw(k, s) for k, s in shapes["layers"].items()}
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _rope(x, positions, theta):
    """HF rotate_half RoPE. x [T, H, D], positions [T]."""
    import jax.numpy as jnp

    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position through the causal mask), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = tokens.shape[0]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    g = Hq // Hkv
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32)  # [T, E]

        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(f32), lp)
            h = _rms_norm(x, lp["attn_norm"], eps)
            q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
            if "bq" in lp:
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            q = _rope(q.reshape(T, Hq, D), pos, theta)
            k = _rope(k.reshape(T, Hkv, D), pos, theta)
            v = v.reshape(T, Hkv, D)
            k = jnp.repeat(k, g, axis=1)  # [T, Hq, D]
            v = jnp.repeat(v, g, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, Hq * D)
            x = x + a @ lp["wo"]
            h = _rms_norm(x, lp["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
            return x, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        if "lm_head" in weights:
            return h @ weights["lm_head"].astype(f32)
        return h @ weights["embed"].astype(f32).T
