"""A throwaway family for test_run.py: Multi-head Latent Attention with a
dense SwiGLU, at the sizes of the program's `deepseek-tiny`. The test
copies this file into a copy of benchmarks/ as families/<name>.py, beside
a configuration that names it: the program's other parameter tree and its
only one-cache family, so the seam is tried on what a later PR brings. It
is no family the benchmark offers: its limits stand on float32 CPU
rehearsals, not on chip readings.

`forward_logits` follows arXiv 2405.04434 section 2.1 in the naive form
(per-head keys and values are materialised from the latent; nothing is
absorbed into the query or the output projection), in float32 jax.numpy at
"highest", and imports nothing from the program. Departures from the
paper's equations, as the released model has them: RMSNorm on the query
latent and on the KV latent; one matrix for W^DKV and W^KR (`w_dkv`) and
one for W^UQ and W^QR (`w_uq`); rotate-half RoPE."""

from __future__ import annotations

from typing import Mapping

import numpy as np

LIMITS = {"logprob_mse": 1e-6, "deficit_max": 0.01}
LIMITS_READINGS = (
    "test fixture, no chip reading: float32 engine on the CPU backend, 512 "
    "tokens a run, 4 seeds (my sandbox runs, PR 30): logprob_mse 1.46e-12 to "
    "1.70e-12, deficit_max at most 7.2e-7; the limits stand far above that "
    "and far under what a wrong projection gives (units)"
)


def model_config(name: str, m: Mapping):
    from xllm_service_tpu.models.configs import ModelConfig

    return ModelConfig(
        name=name,
        vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_attention_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],  # unused by MLA
        rope_theta=float(m["rope_theta"]),
        rms_norm_eps=float(m["rms_norm_eps"]),
        max_position_embeddings=m["max_position_embeddings"],
        tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
        kv_lora_rank=m["kv_lora_rank"],
        q_lora_rank=m["q_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"],
    )


def weight_shapes(m: Mapping) -> dict:
    E, F, L, V = (
        m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"],
        m["vocab_size"],
    )
    H, kvr, qr = m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    layers = {
        "attn_norm": (L, E), "mlp_norm": (L, E), "kv_norm": (L, kvr), "q_norm": (L, qr),
        "w_dq": (L, E, qr), "w_uq": (L, qr, H * (dn + dr)),
        "w_dkv": (L, E, kvr + dr), "w_uk": (L, H, kvr, dn), "w_uv": (L, H, kvr, dv),
        "wo": (L, H * dv, E),
        "w_gate": (L, E, F), "w_up": (L, E, F), "w_down": (L, F, E),
    }
    out = {"embed": (V, E), "final_norm": (E,), "layers": layers}
    if not m.get("tie_word_embeddings"):
        out["lm_head"] = (E, V)
    return out


def make_weights(m: Mapping, key, dtype):
    """Matrices ~ N(0, 1/fan_in) in `dtype`, norm gains ~ N(1, 0.1) in
    float32: nothing left at a value a path could skip unnoticed."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    names = sorted(shapes["layers"]) + sorted(k for k in shapes if k != "layers")
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def draw(name, shape):
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name.endswith("norm"):
            return 1.0 + 0.1 * z
        fan_in = shape[-1] if name == "embed" else shape[-2]
        return (z / np.sqrt(fan_in)).astype(dtype)

    out = {k: draw(k, s) for k, s in shapes.items() if k != "layers"}
    out["layers"] = {k: draw(k, s) for k, s in shapes["layers"].items()}
    return out


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..T-1. x [T, H, D]."""
    import jax.numpy as jnp

    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
    return x * cos + rot * sin


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded), idx [n] positions
    whose next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = tokens.shape[0]
    H, kvr = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32)

        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(f32), lp)
            h = _rms_norm(x, lp["attn_norm"], eps)
            c_q = _rms_norm(h @ lp["w_dq"], lp["q_norm"], eps)  # eq. 6
            q = (c_q @ lp["w_uq"]).reshape(T, H, dn + dr)  # eqs. 7 and 8
            q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)  # eq. 9
            ckv = h @ lp["w_dkv"]  # eqs. 1 and 3, before the rotation
            c_kv = _rms_norm(ckv[:, :kvr], lp["kv_norm"], eps)
            k_r = _rope(ckv[:, None, kvr:], theta)  # [T, 1, dr]: one for all heads
            k_c = jnp.einsum("tc,hcd->thd", c_kv, lp["w_uk"])  # eq. 2
            v = jnp.einsum("tc,hcd->thd", c_kv, lp["w_uv"])  # eq. 5
            k = jnp.concatenate([k_c, jnp.broadcast_to(k_r, (T, H, dr))], -1)  # eq. 4
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dn + dr)  # eq. 10
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, H * dv)
            x = x + o @ lp["wo"]  # eq. 11
            h = _rms_norm(x, lp["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
            return x, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        if "lm_head" in weights:
            return h @ weights["lm_head"].astype(f32)
        return h @ weights["embed"].astype(f32).T
