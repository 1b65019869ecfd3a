"""The Brumby family: a Qwen3 decoder block whose attention layer is power
retention (arXiv:2507.04239, "Scaling Context Requires Rethinking
Attention"), as one family file of the benchmark (the five names of
benchmarks/harness/family.py; see benchmarks/families/llama.py).

What is computed, per layer, token t, KV head c (query heads in groups),
d = head_dim, degree p = 2. Lines marked `paper` follow the paper; lines
marked `assumed` are NOT in the published config.json (which carries
Qwen3's keys and none for the retention) and stand in the configuration
file's `assumed` list; lines marked `qwen3` follow HF's Qwen3 block.

    hn      = RMSNorm(x)                                          qwen3
    q, k    = RoPE(RMSNorm_head(W_q hn)), RoPE(RMSNorm_head(W_k hn))   qwen3
    v       = W_v hn                                              qwen3
    gamma_t = logsigmoid(w_g . hn_t + b_g)   one scalar a token and KV
              head: a linear gate with a bias                     assumed
    G_t     = sum_{m<=t} gamma_m                                  paper
    a_tj    = exp(G_t - G_j) (q_t . k_j / sqrt d)^p,  j <= t      paper, p assumed 2
    y_t     = sum_j a_tj v_j / (sum_j a_tj + eps)                 assumed (normaliser, eps)
    x'      = x + W_o concat_h(y);  x'' = x' + SwiGLU(RMSNorm(x'))     qwen3

This is the ATTENTION form: no state, no chunks. The program serves the
same function in recurrent form through a state pool
(xllm_service_tpu/ops/retention.py); that they agree is what `correct`
checks. float32, matmul precision "highest", plain jax.numpy; layers are
upcast one at a time inside the scan and the head is computed in
vocabulary blocks (a whole float32 head of this family's published size
is 3.1 GB and does not fit beside the engine). Nothing is imported from
the program but ModelConfig (in `model_config`)."""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

# --- limits ---------------------------------------------------------------
# logprob_mse and deficit_max as in families/llama.py. Set from chip
# readings of the cell brumby-14b.reason-batch (PERF.md section 2):
# logprob_mse between the sound largest and the bfloat16 state's smallest,
# deficit_max between the sound largest and the zeroed carry's smallest.
LIMITS = {"logprob_mse": 3.0e-4, "deficit_max": 0.25}
LIMITS_READINGS = (
    "brumby-14b (8 of 40 layers) bf16 weights, float32 state, TPU v5 lite, "
    "PR 31 (my chip runs), 512 tokens a run, the program as committed (q, k, v "
    "kept in float32): 34 sound runs on 21 seeds (28 of the cell at 16 and at 24 "
    "slots, three of them traced, and 6 of benchmarks/tests/control_brumby.py "
    "--mode sound) logprob_mse 1.46e-4 to 2.19e-4, deficit_max at most 0.0912. "
    "Controls, each through the same check: the program's int8 weights on 5 "
    "seeds 32.5e-4 to 36.8e-4, deficit_max at most 0.224; the state pool held "
    "in bfloat16 (control_brumby.py --mode state-bf16) on 11 seeds 4.09e-4 to 5.38e-4, deficit_max "
    "at most 0.087; the carried state dropped at every chunk boundary on 5 "
    "seeds 9.5 to 15.3, deficit_max 6.4 to 7.4: 21 of 21 not correct at this "
    "limit. Limit 3.0e-4, between the sound largest and the nearest control's "
    "smallest: 1.37x of room below, 1.36x above (the Llama family has 2.4x and "
    "2.8x: a bfloat16 state is a mild fault in a check of 64 decode steps). "
    "With q and k rounded to bfloat16 before the feature map (llama.py's _qkv) "
    "10 sound runs read 2.19e-4 to 3.12e-4 and the bfloat16 state 4.43e-4 to "
    "6.53e-4 on 2 seeds: the squares of q.k double that rounding, so the "
    "program keeps them float32. deficit_max 0.25 is UNHELD against precision: "
    "int8 weights (0.224) and the bfloat16 state (0.087, under the sound "
    "largest) pass it, so logprob_mse alone refuses both; it lies between the "
    "sound largest 0.0912 and the zeroed carry's smallest 6.4 (2.7x and 26x) "
    "and guards against a gross error only. The logprob_mse limit has under "
    "1.5x on each side: a driver seed a little above the 34 sound runs is "
    "refused, a bfloat16-state seed a little under the 11 read would pass. "
    "What would widen it is in benchmarks/harness/check.py, which this PR "
    "may not edit: more decode steps than 64 or a longer carried context "
    "than 768, so that a bfloat16 state drifts past 3x (PERF.md section 7)."
)

# Per-token decay exp(gamma) = sigmoid(w_g . hn + b_g). A trained gate keeps
# most of its state from token to token; a zero-mean gate would halve it
# every token, a prefill chunk would have forgotten its own start, and a
# program that dropped the carried state between chunks would still pass.
# So b_g is drawn uniform in GATE_BIAS and w_g with standard deviation
# GATE_W_STD / sqrt(E): the gate's logit lies in about 5.25-9.15, the
# decay in about 0.995-0.9999.
GATE_BIAS = (6.3, 8.1)
GATE_W_STD = 0.35


def model_config(name: str, m: Mapping):
    from xllm_service_tpu.models.configs import ModelConfig

    if m.get("sliding_window") and m.get("use_sliding_window", False):
        raise ValueError("sliding-window configurations are not wired here")
    if m.get("attention_bias"):
        raise ValueError("this family has no QKV bias")
    if float(m["retention_eps"]) != 1e-6:
        raise ValueError("retention_eps: the program's is the constant 1e-6")
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
        tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
        qk_norm=True,
        retention_degree=int(m["retention_degree"]),
    )


def weight_shapes(m: Mapping) -> Dict:
    E, F, L, V = (
        m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"],
        m["vocab_size"],
    )
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    layers = {
        "attn_norm": (L, E), "mlp_norm": (L, E),
        "q_head_norm": (L, D), "k_head_norm": (L, D),
        "wq": (L, E, Hq * D), "wk": (L, E, Hkv * D), "wv": (L, E, Hkv * D),
        "wo": (L, Hq * D, E),
        "w_ret_gate": (L, E, Hkv), "b_ret_gate": (L, Hkv),
        "w_gate": (L, E, F), "w_up": (L, E, F), "w_down": (L, F, E),
    }
    out = {"embed": (V, E), "final_norm": (E,), "layers": layers}
    if not m.get("tie_word_embeddings"):
        out["lm_head"] = (E, V)
    return out


def make_weights(m: Mapping, key, dtype):
    """All parameters from `key`, in the program's parameter tree for this
    family; traceable. Matrices ~ N(0, 1/fan_in); every norm gain (block,
    final, per-head q and k) ~ N(1, 0.1) in float32; the gate as above.
    Nothing is left at a value (0 or 1) that would let a path skip it."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    names = sorted(shapes["layers"]) + sorted(k for k in shapes if k != "layers")
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def draw(name, shape):
        if name == "b_ret_gate":
            return jax.random.uniform(
                keys[name], shape, jnp.float32, GATE_BIAS[0], GATE_BIAS[1]
            )
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name.endswith("norm"):
            return 1.0 + 0.1 * z  # float32, as served
        if name == "w_ret_gate":
            return (GATE_W_STD * z / np.sqrt(shape[-2])).astype(dtype)
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


def _head_blocks(V: int) -> int:
    return next(n for n in (8, 4, 2, 1) if V % n == 0)


def forward_logits(weights, m: Mapping, tokens, idx):
    """tokens [T] int32 (one sequence, right-padded; padding never reaches
    an earlier position through the causal mask), idx [n] positions whose
    next-token logits are wanted -> [n, V] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = tokens.shape[0]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    g = Hq // Hkv
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    p, ret_eps = int(m["retention_degree"]), float(m["retention_eps"])
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(f32)  # [T, E]

        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(f32), lp)
            h = _rms_norm(x, lp["attn_norm"], eps)
            q = (h @ lp["wq"]).reshape(T, Hq, D)
            k = (h @ lp["wk"]).reshape(T, Hkv, D)
            v = (h @ lp["wv"]).reshape(T, Hkv, D)
            q = _rope(_rms_norm(q, lp["q_head_norm"], eps), pos, theta)  # qwen3
            k = _rope(_rms_norm(k, lp["k_head_norm"], eps), pos, theta)  # qwen3
            gamma = jax.nn.log_sigmoid(h @ lp["w_ret_gate"] + lp["b_ret_gate"])  # assumed
            G = jnp.cumsum(gamma, axis=0)  # paper: [T, Hkv]
            decay = jnp.exp(jnp.where(  # paper: exp(G_t - G_j), j <= t
                causal[None], G.T[:, :, None] - G.T[:, None, :], -jnp.inf))
            s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, g, axis=1)) / np.sqrt(D)
            a = jnp.repeat(decay, g, axis=0) * s ** p  # paper; p assumed
            y = jnp.einsum("hqk,khd->qhd", a, jnp.repeat(v, g, axis=1))
            y = y / (a.sum(-1).T[:, :, None] + ret_eps)  # assumed
            x = x + y.reshape(T, Hq * D) @ lp["wo"]
            h = _rms_norm(x, lp["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
            return x, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        h = _rms_norm(x[idx], weights["final_norm"].astype(f32), eps)
        head = weights["lm_head"] if "lm_head" in weights else weights["embed"].T
        V = head.shape[1]
        nb = _head_blocks(V)

        def block(i):  # a whole float32 head does not fit: V / nb columns
            w = jax.lax.dynamic_slice_in_dim(head, i * (V // nb), V // nb, axis=1)
            return h @ w.astype(f32)

        out = jax.lax.map(block, jnp.arange(nb))  # [nb, n, V / nb]
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)
