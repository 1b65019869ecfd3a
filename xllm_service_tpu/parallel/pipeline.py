"""Pipeline parallelism: GPipe-schedule dense forward over a `pp` mesh
axis.

The LAST absent row of SURVEY.md §2.2 — absent everywhere in the
reference too ("None anywhere"), deprioritized by three verdicts, and
closed here at the level the reference family actually uses pipelines:
a stage-sharded forward for prefill/training-shaped work. (PP for
autoregressive DECODE serving trades per-token latency for nothing at
this scale — tp/sp/dp/ep already cover the serving meshes; the
reference ships no PP at all.)

TPU-first design: the stacked layer leaves [L, ...] shard over the
`pp` axis on the LAYER dimension (stage s holds layers
[s*L/S, (s+1)*L/S)); one `shard_map` program runs the classic GPipe
schedule — S + M - 1 ticks over M microbatches, each tick applying the
device's local layer stack (a lax.scan) and rotating activations one
stage forward with `lax.ppermute` over ICI. Every device executes the
same fixed-shape program (inactive ticks compute on garbage and are
masked), so XLA compiles ONE step body; bubbles follow the standard
(S - 1) / (S + M - 1) fraction.

Exactness: output logits equal models/llama.forward_dense on the same
params (parity-pinned in tests/test_pipeline.py and the driver dryrun).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xllm_service_tpu.models.configs import ModelConfig


def pipeline_param_shardings(cfg: ModelConfig, mesh: Mesh,
                             pp_axis: str = "pp"):
    """NamedShardings for the llama param pytree with the stacked layer
    leaves split over `pp_axis` on the layer axis; everything else
    replicated (stage 0 embeds, the last stage unembeds)."""
    from xllm_service_tpu.models import llama

    shapes = jax.eval_shape(
        lambda k: llama.init_params(cfg, k, jnp.float32),
        jax.random.key(0),
    )
    rep = NamedSharding(mesh, P())
    layer = NamedSharding(mesh, P(pp_axis))
    return {
        k: jax.tree.map(lambda _: layer if k == "layers" else rep, v)
        for k, v in shapes.items()
    }


def _apply_local_layers(lp_local, cfg: ModelConfig, x: jnp.ndarray,
                        positions: jnp.ndarray,
                        causal: jnp.ndarray) -> jnp.ndarray:
    """Scan this stage's layer slice over activations [b, Lq, E] — the
    same dense layer body as llama.hidden_dense."""
    from xllm_service_tpu.models.llama import _mlp_block, _qkv
    from xllm_service_tpu.ops.norms import rms_norm
    from xllm_service_tpu.ops.quant import wt

    scale = cfg.head_dim**-0.5
    Lq = x.shape[1]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = Hq // Hkv

    def layer_fn(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)

        def one_seq(hx):
            q, k, v = _qkv(lp, cfg, hx, positions)
            qf = q.astype(jnp.float32).reshape(Lq, Hkv, g, D)
            scores = jnp.einsum(
                "qhgd,khd->hgqk", qf, k.astype(jnp.float32)
            ) * scale
            scores = jnp.where(causal[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum(
                "hgqk,khd->qhgd", probs, v.astype(jnp.float32)
            )
            return attn.reshape(Lq, Hq * D).astype(x.dtype)

        attn = jax.vmap(one_seq)(h)
        # wt() dequantizes int8/int4 leaves at the use site (and is the
        # identity on plain arrays) — same contract as llama.hidden_dense.
        wo = wt(lp["wo"])
        x = x + jnp.einsum(
            "ble,ef->blf", attn,
            wo.astype(attn.dtype) if wo.dtype != attn.dtype else wo,
        )
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        # _mlp_block keeps this body on the exact dense per-row program
        # by default and in lockstep with llama.hidden_dense (whose twin
        # this is) when the grouped-MoE dispatch is enabled — full-length
        # prompts here, every row live.
        x = x + _mlp_block(lp, cfg, h)
        return x, None

    x, _ = jax.lax.scan(layer_fn, x, lp_local)
    return x


def pipeline_forward_dense(
    params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,  # [B, Lq] int32, B % microbatches == 0
    mesh: Mesh,
    pp_axis: str = "pp",
    microbatches: int = 2,
) -> jnp.ndarray:
    """[B, Lq] -> logits [B, Lq, V], exactly llama.forward_dense, with
    the layer stack pipelined over `mesh`'s `pp_axis`. Call under jit
    with the mesh installed and params placed per
    pipeline_param_shardings."""
    from xllm_service_tpu.models.llama import _embed, _project
    from xllm_service_tpu.ops.norms import rms_norm
    from xllm_service_tpu.ops.quant import wdtype

    S = mesh.shape[pp_axis]
    B, Lq = token_ids.shape
    M = microbatches
    assert B % M == 0, (B, M)
    b = B // M
    positions = jnp.arange(Lq, dtype=jnp.int32)
    causal = jnp.tril(jnp.ones((Lq, Lq), dtype=bool))
    if cfg.sliding_window:
        causal &= (
            positions[None, :] > positions[:, None] - cfg.sliding_window
        )

    def local(layers_local, embed_w, final_norm, head_or_embed,
              token_ids):
        d = jax.lax.axis_index(pp_axis)
        full = {"embed": embed_w, "layers": None}
        x_mb = _embed(full, cfg, token_ids, wdtype(embed_w)).reshape(
            M, b, Lq, -1
        )
        E = x_mb.shape[-1]
        perm = [(i, (i + 1) % S) for i in range(S)]
        out0 = jnp.zeros((M, b, Lq, E), x_mb.dtype)
        recv0 = jnp.zeros((b, Lq, E), x_mb.dtype)

        def tick(carry, t):
            recv, outs = carry
            j = t - d  # this device's microbatch index this tick
            valid = (j >= 0) & (j < M)
            jc = jnp.clip(j, 0, M - 1)
            x_in = jnp.where(d == 0, x_mb[jc], recv)
            y = _apply_local_layers(
                layers_local, cfg, x_in, positions, causal
            )
            outs = jnp.where(
                valid & (d == S - 1),
                outs.at[jc].set(y),
                outs,
            )
            recv = jax.lax.ppermute(y, pp_axis, perm)
            return (recv, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (recv0, out0), jnp.arange(S + M - 1, dtype=jnp.int32)
        )
        # Only the last stage holds real outputs; replicate via psum.
        outs = jnp.where(d == S - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, pp_axis)
        h = rms_norm(
            outs.reshape(B, Lq, E), final_norm, cfg.rms_norm_eps
        )
        full2 = (
            {"embed": head_or_embed} if cfg.tie_word_embeddings
            else {"lm_head": head_or_embed, "embed": embed_w}
        )
        return _project(full2, cfg, h)

    head = (
        params["embed"] if cfg.tie_word_embeddings else params["lm_head"]
    )
    rep = P()
    in_specs = (
        jax.tree.map(lambda _: P(pp_axis), params["layers"]),
        rep, rep, rep, rep,
    )
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=rep,
        check_vma=False,
    )
    return fn(
        params["layers"], params["embed"], params["final_norm"], head,
        token_ids,
    )
