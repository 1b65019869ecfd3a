#!/usr/bin/env python3
"""The controls of the Solar-Open2 family's `correct` (after
control_granite.py, whose runner this file uses): the cell's own check
served through a program that is wrong in one known way must come out as
NOT correct, and the comparison at the TIMED sizes, which the check's
512-1536-token prompts do not reach.

    python3 benchmarks/tests/control_solar.py --config solar-open2-250b \
        --mode sound|state-bf16|w-int8|zero-carry|no-conv-carry|beta-1x|scalar-decay|no-delta|no-gate|no-shared|wrong-expert|long|long-bf16 \
        --seeds 11 12 [--rehearse]

  * state-bf16: the delta-rule and convolution state pools held in
    bfloat16, the nearest precision below the float32 the configuration
    states;
  * w-int8: the program's int8 weights (ops/quant.py);
  * zero-carry: the delta-rule state dropped at every chunk boundary;
  * no-conv-carry: the convolution's carried rows never read;
  * beta-1x: beta = sigmoid(.) in (0, 1): kda_allow_neg_eigval's factor 2
    left out;
  * scalar-decay: a head's channels all decayed by their mean (gated
    DeltaNet, not KDA);
  * no-delta: the - beta k (k^T S) term left out (gated linear attention);
  * no-gate: the GQA layers' output gate left out;
  * no-shared: the shared expert left out of every layer;
  * wrong-expert: every held pair goes through the NEXT held expert's
    matrices;
  * long: sound, but ONE prompt of --long-prompt tokens (default 4096: 8
    chunks of 512) and --long-tokens greedy tokens (default 256);
  * long-bf16: `long` with `state-bf16`'s pools.

One process, one engine, every seed in turn; one JSON line a seed and a
summary line. A test of the comparison, not part of the yardstick."""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import control_granite as runner  # noqa: E402  (the engine, the seeds, the judge)

MODES = ("sound", "state-bf16", "w-int8", "zero-carry", "no-conv-carry", "beta-1x",
         "scalar-decay", "no-delta", "no-gate", "no-shared", "wrong-expert", "long", "long-bf16")
SHARED = ("state-bf16", "long-bf16", "no-conv-carry", "no-shared", "wrong-expert")


def _no_delta(kda_ops):
    """Gated linear attention in place of the delta rule: S <- Diag(alpha)
    S + beta k v^T, token by token, decode and chunk alike."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def step(S, k, v, g, beta):  # [..., H, d] each, beta [..., H]
        return jnp.exp(g)[..., None] * S + (beta[..., None] * k)[..., None] * v[..., None, :]

    def decode_update(S, layer, active, q, k, v, g, beta, use_kernel=None, interpret=False):
        R = q.shape[0]
        old = jax.lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)[:R].astype(f32)
        new = step(old, k, v, g, beta)
        o = jnp.einsum("rhkv,rhk->rhv", new, q)
        keep = jnp.where(active[:, None, None, None], new, old)
        S = jax.lax.dynamic_update_slice(S, keep.astype(S.dtype)[None], (layer, 0, 0, 0, 0))
        return jnp.where(active[:, None, None], o, 0.0), S

    def chunk_update(S, layer, slots, start, length, q, k, v, g, beta, chunk=64):
        Pn, Lc = q.shape[:2]
        valid = jnp.arange(Lc)[None, :] < length[:, None]
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
        slots = jnp.clip(slots, 0, S.shape[1] - 1)
        olds = [jax.lax.dynamic_slice(S, (layer, slots[p], 0, 0, 0), (1, 1) + S.shape[2:])[0, 0]
                for p in range(Pn)]
        s0 = jnp.where((start > 0)[:, None, None, None], jnp.stack(olds).astype(f32), 0.0)

        def one(s, t):
            q_t, k_t, v_t, g_t, b_t = t
            s = step(s, k_t, v_t, g_t, b_t)
            return s, jnp.einsum("phkv,phk->phv", s, q_t)

        sT, o = jax.lax.scan(one, s0, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
        for p in range(Pn):
            row = jnp.where(length[p] > 0, sT[p].astype(S.dtype), olds[p])
            S = jax.lax.dynamic_update_slice(S, row[None, None], (layer, slots[p], 0, 0, 0))
        return jnp.moveaxis(o, 0, 1), S

    kda_ops.decode_update, kda_ops.chunk_update = decode_update, chunk_update


def break_program(mode: str, family):
    """Make the program wrong in the one way `mode` names; returns the
    family the executor is built over."""
    import jax.numpy as jnp

    if mode in SHARED:  # the faults both hybrids share: control_granite.py's own patches
        return runner.break_program_granite(mode, family)
    from xllm_service_tpu.models import granite
    from xllm_service_tpu.ops import kda as kda_ops

    if mode == "zero-carry":
        sound = kda_ops.chunk_update
        kda_ops.chunk_update = lambda S, layer, slots, start, *rest: sound(
            S, layer, slots, jnp.zeros_like(start), *rest)
    if mode == "scalar-decay":
        sound_inputs = granite._kda_inputs

        def mean_decay(lp, cfg, h):
            qkv, g, beta, gate = sound_inputs(lp, cfg, h)
            return qkv, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta, gate

        granite._kda_inputs = mean_decay
    if mode == "no-delta":
        _no_delta(kda_ops)
    if mode == "no-gate":
        granite._gated = lambda lp, cfg, h, o: o
    if mode == "beta-1x":
        sound_config = family.model_config
        return runner._Facade(family, lambda name, m: dataclasses.replace(
            sound_config(name, m), kda_neg_eigval=False))
    return family


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=4096)
    ap.add_argument("--long-tokens", type=int, default=256)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    runner.break_program_granite, runner.break_program = runner.break_program, break_program
    res = runner.run(args.config, args.mode, args.seeds, args.rehearse, args.long_prompt,
                     args.long_tokens)
    mse = [r.get("logprob_mse") for r in res]
    print(json.dumps({
        "summary": args.mode, "config": args.config, "seeds": args.seeds,
        "logprob_mse_min": min(mse), "logprob_mse_max": max(mse),
        "deficit_max": max(r.get("deficit_max", 0.0) for r in res),
        "verdicts": [r["ok"] for r in res],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
