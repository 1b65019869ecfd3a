#!/usr/bin/env python3
"""The controls of the Falcon-H1 family's `correct` (after control_mimo.py;
control_granite.py's runner): the cell's own check served through a program
that is wrong in one known way must come out as NOT correct, and the
comparison at the TIMED sizes, which the check's 256-768-token prompts do
not reach.

    python3 benchmarks/tests/control_falcon_h1.py --config falcon-h1-34b \
        --mode sound|w-int8|state-bf16|zero-carry|no-conv-carry|no-attn|no-state|ssm-swap|mlp-swap|group-swap|stale-block|long|long-bf16|shares \
        --seeds 11 12 [--rehearse]

  * w-int8, state-bf16, zero-carry, no-conv-carry, long-bf16:
    control_granite.py's own patches (int8 weights; the state pools in
    bfloat16; the SSM state dropped at every chunk boundary; the
    convolution's carried rows never read);
  * no-attn: the attention branch left out of every block's residual add;
  * no-state: the state branch left out of it;
  * ssm-swap: `ssm_multipliers[2]` and `[3]` (the B and the C lanes')
    exchanged;
  * mlp-swap: `mlp_multipliers[0]` and `[1]` exchanged;
  * group-swap: group 1's heads read group 0's B and C (every head of the
    Mamba-2 mixer reads the first group's planes);
  * stale-block: the K/V rows of a sequence's SECOND 256-token chunk are
    never written: their half block keeps what it held (zeros, or an
    earlier sequence's rows) and every later query reads it;
  * long: sound, but ONE prompt of --long-prompt tokens (default 3072: 12
    chunks of 256, the traffic's longest) and --long-tokens greedy tokens
    (default 64), against the reference in query blocks;
  * shares: no engine: the reference's RMS of the stream and of what each
    block adds to it (attention branch, state branch, MLP) over one
    832-token sequence of the seed's weights: what the draw's gains are
    read by (families/falcon_h1.py LIMITS_READINGS).

A `kv-int8` control is not here: the family refuses `kv_cache_dtype` by
name at build (runtime/executor.py). One process, one engine, every seed in
turn; one JSON line a seed and a summary line. A test of the comparison,
not part of the yardstick."""

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

MODES = ("sound", "w-int8", "state-bf16", "zero-carry", "no-conv-carry", "no-attn", "no-state",
         "ssm-swap", "mlp-swap", "group-swap", "stale-block", "long", "long-bf16", "shares")
SHARED = ("w-int8", "state-bf16", "zero-carry", "no-conv-carry", "long-bf16")


def _swapped(t, i, j):
    t = list(t)
    t[i], t[j] = t[j], t[i]
    return tuple(t)


# a replaced field of the program's ModelConfig
REPLACED = {
    "ssm-swap": lambda c: {"ssm_multipliers": _swapped(c.ssm_multipliers, 2, 3)},
    "mlp-swap": lambda c: {"mlp_multipliers": _swapped(c.mlp_multipliers, 0, 1)},
}
CHUNK = 256  # the cell's prefill chunk (stale-block)


def break_program(mode: str, family):
    """Make the program wrong in the one way `mode` names; returns the
    family the executor is built over."""
    if mode in SHARED:
        return runner.break_program_granite(mode, family)
    if mode in REPLACED:
        sound_config = family.model_config

        def replaced(name, m):
            cfg = sound_config(name, m)
            return dataclasses.replace(cfg, **REPLACED[mode](cfg))

        return runner._Facade(family, replaced)
    if mode in ("no-attn", "no-state"):
        from xllm_service_tpu.models import granite

        sound_scales = granite._branch_scales

        def one_branch(cfg):
            ca, cs = sound_scales(cfg)
            return (0.0, cs) if mode == "no-attn" else (ca, 0.0)

        granite._branch_scales = one_branch
    if mode == "group-swap":
        import jax.numpy as jnp

        from xllm_service_tpu.ops import mamba as mamba_ops

        first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)  # [.., G, N]
        sound_decode, sound_chunk = mamba_ops.decode_update, mamba_ops.chunk_update

        def decode(S, layer, active, x, dt, A, B, C, D, **kw):
            return sound_decode(S, layer, active, x, dt, A, first(B), first(C), D, **kw)

        def chunk(S, layer, slots, start, length, x, dt, A, B, C, D):
            return sound_chunk(S, layer, slots, start, length, x, dt, A, first(B), first(C), D)

        mamba_ops.decode_update, mamba_ops.chunk_update = decode, chunk
    if mode == "stale-block":
        import jax.numpy as jnp

        from xllm_service_tpu.ops import kv_write as kv_write_ops

        sound_plan = kv_write_ops.write_plan

        def skipping(K, tables, start, length, Lpad):
            if Lpad > 1:  # a prefill chunk: the second of a sequence writes nothing
                length = jnp.where(start // CHUNK == 1, 0, length)
            return sound_plan(K, tables, start, length, Lpad)

        kv_write_ops.write_plan = skipping
    return family


def shares(config_name: str, seeds, rehearse: bool) -> int:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import family as family_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        config = json.load(f)
    family = family_mod.load(config)
    dtype = jnp.bfloat16 if config["engine"]["dtype"] == "bfloat16" else jnp.float32
    for seed in seeds:
        weights = jax.jit(lambda k: family.make_weights(config, k, dtype))(family_mod.seed_key(seed))
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
        tokens = jnp.asarray(rng.integers(0, config["vocab_size"], size=832), jnp.int32)
        rows = np.asarray(jax.jit(lambda w, t: family.branch_shares(w, config, t))(weights, tokens))
        print(json.dumps({
            "mode": "shares", "seed": seed, "platform": jax.devices()[0].platform,
            "rms_by_block": {k: [round(float(v), 4) for v in rows[:, i]]
                             for i, k in enumerate(("stream", "attention", "state", "mlp"))},
        }), flush=True)
        del weights
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=3072)
    ap.add_argument("--long-tokens", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.mode == "shares":
        return shares(args.config, args.seeds, args.rehearse)
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
