#!/usr/bin/env python3
"""The controls of the MiMo-V2-Flash family's `correct` (after
control_solar.py; control_granite.py's runner): the cell's own check served
through a program that is wrong in one known way must come out as NOT
correct, and the comparison at the TIMED sizes, which the check's
512-1536-token prompts do not reach.

    python3 benchmarks/tests/control_mimo.py --config mimo-v2-flash \
        --mode sound|no-window|no-sink|theta-swapped|full-rotary|unscaled|bias-weight|w-int8|wrong-expert|stale-block|long \
        --seeds 11 12 [--rehearse]

  * no-window: the window layers' launches get no window: they attend
    every position their table still names (the blocks freed behind the
    sequence read as the garbage block);
  * no-sink: the window layers' launches get no sink logit;
  * theta-swapped: the two kinds of layer exchange their rope theta;
  * full-rotary: all 192 lanes of a head rotate, not the first 64;
  * unscaled: attention_value_scale left out (1 for 0.707);
  * bias-weight: the router's selection bias joins the combine weights
    (the chosen experts weigh by score + bias): separable in float32 on
    the CPU alone; at the cell's size in bfloat16 it reads a sound run's
    number (the bias is a hundredth of a chosen score: PERF.md section 2);
  * w-int8: the program's int8 weights (ops/quant.py);
  * wrong-expert: every held pair goes through the NEXT held expert's
    matrices (the groups' boundaries off by one);
  * stale-block: the engine frees a window block one block EARLY and
    leaves its entry in the table: a freed block, reused by whichever
    sequence allocates next, is read while its positions are in the window;
  * long: sound, but ONE prompt of --long-prompt tokens (default 15360: 30
    chunks of 512, 119 window blocks freed on the way) and
    --long-tokens greedy tokens (default 64), against the reference in
    blocks: the timed path at the timed sizes.

A `kv-int8` control is not here: the family refuses `kv_cache_dtype` by
name at build (runtime/executor.py), so there is no int8 pool to serve
through. One process, one engine, every seed in turn; one JSON line a seed
and a summary line. A test of the comparison, not part of the yardstick."""

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

MODES = ("sound", "no-window", "no-sink", "theta-swapped", "full-rotary", "unscaled",
         "bias-weight", "w-int8", "wrong-expert", "stale-block", "long")
SHARED = ("wrong-expert",)  # control_granite.py's own patch
# a replaced field of the program's ModelConfig
REPLACED = {
    "theta-swapped": lambda c: {"rope_theta": c.window_rope_theta, "window_rope_theta": c.rope_theta},
    "full-rotary": lambda c: {"rotary_dim": c.head_dim},
    "unscaled": lambda c: {"attn_value_scale": 1.0},
}


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
    if mode in ("no-window", "no-sink"):
        from xllm_service_tpu.models import granite

        drop = "window" if mode == "no-window" else "sinks"
        for name in ("paged_attention", "prefill_attention", "mixed_attention"):
            sound = getattr(granite, name)

            def without(*a, _sound=sound, **kw):
                kw.pop(drop, None)
                return _sound(*a, **kw)

            setattr(granite, name, without)
    if mode == "bias-weight":
        import jax
        import jax.numpy as jnp

        from xllm_service_tpu.models import llama

        sound_route = llama.moe_route

        def biased(lp, cfg, x):  # the chosen experts weigh by score + bias
            topi, _ = sound_route(lp, cfg, x)
            scores = jax.nn.sigmoid(jnp.einsum(
                "te,ex->tx", x.astype(jnp.float32), lp["router"].astype(jnp.float32)
            )) + lp["router_bias"].astype(jnp.float32)
            w = jnp.take_along_axis(scores, topi, axis=-1)
            return topi, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)

        llama.moe_route = biased
    if mode == "stale-block":
        from xllm_service_tpu.runtime.block_manager import WindowBlockManager

        sound_slide = WindowBlockManager.slide

        def early(self, block_ids, was_lo, lo, hi, row):
            kept = row[was_lo:hi].copy()
            lo_early = min(lo + 1, max(hi - 1, lo))
            out = sound_slide(self, block_ids, was_lo, lo_early, hi, row)
            stale = row[was_lo:hi] == 0
            row[was_lo:hi][stale] = kept[stale]  # the freed blocks' entries stay
            return out

        WindowBlockManager.slide = early
    return family


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=15360)
    ap.add_argument("--long-tokens", type=int, default=64)
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
