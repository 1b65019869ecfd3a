#!/usr/bin/env python3
"""The controls of the Laguna family's `correct` (after control_mimo.py;
control_granite.py's runner): the cell's own check served through a program
that is wrong in one known way must come out as NOT correct, and the
comparison at the TIMED sizes, which the check's 512-1536-token prompts do
not reach.

    python3 benchmarks/tests/control_laguna.py --config laguna-xs.2 \
        --mode sound|w-int8|no-gate|gate-broadcast|no-yarn|no-attn-factor|lanes-swapped|tables-swapped|window-128|no-window|stale-block|no-shared|no-scale|softmax-scores|wrong-expert|long \
        --seeds 11 12 [--rehearse]

  * w-int8: the program's int8 weights (ops/quant.py);
  * no-gate: the attention output reaches W_o ungated;
  * gate-broadcast: head 0's gate on every head of the layer;
  * no-yarn: the full layers rotate by plain theta 5e5 (no scaled table,
    no attention factor);
  * no-attn-factor: YaRN's frequencies, cos and sin times 1;
  * lanes-swapped: the two kinds exchange their rotary lanes (all 128 on
    the full layers, 64 on the window layers);
  * tables-swapped: the two kinds exchange their rotary TABLES, each over
    its own lanes (plain theta 1e4 on the full layers, YaRN over theta 5e5
    with its attention factor on the window layers);
  * window-128: a window of 128 positions in 512's place, in the launches
    and in the engine's sliding alike;
  * no-window: the window layers' launches get no window: they attend
    every position their table still names (the blocks freed behind the
    sequence read as the garbage block);
  * stale-block: the engine frees a window block one block EARLY and
    leaves its entry in the table;
  * no-shared: the shared expert left out of every layer;
  * no-scale: moe_routed_scaling_factor left out (1 for 2.5);
  * softmax-scores: the chosen experts weigh by their softmax
    probabilities, renormalised and scaled, in the sigmoid's place (the
    choice itself is the same: both are monotone in the logit);
  * wrong-expert: every pair goes through the NEXT expert's matrices;
  * long: sound, but ONE prompt of --long-prompt tokens (default 32768: 64
    chunks of 512, 252 window blocks freed on the way) and --long-tokens
    greedy tokens (default 64), against the reference in blocks: the timed
    path at the timed sizes.

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
import control_mimo  # noqa: E402  (no-window and stale-block are the window family's)

MODES = ("sound", "w-int8", "no-gate", "gate-broadcast", "no-yarn", "no-attn-factor",
         "lanes-swapped", "tables-swapped", "window-128", "no-window", "stale-block",
         "no-shared", "no-scale", "softmax-scores", "wrong-expert", "long")
GRANITE = ("wrong-expert", "no-shared")  # control_granite.py's own patches
MIMO = ("no-window", "stale-block")  # control_mimo.py's
# a replaced field of the program's ModelConfig
REPLACED = {
    "no-yarn": lambda c: {"rope_scaling_type": ""},
    "no-attn-factor": lambda c: {"rope_attention_factor": 1.0},
    "lanes-swapped": lambda c: {"rotary_dim": c.window_rotary_dim,
                                "window_rotary_dim": c.rotary_dim},
    "window-128": lambda c: {"sliding_window": 128},
    "no-scale": lambda c: {"routed_scaling_factor": 1.0},
}


def break_program(mode: str, family):
    """Make the program wrong in the one way `mode` names; returns the
    family the executor is built over."""
    if mode in GRANITE:
        return runner.break_program_granite(mode, family)
    if mode in MIMO:
        return control_mimo.break_program(mode, family)
    if mode in REPLACED:
        sound_config = family.model_config

        def replaced(name, m):
            cfg = sound_config(name, m)
            return dataclasses.replace(cfg, **REPLACED[mode](cfg))

        return runner._Facade(family, replaced)
    if mode in ("no-gate", "gate-broadcast"):
        from xllm_service_tpu.models import granite

        sound_gated = granite._gated

        def one_gate(lp, cfg, h, o):  # head 0's column of W_gate for every head
            w = lp["w_ogate"]
            return sound_gated({**lp, "w_ogate": w[:, :1].repeat(w.shape[1], axis=1)}, cfg, h, o)

        granite._gated = (lambda lp, cfg, h, o: o) if mode == "no-gate" else one_gate
    if mode == "tables-swapped":
        from xllm_service_tpu.models import granite
        from xllm_service_tpu.ops import rope as rope_ops

        sound_tables = granite.rotary_tables

        def swapped(cfg):
            full, window = (sound_tables(cfg)[k] for k in ("attention", "window"))
            inv_freq, scale = rope_ops.rope_parameters(window.lanes, cfg)
            return {
                "attention": granite.RotaryTable(full.lanes, window.theta),
                "window": granite.RotaryTable(window.lanes, full.theta, inv_freq, scale,
                                              full.scaling),
            }

        granite.rotary_tables = swapped
    if mode == "softmax-scores":
        import jax
        import jax.numpy as jnp

        from xllm_service_tpu.models import llama

        sound_route = llama.moe_route

        def softmaxed(lp, cfg, x):
            topi, _ = sound_route(lp, cfg, x)
            p = jax.nn.softmax(jnp.einsum(
                "te,ex->tx", x.astype(jnp.float32), lp["router"].astype(jnp.float32)), axis=-1)
            w = jnp.take_along_axis(p, topi, axis=-1)
            return topi, cfg.routed_scaling_factor * w / jnp.sum(w, axis=-1, keepdims=True)

        llama.moe_route = softmaxed
    return family


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=32768)
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
