#!/usr/bin/env python3
"""The controls of the Granite 4.0-H family's `correct` (after
control_deepseek.py): the cell's own check served through a program that
is wrong in one known way must come out as NOT correct, and the comparison
at the TIMED sizes, which the check's 256-768-token prompts do not reach.

    python3 benchmarks/tests/control_granite.py --config granite-4.0-h-small \
        --mode sound|w-int8|state-bf16|zero-carry|no-conv-carry|wrong-expert|no-shared|attn-scale|long|long-bf16 \
        --seeds 11 12 [--rehearse]

  * w-int8: the program's int8 weights (ops/quant.py);
  * state-bf16: the SSM and convolution state pools held in bfloat16, the
    nearest precision below the float32 the configuration states;
  * zero-carry: the SSM state dropped at every chunk boundary (every
    chunk scans from an empty state);
  * no-conv-carry: the convolution's carried rows never read (a chunk's
    first K-1 tokens and every decode token see a zero history);
  * wrong-expert: every held pair goes through the NEXT held expert's
    matrices (the groups' boundaries off by one);
  * no-shared: the shared MLP left out of every layer;
  * attn-scale: scores scaled by head_dim**-0.5 for attention_multiplier;
  * long: sound, but ONE prompt of --long-prompt tokens (default 2048: 8
    chunks of 256) and --long-tokens greedy tokens (default 64): the state
    carried through 2,112 steps, its logprob_mse beside the family's limit;
  * long-bf16: `long` with `state-bf16`'s pools: what a bfloat16 state
    costs at sizes the cell's check does not reach.

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
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MODES = ("sound", "w-int8", "state-bf16", "zero-carry", "no-conv-carry", "wrong-expert",
         "no-shared", "attn-scale", "long", "long-bf16")


def break_program(mode: str, family):
    """Make the program wrong in the one way `mode` names; returns the
    family the executor is built over."""
    import jax.numpy as jnp

    if mode in ("state-bf16", "long-bf16"):
        from xllm_service_tpu.runtime.executor import ModelExecutor

        ModelExecutor.state_dtype = jnp.bfloat16
    if mode in ("zero-carry", "no-conv-carry"):
        from xllm_service_tpu.ops import mamba as mamba_ops

        name = "chunk_update" if mode == "zero-carry" else "conv_chunk"
        sound = getattr(mamba_ops, name)

        def from_nothing(pool, layer, slots, start, *rest):
            return sound(pool, layer, slots, jnp.zeros_like(start), *rest)

        setattr(mamba_ops, name, from_nothing)
    if mode == "no-conv-carry":
        sound_decode = mamba_ops.conv_decode

        def decode_from_nothing(conv, *rest):
            out, _ = sound_decode(jnp.zeros_like(conv), *rest)
            return out, conv

        mamba_ops.conv_decode = decode_from_nothing
    if mode == "no-shared":
        from xllm_service_tpu.models import llama

        llama._shared_experts = lambda lp, x: jnp.zeros_like(x)
    if mode == "wrong-expert":
        from xllm_service_tpu.ops import moe as moe_ops

        sound_product = moe_ops._held_product

        def next_expert(x, loc_e, held, w_gate, *rest):
            return sound_product(x, (loc_e + 1) % w_gate.shape[-3], held, w_gate, *rest)

        moe_ops._held_product = next_expert
    if mode == "attn-scale":
        sound_config = family.model_config

        def rescaled(name, m):
            cfg = sound_config(name, m)
            return dataclasses.replace(cfg, attention_multiplier=cfg.head_dim ** -0.5)

        return _Facade(family, rescaled)
    return family


def run(config_name: str, mode: str, seeds, rehearse: bool, long_prompt: int,
        long_tokens: int = 64) -> list:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    if rehearse:  # as run.py --rehearse does, and for its reason
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    from benchmarks.harness import check, family as family_mod, stack as stack_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        config = json.load(f)
    family = break_program(mode, family_mod.load(config))
    long = mode in ("long", "long-bf16")
    if long:  # this process's copy of the check's size, not the yardstick's
        check.CHECK_TOKENS = long_tokens
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax-compile-cache")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not rehearse:
        raise SystemExit("control_granite: no accelerator (use --rehearse on the CPU)")
    stack = stack_mod.Stack(config_name, family, config, seeds[0], cache_dir)
    out = []
    try:
        ex = stack.executor
        shardings = jax.tree.map(lambda a: a.sharding, ex.params)

        def fresh_weights(seed):
            for leaf in jax.tree.leaves(ex.params):
                leaf.delete()
            stack_mod.place_weights(ex, family, config, seed, shardings)

        for i, seed in enumerate(seeds):
            if i or mode == "w-int8":
                fresh_weights(seed)
            if mode == "w-int8":
                ex._quantize_weights(shardings, bits=8)
            if long:
                rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
                prompt = rng.integers(0, config["vocab_size"], size=long_prompt).tolist()
                samples = stack_mod.greedy_sample(stack, [prompt], check.CHECK_TOKENS)
            else:
                samples = check.serve_sample(stack, seed)
            if mode == "w-int8":  # the reference reads the unquantized weights
                fresh_weights(seed)
            res = check.judge(stack, samples)
            res.update(seed=seed, mode=mode, platform=dev.platform, kind=dev.device_kind,
                       prompt_tokens=[len(s["prompt"]) for s in samples])
            print(json.dumps(res), flush=True)
            out.append(res)
    finally:
        stack.stop()
    return out


class _Facade:
    """A family whose `model_config` is replaced; the other four names
    (weights, reference, limits) are the sound family's."""

    def __init__(self, family, model_config):
        self._family, self.model_config = family, model_config
        self.__name__ = family.__name__

    def __getattr__(self, name):
        return getattr(self._family, name)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=2048)
    ap.add_argument("--long-tokens", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    res = run(args.config, args.mode, args.seeds, args.rehearse, args.long_prompt,
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
