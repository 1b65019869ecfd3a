#!/usr/bin/env python3
"""The controls of the DeepSeek-V2 family's `correct` (after
control_brumby.py): the cell's own check served through a program that is
wrong in one known way must come out as NOT correct, and the comparison
at the TIMED sizes, which the check's 512-1536-token prompts do not reach.

    python3 benchmarks/tests/control_deepseek.py --config deepseek-v2 \
        --mode sound|w-int8|kv-int8|no-shared|unscaled|wrong-expert|long --seeds 11 12 [--rehearse]

  * w-int8: the program's int8 weights (ops/quant.py);
  * kv-int8: the program's int8 latent cache, the nearest precision below
    the bfloat16 the configuration states;
  * no-shared: the shared expert left out of every expert layer (a fault
    of the mechanism: the program computes the routed part alone);
  * unscaled: a chosen expert's weight not multiplied by
    routed_scaling_factor (the program routes with a factor of 1);
  * wrong-expert: a fault of the grouped expert PRODUCT: every held pair
    goes through the next held expert's matrices (the groups' boundaries
    off by one), weights and routing as they were;
  * long: sound, but ONE prompt of --long-prompt tokens (default 7680: 15
    chunks of 512) and 64 greedy tokens, against the reference in blocks:
    its logprob_mse beside the family's limit.

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

MODES = ("sound", "w-int8", "kv-int8", "no-shared", "unscaled", "wrong-expert", "long")


def run(config_name: str, mode: str, seeds, rehearse: bool, long_prompt: int) -> list:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    if rehearse:  # as run.py --rehearse does, and for its reason
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    from benchmarks.harness import check, family as family_mod, stack as stack_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        config = json.load(f)
    family = family_mod.load(config)
    engine = dict(config["engine"])
    if mode == "kv-int8":
        engine["kv_cache_dtype"] = "int8"
    if mode == "no-shared":
        import jax.numpy as jnp
        from xllm_service_tpu.models import llama

        llama._shared_experts = lambda lp, x: jnp.zeros_like(x)
    if mode == "wrong-expert":
        from xllm_service_tpu.ops import moe as moe_ops

        sound_product = moe_ops._held_product

        def next_expert(x, loc_e, held, w_gate, *rest):
            return sound_product(x, (loc_e + 1) % w_gate.shape[-3], held, w_gate, *rest)

        moe_ops._held_product = next_expert
    if mode == "unscaled":
        # the executor is built over a configuration that routes with a
        # factor of 1; the weights and the reference stay the sound one's
        sound = family.model_config
        family = _Facade(family, lambda name, m: dataclasses.replace(
            sound(name, m), routed_scaling_factor=1.0))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax-compile-cache")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not rehearse:
        raise SystemExit("control_deepseek: no accelerator (use --rehearse on the CPU)")
    stack = stack_mod.Stack(config_name, family, config, seeds[0], cache_dir, engine=engine)
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
            if mode == "long":
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
    ap.add_argument("--long-prompt", type=int, default=7680)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    res = run(args.config, args.mode, args.seeds, args.rehearse, args.long_prompt)
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
