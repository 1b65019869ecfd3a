#!/usr/bin/env python3
"""The controls of the Brumby family's `correct` (after control_lowprec.py,
which is the Llama family's): the cell's own check served through a
program that is wrong in one known way must come out as NOT correct.

    python3 benchmarks/tests/control_brumby.py --config brumby-14b \
        --mode sound|w-int8|state-bf16|zero-carry --seeds 11 12 [--rehearse]

  * w-int8: the program's int8 weights (ops/quant.py);
  * state-bf16: the state pool held in bfloat16 (the program offers no
    such path: `ModelExecutor.state_dtype` is patched here), the nearest
    precision below the float32 the configuration states;
  * zero-carry: the carried state dropped at every chunk boundary (each
    prefill chunk starts as if at position 0): what a program that lost
    its state between chunks would serve. The family's weights draw slow
    decays so that this cannot pass.

One process, one engine, every seed in turn; one JSON line a seed and a
summary line. A test of the comparison, not part of the yardstick."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MODES = ("sound", "w-int8", "state-bf16", "zero-carry")


def run(config_name: str, mode: str, seeds, rehearse: bool) -> list:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    if rehearse:  # as run.py --rehearse does, and for its reason
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    from benchmarks.harness import check, family as family_mod, stack as stack_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        config = json.load(f)
    family = family_mod.load(config)
    engine = dict(config["engine"])
    if mode == "state-bf16":
        from xllm_service_tpu.runtime.executor import ModelExecutor

        ModelExecutor.state_dtype = jnp.bfloat16
    if mode == "zero-carry":
        from xllm_service_tpu.ops import retention

        sound = retention.chunk_update

        def forgetful(S, z, layer, slots, start, length, *a, **kw):
            return sound(S, z, layer, slots, jnp.zeros_like(start), length, *a, **kw)

        retention.chunk_update = forgetful
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax-compile-cache")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not rehearse:
        raise SystemExit("control_brumby: no accelerator (use --rehearse on the CPU)")
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
            samples = check.serve_sample(stack, seed)
            if mode == "w-int8":  # the reference reads the unquantized weights
                fresh_weights(seed)
            res = check.judge(stack, samples)
            res.update(seed=seed, mode=mode, platform=dev.platform, kind=dev.device_kind)
            print(json.dumps(res), flush=True)
            out.append(res)
    finally:
        stack.stop()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    res = run(args.config, args.mode, args.seeds, args.rehearse)
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
