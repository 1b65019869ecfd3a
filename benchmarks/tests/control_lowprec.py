#!/usr/bin/env python3
"""The control of "How correct is decided": the same cell served in the
nearest precision below the one its configuration states, through the
program's own paths (an int8 KV cache, `kv_cache_dtype`, and int8
weights, ops/quant.py), must come out as NOT correct.

    python3 benchmarks/tests/control_lowprec.py --config qwen2.5-3b \
        --mode sound|kv-int8|w-int8 --seeds 11 12 13 [--rehearse]

One process, one engine, every seed in turn: new weights from the seed,
the correctness sample served, the reference run. Prints one JSON line per
seed and a summary line. Not run by the benchmark's own runs; the CPU
test beside this file runs it at tiny size. This file reaches further
into the program than the harness does (it calls the executor's
quantizer): it is a test of the comparison, not part of the yardstick."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run(config_name: str, mode: str, seeds, rehearse: bool, dtype: str = "") -> list:
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if rehearse:  # as run.py --rehearse does, and for its reason
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    from benchmarks.harness import check, family as family_mod, stack as stack_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        config = json.load(f)
    family = family_mod.load(config)
    engine = dict(config["engine"])
    if dtype:
        engine["dtype"] = dtype
    if mode == "kv-int8":
        engine["kv_cache_dtype"] = "int8"
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax-compile-cache")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not rehearse:
        raise SystemExit("control_lowprec: no accelerator (use --rehearse on the CPU)")
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
    ap.add_argument("--mode", choices=("sound", "kv-int8", "w-int8"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    res = run(args.config, args.mode, args.seeds, args.rehearse, args.dtype)
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
