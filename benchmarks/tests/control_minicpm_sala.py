#!/usr/bin/env python3
"""The controls of the MiniCPM-SALA family's `correct` (control_granite.py's
runner): the cell's own check served through a program that is wrong in one
known way must come out as NOT correct, and the comparison at the TIMED
sizes, which the check's 4,096-12,288-token prompts do not reach.

    python3 benchmarks/tests/control_minicpm_sala.py --config minicpm-sala \
        --mode sound|dense|no-local|stale-ck|state-bf16|no-rope|zero-carry|decay-l0|w-int8|long|long-bf16|shares \
        --seeds 11 12 [--rehearse]

  * dense: the dense launch in the selected rows' place (no row ever
    selects): proves that the check's prompts cross dense_len and that the
    draw makes the selected pages differ from the whole context;
  * no-local: a selection without the always-selected local blocks (the
    query's own block alone is forced; the other 31 of the window are
    left to the scores);
  * stale-ck: the compressed keys are never written (stage 1 scores
    zeros: every block ties and the lowest-numbered win);
  * state-bf16: the lightning state rounded to bfloat16 whenever it is
    stored (after every decode token and at every chunk's end), the
    nearest precision below the float32 the configuration states; the
    compressed keys stay float32;
  * no-rope: no rotary in the lightning layers;
  * zero-carry: the lightning state dropped at every chunk boundary;
  * decay-l0: every lightning layer decays as published layer 0 would;
  * w-int8: the program's int8 weights (ops/quant.py);
  * long: sound, but ONE prompt of --long-prompt tokens (default 24576: 6
    chunks of 4,096) and --long-tokens greedy tokens (default 64), against
    the reference in query blocks; long-bf16: `long` with `state-bf16`;
  * shares: no engine: the reference's RMS of the stream and of what each
    layer's mixer and MLP add to it over one 1,024-token sequence of the
    seed's weights (what the draw's gains are read by).

One process, one engine, every seed in turn; one JSON line a seed and a
summary line. A test of the comparison, not part of the yardstick."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import control_granite as runner  # noqa: E402  (the engine, the seeds, the judge)

DRAW = {}  # --set NAME=value: constants of the family's draw overridden for this process
MODES = ("sound", "dense", "no-local", "stale-ck", "state-bf16", "no-rope", "zero-carry",
         "decay-l0", "w-int8", "long", "long-bf16", "shares")


def break_program(mode: str, family):
    """Make the program wrong in the one way `mode` names; returns the
    family the executor is built over."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.models import granite
    from xllm_service_tpu.ops import lightning as lightning_ops
    from xllm_service_tpu.ops import sparse_attention as sparse_ops

    for name, value in DRAW.items():  # --set: another draw of the family (a study, never a cell)
        setattr(family, name, type(getattr(family, name))(value))
    if mode in ("dense", "no-local"):
        sound = sparse_ops.selection_of
        # (dense_len is also the decode launch's table width: max_seq_len, not "infinity")
        change = {"dense": dict(dense_len=1 << 16), "no-local": dict(local_blocks=1)}[mode]
        granite.sparse_ops.selection_of = lambda cfg: sound(cfg)._replace(**change)
    if mode == "stale-ck":
        sparse_ops.write_compressed = lambda CK, *rest: CK
    if mode in ("state-bf16", "long-bf16"):
        # (reduce_precision: XLA elides a float32 -> bfloat16 -> float32 convert pair)
        rounded = lambda S: jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        sound_dec, sound_chunk = lightning_ops.decode_update, lightning_ops.chunk_update

        def decode(S, *a, **kw):
            o, S = sound_dec(S, *a, **kw)
            return o, rounded(S)

        def chunk(S, *a, **kw):
            o, S = sound_chunk(S, *a, **kw)
            return o, rounded(S)

        lightning_ops.decode_update, lightning_ops.chunk_update = decode, chunk
    if mode == "no-rope":
        granite.rope_ops.apply_rope = lambda x, positions, theta: x
    if mode == "zero-carry":
        sound = lightning_ops.chunk_update
        lightning_ops.chunk_update = lambda S, layer, slots, start, *rest: sound(
            S, layer, slots, jnp.zeros_like(start), *rest)
    if mode == "decay-l0":
        granite.lightning_layer_ids = lambda cfg: (0,) * cfg.num_state_layers
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
        tokens = jnp.asarray(rng.integers(0, config["vocab_size"], size=1024), jnp.int32)
        rows = np.asarray(jax.jit(lambda w, t: family.branch_shares(w, config, t))(weights, tokens))
        print(json.dumps({
            "mode": "shares", "seed": seed, "platform": jax.devices()[0].platform,
            "rms_by_layer": {k: [round(float(v), 4) for v in rows[:, i]]
                             for i, k in enumerate(("stream", "mixer", "mlp"))},
        }), flush=True)
        del weights
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mode", choices=MODES, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--long-prompt", type=int, default=24576)
    ap.add_argument("--long-tokens", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", nargs="*", default=[], metavar="NAME=value")
    args = ap.parse_args()
    DRAW.update({kv.split("=")[0]: float(kv.split("=")[1]) for kv in args.set})
    if args.mode == "shares":
        return shares(args.config, args.seeds, args.rehearse)
    runner.break_program = break_program
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
