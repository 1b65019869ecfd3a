#!/usr/bin/env python3
"""What a draw of the MiniCPM-SALA family gives the check to read, before
chip time is spent on controls (after study_solar.py): the plain reference
alone, over ONE sequence, no engine.

A top-k among near-equal block scores flips on bfloat16 noise, and the
check then reads the draw's ties. For each sparse layer, over the last
--rows query rows of a --tokens-token sequence of the seed's weights, this
prints

  * `flip_rows`: the share of (row, KV head) whose selected block set
    changes when the layer's input is perturbed by --noise (relative,
    Gaussian: what a bfloat16 pipeline's stream is off by), and
    `flip_blocks` the mean number of blocks that change;
  * `flip_effect`: |attention under the perturbed selection - attention
    under the exact one| / |attention|, over the rows that flipped (the
    query, keys and values exact: the flips' own cost);
  * `dense_effect`: the same for the dense launch in the selected rows'
    place (what the `dense` control changes);
  * `mass_selected`: the share of the DENSE softmax's mass that lies in
    the selected blocks (1.0: selection changes nothing; the `dense`
    control would not fail), and `mass_margin` the share in the
    weakest-scored chosen block;
  * `score_std`: the std of stage 1's scores over the visible keys.

A draw is sound when flip_effect x flip_rows is far under dense_effect.
`--set NAME=value` overrides a module constant of the family (EMBED_COMMON,
QK_GAIN, the OUT gains). Runs anywhere JAX does (the chip for the
published widths: one 12k sequence is 30 s; `--config
rehearse-minicpm-sala-tiny --tokens 400` on the CPU). Not a measurement of
the program: no device number."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def study(config_name: str, seed: int, tokens: int, rows: int, noise: float, sets: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import family as family_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        m = json.load(f)
    fam = family_mod.load(m)
    for k, v in sets.items():
        if not hasattr(fam, k):
            raise SystemExit(f"study: the family has no constant {k}")
        setattr(fam, k, type(getattr(fam, k))(v))
    dtype = jnp.bfloat16 if m["engine"]["dtype"] == "bfloat16" else jnp.float32
    weights = jax.jit(lambda k: fam.make_weights(m, k, dtype))(family_mod.seed_key(seed))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], size=tokens), jnp.int32)
    sp = m["sparse_config"]
    Hq, Hkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    g, B, eps = Hq // Hkv, sp["block_size"], float(m["rms_norm_eps"])
    T, NB, scale = tokens, -(-tokens // sp["block_size"]), D ** -0.5
    f32 = jnp.float32
    first = T - rows
    pos = jnp.arange(first, T)
    cols = jnp.arange(T)
    held = fam.held_layers(m)

    def layer_report(u, lp, key):
        def qkc(u_):
            q = fam._rms_norm((u_[first:] @ lp["wq"]).reshape(rows, Hkv, g, D), lp["q_norm"], eps)
            k = fam._rms_norm((u_ @ lp["wk"]).reshape(T, Hkv, D), lp["k_norm"], eps)
            return q, k, fam.compressed_keys(k, sp)

        q, k, c = qkc(u)
        v = (u @ lp["wv"]).reshape(T, Hkv, D)
        qn, _, cn = qkc(u * (1.0 + noise * jax.random.normal(key, u.shape, f32)))
        causal = cols[None, :] <= pos[:, None]
        out = {}
        for h in range(Hkv):
            sel = fam.selected_blocks(q[:, h], c[:, h], pos, sp, NB, scale)
            sel_n = fam.selected_blocks(qn[:, h], cn[:, h], pos, sp, NB, scale)
            s = jnp.einsum("qgd,kd->gqk", q[:, h], k[:, h]) * scale
            s = jnp.where(causal[None], s, -jnp.inf)

            def attend(mask):
                p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
                return jnp.einsum("gqk,kd->qgd", p, v[:, h])

            picked = lambda chosen: jnp.take(chosen, cols // B, axis=1) & causal
            o, o_n, o_d = attend(picked(sel)), attend(picked(sel_n)), attend(causal)
            norm = lambda x: jnp.sqrt(jnp.sum(x * x, axis=(1, 2)))
            changed = jnp.sum(sel != sel_n, axis=1) // 2
            flipped = changed > 0
            dense_p = jax.nn.softmax(s, axis=-1).mean(axis=0)  # [rows, T]
            mass = jnp.sum(jnp.where(picked(sel), dense_p, 0.0), axis=1)
            s1 = jnp.einsum("qgd,jd->gqj", q[:, h], c[:, h]) * scale
            out[h] = {
                "flip_rows": flipped.mean(), "flip_blocks": changed.mean(),
                "flip_effect": jnp.where(flipped, norm(o_n - o) / norm(o), 0.0).sum()
                / jnp.maximum(flipped.sum(), 1),
                "dense_effect": (norm(o_d - o) / norm(o)).mean(),
                "mass_selected": mass.mean(), "mass_selected_min": mass.min(),
                "score_std": jnp.std(s1[:, -1]),
            }
        return out

    with jax.default_matmul_precision("highest"):
        x = fam.embed(weights, m, toks)
        for l, (published, kind) in enumerate(held):
            if kind == "minicpm4":
                a = sum(1 for _, k in held[:l] if k == kind)
                lp = {k: w[a].astype(f32) for k, w in weights["attn"].items()}
                u = fam._rms_norm(x, weights["layers"]["attn_norm"][l].astype(f32), eps)
                rep = jax.jit(layer_report)(u, lp, jax.random.key(l))
                print(json.dumps({"layer": published, "seed": seed, "tokens": T, "noise": noise,
                                  "set": sets, "by_kv_head": {str(h): {k: round(float(v), 4) for k, v in r.items()}
                                                          for h, r in rep.items()}}), flush=True)
            # (the weights as an ARGUMENT: closed over they would be constants of the program)
            x = jax.jit(lambda x_, w_, l=l: fam.layer_terms(x_, w_, l, m)[2])(x, weights)


def logits_study(config_name: str, seed: int, tokens: int, last: int, sets: dict):
    """What the two precision-or-path controls would move, by the reference
    alone: logprob MSE, over the greedy tokens of the last `last` positions
    of one `tokens`-token sequence, between the exact forward and (a) the
    lightning state rounded to bfloat16 after every token, (b) the dense
    launch in every row's place. A sound bfloat16 program reads about 1e-4
    against the exact forward (the chip's sound runs): (a) and (b) have to
    stand well over that for the controls to fail the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import family as family_mod

    with open(os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")) as f:
        m = json.load(f)
    fam = family_mod.load(m)
    for k, v in sets.items():
        setattr(fam, k, type(getattr(fam, k))(v))
    dtype = jnp.bfloat16 if m["engine"]["dtype"] == "bfloat16" else jnp.float32
    weights = jax.jit(lambda k: fam.make_weights(m, k, dtype))(family_mod.seed_key(seed))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], size=tokens), jnp.int32)
    idx = jnp.arange(tokens - last, tokens)
    fwd = lambda **how: jax.jit(lambda w, t: jax.nn.log_softmax(
        fam.forward_logits(w, m, t, idx, **how), axis=-1))(weights, toks)
    exact = fwd()
    ids = jnp.argmax(exact, axis=-1)
    take = lambda lp: jnp.take_along_axis(lp, ids[:, None], axis=1)[:, 0]
    out = {"seed": seed, "tokens": tokens, "set": sets, "top_logprob_mean": float(take(exact).mean())}
    for name, how in (("state_bf16", {"state_dtype": jnp.bfloat16}), ("dense", {"always_dense": True})):
        out["mse_" + name] = float(jnp.mean((take(fwd(**how)) - take(exact)) ** 2))
    rows = np.asarray(jax.jit(lambda w, t: fam.branch_shares(w, m, t))(weights, toks[:1024]))
    out["rms"] = {k: [round(float(v), 3) for v in rows[:, i]] for i, k in enumerate(("stream", "mixer", "mlp"))}
    print(json.dumps(out), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="minicpm-sala")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--tokens", type=int, default=12288)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--noise", type=float, default=0.005)
    ap.add_argument("--set", nargs="*", default=[], metavar="NAME=value")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--logits", type=int, default=0, metavar="LAST",
                    help="the controls' study instead: logprob MSE over the last LAST positions")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sets = {kv.split("=")[0]: float(kv.split("=")[1]) for kv in args.set}
    for seed in args.seeds:
        if args.logits:
            logits_study(args.config, seed, args.tokens, args.logits, sets)
        else:
            study(args.config, seed, args.tokens, args.rows, args.noise, sets)
    return 0


if __name__ == "__main__":
    sys.exit(main())
