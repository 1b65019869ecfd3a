#!/usr/bin/env python3
"""How many of the 256 experts a 512-token chunk (and a decode step of 8
rows) touches under the Laguna family's draw, by a gain on both kinds'
output projections (after study_minicpm_sala.py: no engine, the family's
own reference at reduced widths, float32, on the CPU in a minute): run it
before spending chip time on a new draw of families/laguna.py. With a gain
of 4 (PR 60's first draw) the mean of a layer's attention output is most
of a normed row, every token's router scores share one offset, and a chunk
touches 40-90 experts in the later layers; with the plain draw 255.

    python3 benchmarks/tests/study_laguna.py 4,2,1 [tokens]
"""
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax, jax.numpy as jnp, numpy as np
from benchmarks.harness import family as F
m = json.load(open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs", "laguna-xs.2.json")))
m = copy.deepcopy(m)
m.update(hidden_size=256, head_dim=32, intermediate_size=512, moe_intermediate_size=64,
         shared_expert_intermediate_size=64, vocab_size=4096, num_key_value_heads=4)
m["num_attention_heads_per_layer"] = [12 if h == 48 else 16 for h in m["num_attention_heads_per_layer"]]
fam = F.load(m)
T = int(sys.argv[2]) if len(sys.argv) > 2 else 1536
for gain in [float(g) for g in sys.argv[1].split(",")]:
    fam.GAINS[("attn", "wo")] = gain; fam.GAINS[("attn_w", "wo")] = gain
    w = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(F.seed_key(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 4096, T), jnp.int32)
    f32 = jnp.float32
    eps = float(m["rms_norm_eps"]); kinds, kd = fam.layer_types(m), fam.dense_layers(m)
    of = {"attention": 0, "window": 0}
    x = w["embed"][toks]
    out = []
    with jax.default_matmul_precision("highest"):
        for l, kind in enumerate(kinds):
            st = w["attn_w" if kind == "window" else "attn"]
            lp = {k: v[of[kind]] for k, v in st.items()}; of[kind] += 1
            u = fam._rms_norm(x, w["layers"]["attn_norm"][l], eps)
            a = fam.attention(u, lp, m, kind)
            x = x + a
            u = fam._rms_norm(x, w["layers"]["mlp_norm"][l], eps)
            if l < kd:
                x = x + fam._swiglu(u, *(w["dense_layers"][k][l] for k in ("w_gate", "w_up", "w_down")))
            else:
                r = fam.route(u[-512:], w["layers"]["router"][l - kd], m)
                touched = int((np.asarray(r) > 0).any(0).sum())
                dec = [int((np.asarray(r[i::64][:8]) > 0).any(0).sum()) for i in range(4)]  # 8 rows
                mean = u[-512:].mean(0); common = float(jnp.linalg.norm(mean) / jnp.sqrt(jnp.mean(jnp.sum(u[-512:]**2, -1))))
                out.append((l, touched, dec, round(common, 3), round(float(jnp.sqrt(jnp.mean(a**2))), 3), round(float(jnp.sqrt(jnp.mean(x**2))), 3)))
                x = x + fam.expert_layer(u, w["layers"], l - kd, m)
    print("gain", gain, "T", T, "(layer, touched by the last 512 rows, by 8 rows x4, |mean u|/|u|, rms attn out, rms stream):", out)
