#!/usr/bin/env python3
"""A draw of families/solar.py read on the CPU in half a minute (PR 46's
review round): what a SOUND run and a run with the KDA state pools held in
bfloat16 (`control_solar.py --mode state-bf16`) would read in `logprob_mse`,
without an engine.

The family's float32 reference is held against the program's own dense
oracle (`models/granite.py` `forward_dense`: the recurrence, the program's
arithmetic, bfloat16 activations) on prompts of whole 512-token chunks and
64 more tokens, as harness/check.py sizes them; the fault is the same
oracle with `ops.kda.recurrent_form` rounding the delta-rule state to
bfloat16 after every decode token and at every 512-token chunk's end, and
the convolution's carried rows rounded for every decode token. Compared at
the oracle's own argmax (the continuation is random ids, not greedy: a
stand-in for the served token). The cut is `CUT` below: KDA heads of the
published 128 x 128, everything else small; 4 of 64 experts held at top 8
(half a held pair a token and layer, as the cell). It RANKS draws and does
not predict the number: for the committed draw the engine at the same cut
(`control_solar.py --rehearse`) read within 2.5x of it, and the chip at
the cell's size 2.4x to 4.3x it, the sound runs and the bfloat16 state
alike (PERF.md section 2); a draw is settled by the controls on the chip.
A study tool, not part of the yardstick: no device number.

    python3 benchmarks/tests/study_solar.py [--set V_BIAS_MEAN=8.0 SINK_LANES=0 ...] [--seeds 1 2 3]
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CUT = dict(
    hidden_size=256, num_hidden_layers=4, num_attention_heads=4, head_dim=64, num_key_value_heads=2,
    vocab_size=2048, moe_intermediate_size=128, n_routed_experts=4, n_routed_experts_published=64,
    experts_held=[0, 4], num_experts_per_tok=8, kda_gate_rank=32,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 4, "num_kv_heads": None},
)
CHUNK, TOKENS = 512, 64


def family(consts):
    spec = importlib.util.spec_from_file_location(
        "solar_study", os.path.join(ROOT, "benchmarks", "families", "solar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for k, v in consts.items():
        if not hasattr(mod, k):
            raise SystemExit(f"families/solar.py has no constant {k}")
        setattr(mod, k, v)
    return mod


def rounded_state(P: int):
    """Patch the oracle's recurrence and convolution: the state pools in
    bfloat16 from position P on (and at chunk ends before it). Returns
    the undo."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.ops import kda as kda_ops, mamba as mamba_ops

    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    sound = kda_ops.recurrent_form, mamba_ops.conv_dense

    def recurrent_form(q, k, v, g, beta):
        T, H, d = q.shape

        def step(S, t):
            i, qt, kt, vt, gt, bt = t
            S = jnp.exp(gt)[..., None] * S
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision="highest"))
            S = S + kt[..., None] * u[:, None, :]
            o = jnp.einsum("hkv,hk->hv", S, qt, precision="highest")
            return jnp.where((i >= P) | ((i + 1) % CHUNK == 0), bf(S), S), o

        S, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (
            jnp.arange(T), *(t.astype(jnp.float32) for t in (q, k, v, g, beta))))
        return o, S

    def conv_dense(x, w, b):
        K, T = w.shape[0], x.shape[0]
        hist = jnp.pad(bf(x), ((K - 1, 0), (0, 0)))  # the carried rows; the token's own row is not stored
        c = jax.nn.silu(b + sum(w[j] * hist[j:j + T] for j in range(K - 1)) + w[K - 1] * x)
        return jnp.where((jnp.arange(T) >= P)[:, None], c, sound[1](x, w, b))

    kda_ops.recurrent_form, mamba_ops.conv_dense = recurrent_form, conv_dense

    def undo():
        kda_ops.recurrent_form, mamba_ops.conv_dense = sound

    return undo


def study(consts, seeds, chunks=(1, 2)):
    """[(sound logprob_mse, state-bf16 logprob_mse)] a seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from xllm_service_tpu.models import granite

    with open(os.path.join(ROOT, "benchmarks", "configs", "rehearse-solar-tiny.json")) as f:
        m = {**json.load(f), **CUT}
    fam = family(consts)
    cfg = fam.model_config("study-solar", m)
    logp = lambda x: x - jax.scipy.special.logsumexp(x, axis=-1, keepdims=True)
    out = []
    for seed in seeds:
        w = jax.jit(lambda k: fam.make_weights(m, k, jnp.bfloat16))(jax.random.key(seed))
        rng = np.random.default_rng(seed)
        sq = np.zeros(2)
        for n in chunks:
            P = n * CHUNK
            toks = jnp.asarray(rng.integers(0, m["vocab_size"], size=P + TOKENS).astype(np.int32))
            idx = jnp.arange(P - 1, P - 1 + TOKENS)
            ref = logp(jax.jit(lambda w_, t, i: fam.forward_logits(w_, m, t, i))(w, toks, idx))
            oracle = lambda: jax.jit(lambda w_, t: granite.forward_dense(w_, cfg, t[None])[0])
            sound = logp(oracle()(w, toks)[idx].astype(jnp.float32))
            undo = rounded_state(P)
            try:
                fault = logp(oracle()(w, toks)[idx].astype(jnp.float32))
            finally:
                undo()
            at = jnp.argmax(sound, -1)[:, None]
            pick = lambda a: jnp.take_along_axis(a, at, 1)[:, 0]
            sq += [float(jnp.sum((pick(x) - pick(ref)) ** 2)) for x in (sound, fault)]
        out.append(tuple(sq / (len(chunks) * TOKENS)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--set", nargs="*", default=[], help="NAME=value constants of families/solar.py")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    args = ap.parse_args()
    consts = {k: ast.literal_eval(v) for k, v in (kv.split("=", 1) for kv in args.set)}
    rows = study(consts, args.seeds)
    for seed, (s, f) in zip(args.seeds, rows):
        print(json.dumps({"seed": seed, "sound": s, "state_bf16": f, "ratio": f / s}), flush=True)
    sound, fault = [r[0] for r in rows], [r[1] for r in rows]
    print(json.dumps({"summary": consts, "sound_max": max(sound), "state_bf16_min": min(fault),
                      "apart": min(fault) / max(sound)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
