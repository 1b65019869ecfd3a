"""The comparison that decides `correct`: a seeded sample of prompts is
served greedily with logprobs through the master, outside the window, and
held against the plain float32 reference (reference.py) run over the
benchmark's own weights."""

from __future__ import annotations

import time
from typing import List, Mapping

import numpy as np

from benchmarks.harness import reference, stack as stack_mod

CHECK_PROMPTS = 8  # prompts of 1 to 3 prefill chunks of token ids
CHECK_TOKENS = 64  # greedy tokens served for each: 512 numbers compared
CHECK_PAD = 1024  # reference sequence length (>= 3 chunks of 256 + CHECK_TOKENS)


def serve_sample(stack, model: Mapping, seed: int) -> List[dict]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    # whole prefill chunks (1 to 3 of them, or as many as fit): a ragged
    # tail would make the engine compile one more program per tail shape
    chunk = int(stack.engine_cfg.max_prefill_tokens)
    most = max(1, min(3, (stack.engine_cfg.max_seq_len - CHECK_TOKENS - 1) // chunk))
    prompts = [
        rng.integers(0, model["vocab_size"], size=chunk * int(k)).tolist()
        for k in rng.integers(1, most + 1, size=CHECK_PROMPTS)
    ]
    return stack_mod.greedy_sample(stack, prompts, CHECK_TOKENS)


def judge(stack, model: Mapping, samples: List[dict]) -> dict:
    """Reference logits for every served token, then reference.compare."""
    import jax
    import jax.numpy as jnp

    pad = max([CHECK_PAD] + [len(s["prompt"]) + CHECK_TOKENS for s in samples])
    fwd = jax.jit(lambda w, t, i: reference.forward_logits(w, model, t, i))
    logits = []
    with stack.executor.mesh:
        for s in samples:
            n_p, n_o = len(s["prompt"]), len(s["served_ids"])
            if n_o != CHECK_TOKENS:
                return {"ok": False, "why": f"served {n_o} tokens, asked {CHECK_TOKENS}"}
            toks = np.zeros((pad,), np.int32)
            seq = s["prompt"] + s["served_ids"]
            toks[: len(seq)] = seq
            idx = np.arange(n_p - 1, n_p - 1 + n_o, dtype=np.int32)
            logits.append(np.asarray(fwd(stack.weights(), jnp.asarray(toks), jnp.asarray(idx))))
    return reference.compare(samples, logits)


def check_correct(stack, model: Mapping, seed: int) -> dict:
    t0 = time.monotonic()
    samples = serve_sample(stack, model, seed)
    t1 = time.monotonic()
    res = judge(stack, model, samples)
    res["serve_s"], res["reference_s"] = t1 - t0, time.monotonic() - t1
    return res
