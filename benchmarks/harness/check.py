"""The comparison that decides `correct`: a seeded sample of prompts is
served greedily with logprobs through the master, outside the window, and
held against the plain float32 reference of the configuration's family
(benchmarks/families/) run over the benchmark's own weights, by the
family's LIMITS. Nothing here knows an architecture: prompts are token
ids under `vocab_size`, and a sliced vocabulary is a smaller one."""

from __future__ import annotations

import time
from typing import Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import stack as stack_mod

CHECK_PROMPTS = 8  # prompts of 1 to 3 prefill chunks of token ids
CHECK_TOKENS = 64  # greedy tokens served for each: 512 numbers compared
CHECK_PAD = 1024  # reference sequence length (>= 3 chunks of 256 + CHECK_TOKENS)


def serve_sample(stack, seed: int) -> List[dict]:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    # whole prefill chunks (1 to 3 of them, or as many as fit): a ragged
    # tail would make the engine compile one more program per tail shape
    chunk = int(stack.engine_cfg.max_prefill_tokens)
    most = max(1, min(3, (stack.engine_cfg.max_seq_len - CHECK_TOKENS - 1) // chunk))
    prompts = [
        rng.integers(0, stack.config["vocab_size"], size=chunk * int(k)).tolist()
        for k in rng.integers(1, most + 1, size=CHECK_PROMPTS)
    ]
    return stack_mod.greedy_sample(stack, prompts, CHECK_TOKENS)


def judge(stack, samples: List[dict]) -> dict:
    """The family's reference logits for every served token, then compare."""
    import jax
    import jax.numpy as jnp

    pad = max([CHECK_PAD] + [len(s["prompt"]) + CHECK_TOKENS for s in samples])
    fwd = jax.jit(lambda w, t, i: stack.family.forward_logits(w, stack.config, t, i))
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
    return compare(samples, logits, stack.family.LIMITS)


def compare(samples: Sequence[Mapping], ref_logits: Sequence[np.ndarray],
            limits: Mapping) -> Dict:
    """samples[i]: {"served_ids": [n], "served_logprobs": [n]}; ref_logits[i]
    [n, V] float32 from forward_logits at the positions that predicted
    them; limits: the family's LIMITS. Returns the numbers compared, their
    limits, and the verdict."""
    sq, n, deficit, exact, lp_max = 0.0, 0, 0.0, 0, 0.0
    for s, logits in zip(samples, ref_logits):
        ids = np.asarray(s["served_ids"], np.int64)
        lps = np.asarray(s["served_logprobs"], np.float64)
        logits = np.asarray(logits, np.float64)
        if logits.shape[0] != len(ids) or len(lps) != len(ids) or not len(ids):
            return {"ok": False, "why": "served tokens, logprobs and reference rows differ in number"}
        if not np.isfinite(logits).all() or not np.isfinite(lps).all():
            return {"ok": False, "why": "non-finite logits or logprobs"}
        rows = np.arange(len(ids))
        ref_lp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
        err = ref_lp[rows, ids] - lps
        sq += float((err ** 2).sum())
        lp_max = max(lp_max, float(np.abs(err).max()))
        n += len(ids)
        deficit = max(deficit, float((logits.max(-1) - logits[rows, ids]).max()))
        exact += int((logits.argmax(-1) == ids).sum())
    mse = sq / n
    return {
        "ok": mse <= limits["logprob_mse"] and deficit <= limits["deficit_max"],
        "logprob_mse": mse, "logprob_mse_limit": limits["logprob_mse"],
        "deficit_max": deficit, "deficit_max_limit": limits["deficit_max"],
        "logprob_rms": float(np.sqrt(mse)), "logprob_abs_max": lp_max,
        "argmax_exact": exact, "tokens": n,
    }


def check_correct(stack, seed: int) -> dict:
    t0 = time.monotonic()
    samples = serve_sample(stack, seed)
    t1 = time.monotonic()
    res = judge(stack, samples)
    res["serve_s"], res["reference_s"] = t1 - t0, time.monotonic() - t1
    return res
