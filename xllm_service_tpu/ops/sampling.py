"""Batched token sampling with per-request parameters.

Engine-tier op (reference delegates sampling to the absent CUDA engine;
logprob wire shape constrained by proto/xllm_rpc_service.proto:85-113).

All functions are jit-safe over a fixed batch R: every request carries its
own (temperature, top_k, top_p, greedy-flag, seed) so one compiled step
serves any mixture — no recompilation on batch composition changes.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from xllm_service_tpu.obs.spans import region

NEG_INF = -1e30


@dataclass
class SamplingParams:
    """Host-side per-request sampling spec (OpenAI-compatible surface)."""

    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    # min_p (vLLM semantics): drop tokens whose probability is below
    # min_p * max-probability. 0 disables. Applied with top-k/top-p.
    min_p: float = 0.0
    seed: int = 0
    logprobs: bool = False
    top_logprobs: int = 0
    max_new_tokens: int = 512
    stop_token_ids: tuple = ()
    ignore_eos: bool = False
    # OpenAI penalties over GENERATED tokens (vLLM semantics — the prompt
    # is not penalized): presence subtracts a flat amount from every
    # already-sampled token's logit; frequency subtracts per occurrence.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # OpenAI logit_bias: ((token_id, bias), ...) pairs added to the
    # token's logit before filtering/sampling; bias in [-100, 100]
    # (-100 effectively bans, +100 effectively forces).
    logit_bias: tuple = ()

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def apply_top_k_top_p(
    logits: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray,
    min_p: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Combined per-row top-k + nucleus + min-p filtering with ONE
    descending argsort (the sort over V dominates sampling cost at vocab
    ~128K). top_k<=0, top_p>=1, and min_p<=0 disable their respective
    filters; the argmax is always kept."""
    R, vocab = logits.shape
    order = jnp.argsort(logits, axis=-1)[:, ::-1]  # descending
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    ranks = jnp.arange(vocab, dtype=jnp.int32)[None, :]

    k = jnp.where(top_k <= 0, vocab, jnp.minimum(top_k, vocab))
    keep_k = ranks < k[:, None]

    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Token i is kept if the cumulative mass *before* it is < top_p.
    keep_p = (cum - probs) < top_p[:, None]

    keep_sorted = keep_k & keep_p
    if min_p is not None:
        # vLLM semantics: prob >= min_p * max-prob (column 0 after the
        # descending sort holds the max).
        floor = jnp.where(min_p > 0, min_p, 0.0)[:, None] * probs[:, :1]
        keep_sorted = keep_sorted & (probs >= floor)
    keep_sorted = keep_sorted.at[:, 0].set(True)
    keep = jnp.zeros_like(keep_sorted).at[jnp.arange(R)[:, None], order].set(
        keep_sorted
    )
    return jnp.where(keep, logits, NEG_INF)


def apply_penalties(
    logits: jnp.ndarray,  # [R, V] float32
    counts: jnp.ndarray,  # [R, V] int32 — generated-token occurrence counts
    presence: jnp.ndarray,  # [R] float32
    frequency: jnp.ndarray,  # [R] float32
) -> jnp.ndarray:
    """OpenAI presence/frequency penalties over generated tokens. The
    count update (scatter-add of the sampled token) lives with the caller
    so the counts array can be donated through the decode step. Skipped at
    runtime (lax.cond) when no live row has a penalty — the [R, V]
    elementwise pass is real HBM traffic at V~128K."""
    active = (presence != 0.0) | (frequency != 0.0)

    def apply(x):
        cf = counts.astype(jnp.float32)
        seen = (counts > 0).astype(jnp.float32)
        return x - presence[:, None] * seen - frequency[:, None] * cf

    return jax.lax.cond(jnp.any(active), apply, lambda x: x, logits)


@region("sample")
def sample_tokens(
    logits: jnp.ndarray,  # [R, V] float32
    temperature: jnp.ndarray,  # [R] float32; <=0 means greedy
    top_k: jnp.ndarray,  # [R] int32; 0 disables
    top_p: jnp.ndarray,  # [R] float32; 1.0 disables
    step_keys: jnp.ndarray,  # [R, 2] uint32 PRNG keys (pre-folded per step)
    counts: jnp.ndarray | None = None,  # [R, V] int32 generated-token counts
    presence: jnp.ndarray | None = None,  # [R] float32
    frequency: jnp.ndarray | None = None,  # [R] float32
    bias_ids: jnp.ndarray | None = None,  # [R, K] int32 (pad: id 0, bias 0)
    bias_vals: jnp.ndarray | None = None,  # [R, K] float32
    allowed: jnp.ndarray | None = None,  # [R, V] bool (guided decoding)
    min_p: jnp.ndarray | None = None,  # [R] float32; 0 disables
):
    """Returns (token_ids [R], logprob_of_chosen [R], logprobs [R, V])."""
    logits = logits.astype(jnp.float32)
    if bias_ids is not None and bias_vals is not None:
        # OpenAI logit_bias: sparse per-request add BEFORE penalties /
        # filtering / softmax, so greedy, sampling, and reported logprobs
        # all see the biased distribution. Padding rows carry (0, 0.0) —
        # adding zero to token 0 is a no-op.
        R = logits.shape[0]
        logits = logits.at[
            jnp.arange(R, dtype=jnp.int32)[:, None], bias_ids
        ].add(bias_vals)
    if counts is not None and presence is not None and frequency is not None:
        logits = apply_penalties(logits, counts, presence, frequency)
    if allowed is not None:
        # Guided decoding (JSON mode): hard-mask disallowed tokens LAST so
        # no bias or penalty can resurrect them; reported logprobs are
        # over the allowed set.
        logits = jnp.where(allowed, logits, NEG_INF)
    logprobs_full = jax.nn.log_softmax(logits, axis=-1)

    greedy_ids = jnp.argmax(logits, axis=-1)

    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]
    # The argsort over V (~128K) dominates sampling cost; skip it at
    # runtime (lax.cond — real control flow on TPU) when NO live row has a
    # filter enabled: greedy rows and filters-off rows don't need it.
    vocab = logits.shape[-1]
    needs_filter = (temperature > 0) & (
        ((top_k > 0) & (top_k < vocab))
        | (top_p < 1.0)
        | ((min_p > 0) if min_p is not None else False)
    )
    # Under a vocab-sharded head the filter's collectives (the sort) come
    # AFTER the argmax's and the log-softmax's, by a data dependence that
    # holds for every finite row: XLA:CPU runs independent thunks of one
    # program in any order on each device, and two devices that enter
    # different collectives first wait for each other until the
    # rendezvous aborts the process (40 s; the tp>1 engine tests). The
    # chip runs one stream, in this order anyway.
    ordered = (greedy_ids[0] >= 0) & (logprobs_full[0, 0] <= 0.0)
    scaled = jax.lax.cond(
        jnp.any(needs_filter) & ordered,
        lambda x: apply_top_k_top_p(x, top_k, top_p, min_p),
        lambda x: x,
        scaled,
    )

    def sample_one(key, row):
        return jax.random.categorical(jax.random.wrap_key_data(key), row)

    sampled_ids = jax.vmap(sample_one)(step_keys, scaled)

    token_ids = jnp.where(temperature > 0, sampled_ids, greedy_ids).astype(jnp.int32)
    chosen_logprob = jnp.take_along_axis(
        logprobs_full, token_ids[:, None], axis=-1
    )[:, 0]
    return token_ids, chosen_logprob, logprobs_full


@region("sample")
def speculative_sample(
    logits: jnp.ndarray,  # [R, S, V] — verify-pass logits, position-major
    drafts: jnp.ndarray,  # [R, S-1] int32 — proposed tokens d_1..d_k
    temperature: jnp.ndarray,  # [R]
    top_k: jnp.ndarray,  # [R]
    top_p: jnp.ndarray,  # [R]
    step_keys: jnp.ndarray,  # [R, S, 2] — per-position keys (step_base + j)
    limits: jnp.ndarray,  # [R] int32 — max tokens this row may emit (<= S)
    active: jnp.ndarray,  # [R] bool
    counts: jnp.ndarray | None = None,  # [R, V] int32 (donated by caller)
    presence: jnp.ndarray | None = None,  # [R]
    frequency: jnp.ndarray | None = None,  # [R]
    bias_ids: jnp.ndarray | None = None,  # [R, K]
    bias_vals: jnp.ndarray | None = None,  # [R, K]
    allowed: jnp.ndarray | None = None,  # [R, S, V] bool per-position masks
    min_p: jnp.ndarray | None = None,  # [R]
):
    """Speculative acceptance for point-mass (n-gram / prompt-lookup) drafts.

    Position j's logits condition on [x_0, d_1..d_j] (the verify pass fed
    the last accepted token then the drafts). Sample t_j ~ p_j with the SAME
    per-step key schedule the sequential decode path would use at step
    base+j, and keep emitting while t_j equals the draft. This is *exactly*
    sequential sampling, not an approximation: accepting d_j with
    probability p_j(d_j) and otherwise emitting a sample from
    p_j(x | x != d_j) is the same joint law as emitting t_j ~ p_j outright —
    the standard speculative rejection rule collapses to equality-coupling
    when the draft distribution is a point mass. Consequently the
    speculative engine reproduces the non-speculative token stream
    bit-for-bit under identical seeds (tests/test_speculative.py asserts
    this), while emitting up to S tokens per verify step.

    Penalty exactness: the scan threads `counts` through the positions, so
    each emitted token penalizes later positions inside the same verify
    step just as it would across sequential decode steps.

    Returns (tokens [R, S], logprobs [R, S], n_emit [R], counts').
    Rows emit their first n_emit tokens; the rest is garbage.
    """
    R, S, V = logits.shape
    logits = logits.astype(jnp.float32)
    # pad drafts with an impossible token so position S-1 never "accepts"
    drafts_p = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.full((R, 1), -1, jnp.int32)], axis=1
    )
    have_counts = counts is not None
    if not have_counts:
        counts = jnp.zeros((R, 1), jnp.int32)  # dummy carry

    have_mask = allowed is not None
    if not have_mask:
        allowed = jnp.zeros((R, S, 1), bool)  # dummy scan input

    def body(carry, xs):
        cnts, going = carry
        lg, keys_j, d_j, j, allow_j = xs
        tok, lp, _ = sample_tokens(
            lg, temperature, top_k, top_p, keys_j,
            counts=cnts if have_counts else None,
            presence=presence, frequency=frequency,
            bias_ids=bias_ids, bias_vals=bias_vals,
            allowed=allow_j if have_mask else None,
            min_p=min_p,
        )
        emit = going & (j < limits)
        if have_counts:
            cnts = cnts.at[jnp.arange(R), tok].add(emit.astype(jnp.int32))
        going = emit & (tok == d_j)
        return (cnts, going), (tok, lp, emit)

    (counts, _), (toks, lps, emits) = jax.lax.scan(
        body,
        (counts, active),
        (
            jnp.swapaxes(logits, 0, 1),  # [S, R, V]
            jnp.swapaxes(step_keys, 0, 1),  # [S, R, 2]
            drafts_p.T,  # [S, R]
            jnp.arange(S, dtype=jnp.int32),
            jnp.swapaxes(allowed, 0, 1),  # [S, R, V] (or dummy)
        ),
    )
    n_emit = jnp.sum(emits.astype(jnp.int32), axis=0)  # [R]
    return toks.T, lps.T, n_emit, counts


def pack_logit_bias(rows, n_rows: int):
    """Pack per-row ((token_id, bias), ...) tuples into the sparse
    [n_rows, K] (ids, vals) arrays sample_tokens takes; K is pow2-bucketed
    to bound compile count, padding entries are (0, 0.0) — adding zero to
    token 0 is a no-op. Returns (None, None) when no row has bias."""
    import numpy as np

    if not any(rows):
        return None, None
    K = 1
    while K < max(len(r) for r in rows if r):
        K *= 2
    ids = np.zeros((n_rows, K), np.int32)
    vals = np.zeros((n_rows, K), np.float32)
    for i, r in enumerate(rows):
        for j, (tid, bv) in enumerate(r[:K] if r else ()):
            ids[i, j] = tid
            vals[i, j] = bv
    return ids, vals


@region("sample")
def make_step_keys(base_seeds: jnp.ndarray, steps: jnp.ndarray) -> jnp.ndarray:
    """Per-request keys folded with the generation step index: [R] -> [R, 2].

    `steps` may be a scalar (all rows at the same step) or a [R] array
    (continuous-batching: every slot at its own step). This is the ONLY
    seed-folding definition — executor prefill and decode both call it, so
    prefill and decode RNG streams can never diverge (PD-disagg resume
    depends on that)."""

    def one(seed, st):
        k = jax.random.key(seed)
        k = jax.random.fold_in(k, st)
        return jax.random.key_data(k)

    steps = jnp.broadcast_to(jnp.asarray(steps, jnp.int32), base_seeds.shape)
    return jax.vmap(one)(base_seeds, steps)
