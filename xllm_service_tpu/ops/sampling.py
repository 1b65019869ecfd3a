"""Batched token sampling with per-request parameters.

Engine-tier op (reference delegates sampling to the absent CUDA engine;
logprob wire shape constrained by proto/xllm_rpc_service.proto:85-113).

All functions are jit-safe over a fixed batch R: every request carries its
own (temperature, top_k, top_p, greedy-flag, seed) so one compiled step
serves any mixture — no recompilation on batch composition changes.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

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


def _keep_top_k_top_p(
    logits: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray,
    min_p: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The [R, V] bool mask of tokens that survive the combined per-row
    top-k + nucleus + min-p filter, from ONE descending argsort (the sort
    over V dominates sampling cost at vocab ~128K). top_k<=0, top_p>=1,
    and min_p<=0 disable their respective filters; the argmax is always
    kept."""
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
    return jnp.zeros_like(keep_sorted).at[jnp.arange(R)[:, None], order].set(
        keep_sorted
    )


def apply_top_k_top_p(
    logits: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray,
    min_p: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """`logits` with every token the filter drops at NEG_INF."""
    keep = _keep_top_k_top_p(logits, top_k, top_p, min_p)
    return jnp.where(keep, logits, NEG_INF)


def apply_penalties(
    logits: jnp.ndarray,  # [R, V] float32
    counts: jnp.ndarray,  # [R, V] int32 — generated-token occurrence counts
    presence: jnp.ndarray,  # [R] float32
    frequency: jnp.ndarray,  # [R] float32
) -> jnp.ndarray:
    """OpenAI presence/frequency penalties over generated tokens. The
    count update (scatter-add of the sampled token) lives with the caller
    so the counts array can be donated through the decode step. A row
    whose penalties are both zero comes back bit for bit; the sampler
    hands over only the blocks of rows that hold a penalized one
    (sample_tokens)."""
    cf = counts.astype(jnp.float32)
    seen = (counts > 0).astype(jnp.float32)
    return logits - presence[:, None] * seen - frequency[:, None] * cf


# Consecutive slots the sampler works on at a time. Read on the chip
# (PERF.md section 6, PR 48): at 8 (one float32 sublane tile) every pass
# over a block's logits runs at under half the memory's speed, at 32 a
# full batch costs what the whole-batch form cost; a block is also the
# grain of what dead slots cost.
BLOCK_ROWS = 32


def _max_and_logsumexp(x: jnp.ndarray):
    """(argmax [B], max [B], log(sum(exp(x - max))) [B]) of [B, V] rows:
    what a greedy id and any token's logprob need, as reductions that
    write nothing of the rows' width."""
    m = jnp.max(x, axis=-1)
    log_z = jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
    return jnp.argmax(x, axis=-1).astype(jnp.int32), m, log_z


def _rows(a: jnp.ndarray, at, B: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice_in_dim(a, at, B)


def _altered(B: int, at, logits, bias, pen, allowed) -> jnp.ndarray:
    """Rows `at .. at + B` of the logits as they are sampled from, sliced
    from the whole arrays: `bias` is (ids, vals) or None, `pen` (counts,
    presence, frequency) or None, `allowed` the mask or None."""
    x = _rows(logits, at, B)
    if bias is not None:
        # OpenAI logit_bias: sparse per-request add BEFORE penalties /
        # filtering / softmax, so greedy, sampling, and reported logprobs
        # all see the biased distribution. Padding entries carry (0, 0.0)
        # — adding zero to token 0 is a no-op.
        ids, vals = bias
        x = x.at[
            jnp.arange(B, dtype=jnp.int32)[:, None], _rows(ids, at, B)
        ].add(_rows(vals, at, B))
    if pen is not None:
        x = apply_penalties(x, *(_rows(a, at, B) for a in pen))
    if allowed is not None:
        # Guided decoding (JSON mode): hard-mask disallowed tokens LAST
        # so no bias or penalty can resurrect them; reported logprobs are
        # over the allowed set.
        x = jnp.where(_rows(allowed, at, B), x, NEG_INF)
    return x


# What a block does with its altered logits. Each is a jit of its own,
# taking the WHOLE arrays (`src` = logits, bias, pen, allowed) and slicing
# the block's rows itself: a [B, V] value that crosses a conditional's
# boundary is written out (and a branch that only hands it on copies
# it), so every branch of `sample_tokens` calls one of these and hands
# back [B] vectors. A jit, because the sampler's branches repeat them
# (with and without the counts, with and without a filter): each is
# traced and lowered once, not once a branch (PERF.md section 6, PR 48:
# `setup_s`). XLA inlines the calls.


@partial(jax.jit, static_argnums=0)
def _block_altered(B, at, src):
    return _altered(B, at, *src)


@partial(jax.jit, static_argnums=0)
def _block_reductions(B, at, src):
    return _max_and_logsumexp(_altered(B, at, *src))


@partial(jax.jit, static_argnums=0)
def _block_draw(B, at, src, temp, keys, keep=None):
    """(drawn ids [B], their altered logits [B]): Gumbel-argmax over the
    rows scaled by `temp` [B, 1], each row from its own key, over the
    tokens of `keep` [B, V] if there is a filter."""
    x = _altered(B, at, *src)
    scaled = x / temp
    if keep is not None:
        scaled = jnp.where(keep, scaled, NEG_INF)

    def one(key, scaled_row):
        return jax.random.categorical(
            jax.random.wrap_key_data(key), scaled_row
        )

    ids = jax.vmap(one)(keys, scaled).astype(jnp.int32)
    return ids, jnp.take_along_axis(x, ids[:, None], axis=-1)[:, 0]


def _for_blocks_with(flags: jnp.ndarray, width: int, body, out):
    """`out = body(start, mine, out)` for every block of `width`
    consecutive rows that holds a flagged row: `start` its first row,
    `mine` its rows' flags. One trip a block, each behind a conditional
    that a block with no flagged row does not enter: the trip count is
    static, as every loop's of the step programs is. A last block that
    would run past the rows starts `width` before their end instead and
    meets some rows a second time, to the same result."""
    R = flags.shape[0]
    n_blocks = -(-R // width)
    holds = jnp.any(
        jnp.pad(flags, (0, n_blocks * width - R)).reshape(n_blocks, width),
        axis=1,
    )

    def trip(i, out):
        at = jnp.minimum(i * width, R - width)
        mine = jax.lax.dynamic_slice_in_dim(flags, at, width)
        return jax.lax.cond(
            holds[i], lambda out: body(at, mine, out), lambda out: out, out
        )

    return jax.lax.fori_loop(0, n_blocks, trip, out)


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
    active: jnp.ndarray | None = None,  # [R] bool live rows; None = all
):
    """Returns (token_ids [R], logprob_of_chosen [R], None). A row that
    is not `active` returns token 0 and logprob 0.0.

    The work follows the rows that need it, decided on the device from
    the rows' own parameters (docs/ENGINE_PIPELINE.md "The sampler"):
    one loop over the blocks of BLOCK_ROWS consecutive slots that hold a
    live row, and inside a block

    - bias, penalties, mask (in that order: the mask LAST, so no bias or
      penalty resurrects a token), the greedy id and the log-sum-exp, as
      reductions over the block's logits where they lie; the counts'
      rows are read only in a block with a penalized row;
    - only where a row of the block draws: the scale, the filter's sort
      if a drawing row asks for one, and the Gumbel draw.

    A block of slots with no live row is not visited, and a batch with
    no drawing row makes no draw. Rows are independent (each draws from
    its own key), so every live row's token is the one the whole-batch
    form gave, bit for bit, and no array of the batch's [R, V] is
    written or copied. The third place of the result held the [R, V]
    log-softmax, which no program read; it stays as None for the callers
    that unpack three.

    The body is a jit of its own (`_sample_tokens`): the sampler is in
    every step program, twice in a mixed one, and a process that starts
    warm still traces each program; as a jit the sampler is traced once
    for each shape of its arguments, not once a program (`setup_s`)."""
    return _sample_tokens(
        logits, temperature, top_k, top_p, step_keys, counts, presence,
        frequency, bias_ids, bias_vals, allowed, min_p, active,
    )


@jax.jit
def _sample_tokens(
    logits, temperature, top_k, top_p, step_keys, counts, presence,
    frequency, bias_ids, bias_vals, allowed, min_p, active,
):
    R, vocab = logits.shape
    logits = logits.astype(jnp.float32)
    live = jnp.ones((R,), bool) if active is None else active
    draws = live & (temperature > 0)
    bias = (
        (bias_ids, bias_vals)
        if bias_ids is not None and bias_vals is not None else None
    )
    pen = (
        (counts, presence, frequency)
        if counts is not None and presence is not None
        and frequency is not None else None
    )
    if pen is not None:
        penalized = live & ((presence != 0.0) | (frequency != 0.0))
    needs_filter = draws & (
        ((top_k > 0) & (top_k < vocab))
        | (top_p < 1.0)
        | ((min_p > 0) if min_p is not None else False)
    )
    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    B = min(BLOCK_ROWS, R)

    def block(at, mine, out):
        def rows(a):
            return _rows(a, at, B)

        def of_altered(f, *more):
            """f(B, at, the sources of the block's altered logits,
            *more). Only a block with a penalized row reads the counts'
            rows (a row without a penalty comes through them bit for
            bit). The fence keeps each branch's reductions inside it:
            hoisted out, as XLA would, they make the branch hand over
            its [B, V] rows."""
            if pen is None:
                return f(B, at, (logits, bias, None, allowed), *more)
            fence = jax.lax.optimization_barrier
            return jax.lax.cond(
                jnp.any(rows(penalized)),
                lambda: fence(f(B, at, (logits, bias, pen, allowed), *more)),
                lambda: fence(f(B, at, (logits, bias, None, allowed), *more)),
            )

        greedy_ids, m, log_z = of_altered(_block_reductions)

        temp = rows(safe_temp)[:, None]
        keys = rows(step_keys)
        drawing = rows(draws)
        filtering = rows(needs_filter)
        # Under a vocab-sharded head the draw's collectives (the sort,
        # the Gumbel argmax) come AFTER the greedy argmax's and the
        # log-sum-exp's, by a data dependence that holds for every
        # finite row: XLA:CPU runs independent thunks of one program in
        # any order on each device, and two devices that enter different
        # collectives first wait for each other until the rendezvous
        # aborts the process (40 s; the tp>1 engine tests). The chip
        # runs one stream, in this order anyway. Every device takes the
        # same branches: the rows' flags and parameters are replicated.
        ordered = (greedy_ids[0] >= 0) & (log_z[0] >= 0.0)

        def draw():
            def filtered():
                # The one place the block's rows are written out: the
                # sort's operand (one sort in the program's text). It
                # filters only the rows that ask.
                keep = _keep_top_k_top_p(
                    of_altered(_block_altered) / temp,
                    rows(top_k), rows(top_p),
                    None if min_p is None else rows(min_p),
                ) | ~filtering[:, None]
                return of_altered(_block_draw, temp, keys, keep)

            # The argsort over V (~128K) dominates sampling cost; skipped
            # (lax.cond — real control flow on TPU) unless a drawing row
            # of THIS block has a filter enabled.
            return jax.lax.cond(
                jnp.any(filtering), filtered,
                lambda: of_altered(_block_draw, temp, keys),
            )

        sampled_ids, sampled_x = jax.lax.cond(
            jnp.any(drawing) & ordered, draw,
            lambda: (jnp.zeros((B,), jnp.int32), jnp.zeros((B,))),
        )
        # a greedy row's chosen logit is the max itself
        ids = jnp.where(drawing, sampled_ids, greedy_ids)
        lp = (jnp.where(drawing, sampled_x, m) - m) - log_z
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                old, jnp.where(mine, new, rows(old)), at, 0
            )
            for old, new in zip(out, (ids, lp))
        )

    tokens, logprob = _for_blocks_with(
        live, B, block,
        (jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.float32)),
    )
    return tokens, logprob, None


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
    Rows emit their first n_emit tokens; the rest is 0 / 0.0 (a position
    a row no longer emits at is a dead row of that position's sampler).
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
        emit = going & (j < limits)
        # a position costs what its still-emitting rows cost
        tok, lp, _ = sample_tokens(
            lg, temperature, top_k, top_p, keys_j,
            counts=cnts if have_counts else None,
            presence=presence, frequency=frequency,
            bias_ids=bias_ids, bias_vals=bias_vals,
            allowed=allow_j if have_mask else None,
            min_p=min_p, active=emit,
        )
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
