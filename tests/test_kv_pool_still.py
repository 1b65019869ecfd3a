"""The KV pool does not move: every cache-threading step program of the
executor carries the stacked pool through its layer scan and writes it in
place (models/llama.py `_scan_layers`, ops/kv_write.py `write_kv`).

Before PR 29 the scans took the caches as scanned inputs and returned
them as stacked outputs: each layer of each step sliced a whole layer out
of each pool, updated it and wrote it into a second stacked buffer (74 %
of the chip's time in both benchmark cells; PERF.md). Two properties of
the COMPILED program pin the cure, checked here on the CPU backend at
tiny size for all six programs and, by hand, in the chip's HLO at the
benchmark's size (PERF.md, PR 29):

  * no `copy`, `dynamic-slice` or `dynamic-update-slice` (nor a fusion
    rooted in one) whose result has the shape of one layer of a pool or
    of the whole stack;
  * the program's temporaries are smaller than ONE layer of ONE pool.

XLA:CPU writes the rows with its own scatter, in place (the chip writes
through ops/pallas/kv_write.py, because the chip's scatter re-tiles the
stack); on this backend the temporaries of all six programs stay under a
layer's size as they are, so the bound needs no allowance.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.runtime.executor import (
    DEC_FIELDS,
    PF_FIELDS,
    ModelExecutor,
)

R, BS, NB, CB = 4, 16, 512, 4  # slots, block size, pool blocks (a layer of
# a pool, 2 MiB, outweighs every activation), table width
P, LPAD = 2, 32  # prefill rows, padded chunk
K = 3  # speculative drafts; verify rows are S = K + 1 wide


@pytest.fixture(scope="module")
def executor():
    return ModelExecutor(
        EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=BS,
            num_blocks=NB, max_running_requests=R, max_seq_len=CB * BS,
            prefill_buckets=[LPAD], speculative_tokens=K,
        ),
        init_seed=0,
    )


def _i(*shape):
    return jnp.zeros(shape, jnp.int32)


def _f(*shape):
    return jnp.zeros(shape, jnp.float32)


def _decode_half():
    """The decode rows' pack and the device feedback of _decode_impl /
    _mixed_impl (every row live: a pack of ones)."""
    return _i(R, len(DEC_FIELDS) + CB) + 1, _i(R)


def _prefill_half():
    """tokens .. steps of _prefill_impl."""
    return (
        _i(P, LPAD), _i(P), _i(P) + LPAD, _i(P, CB), _f(P), _i(P),
        _f(P) + 1, jnp.zeros((P,), jnp.uint32), _i(P),
    )


def _prefill_pack():
    """The prefill rows' pack of the fused programs (its `len` column
    included: ones)."""
    return (_i(P, len(PF_FIELDS) + LPAD + CB) + 1,)


def _verify_pipe_half():
    """drafts .. frequency of _verify_pipe_impl / _mixed_verify_impl."""
    return (
        _i(R, K), _i(R), _i(R), _i(R), jnp.ones((R,), bool), _i(R, K + 1),
        _i(R), jnp.zeros((R,), jnp.uint32), _i(R, CB), jnp.ones((R,), bool),
        _f(R), _i(R), _f(R) + 1, _f(R), _f(R),
    )


def _program_args(ex, name):
    """(takes_counts, arguments after params, static keywords, optional
    array keywords) for each step program."""
    if name == "_decode_impl":
        return True, _decode_half(), {}, {}
    if name == "_prefill_impl":
        return False, _prefill_half(), {}, {}
    if name == "_mixed_impl":
        return True, _decode_half() + _prefill_pack(), {"lpad": LPAD}, {}
    if name == "_verify_pipe_impl+guided":
        # guided slots under speculation: a mask row per verify position
        # gathered in-graph in front of the same program
        return True, _verify_pipe_half(), {}, {
            "mask_rows": _i(R, K + 1),
            "guided_table": jnp.ones((3, ex.cfg.vocab_size), bool),
        }
    if name == "_verify_pipe_impl":
        return True, _verify_pipe_half(), {}, {}
    assert name == "_mixed_verify_impl"
    return True, _verify_pipe_half() + _prefill_pack(), {"lpad": LPAD}, {}


PROGRAMS = [
    "_decode_impl", "_mixed_impl", "_prefill_impl", "_verify_pipe_impl",
    "_verify_pipe_impl+guided", "_mixed_verify_impl",
]


@pytest.mark.parametrize("name", PROGRAMS)
def test_step_program_keeps_the_pool_still(executor, name):
    ex = executor
    takes_counts, rest, static, optional = _program_args(ex, name)
    counts = (ex.token_counts,) if takes_counts else ()
    donate = (0, 1, 2) if takes_counts else (0, 1)  # as the executor's jits
    ex._set_shard_ctx()
    compiled = (
        jax.jit(
            getattr(ex, name.partition("+")[0]), donate_argnums=donate,
            static_argnames=tuple(static),
        )
        .lower(
            ex.k_cache, ex.v_cache, *counts, ex.params, *rest, **static,
            **optional,
        )
        .compile()
    )
    stack = tuple(ex.k_cache.data.shape)  # [L, N, Hkv, BS, D]
    layer_bytes = int(np.prod(stack[1:])) * ex.k_cache.data.dtype.itemsize

    shapes = {
        ",".join(map(str, s)) for s in (stack, stack[1:])
    }
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        result, op = m.group(1), m.group(2)
        dims = re.match(r"\w+\[([\d,]*)\]", result)
        if dims is None or dims.group(1) not in shapes:
            continue
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        kind = op if op != "fusion" or calls is None else calls.group(1)
        if re.search(r"copy|dynamic[-_]slice|dynamic[-_]update[-_]slice", kind):
            moved.append(line.strip()[:160])
    assert not moved, (
        f"{name}: pool-sized results of copy/slice ops:\n" + "\n".join(moved)
    )

    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (
        f"{name}: {temp} bytes of temporaries, one layer of one pool is "
        f"{layer_bytes}"
    )


def test_caches_ride_the_carry():
    """The structure itself, in the jaxpr: the layer scan of decode_step
    has the two stacks among its carries and scans over parameters and a
    layer index only (no [L, ...] cache operand is scanned)."""
    from xllm_service_tpu.models import llama
    from xllm_service_tpu.models.configs import get_model_config

    cfg = get_model_config("llama3-tiny")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0), jnp.float32)
    )
    cache = jax.ShapeDtypeStruct(
        (cfg.num_layers, NB, cfg.num_kv_heads, BS, cfg.head_dim), jnp.float32
    )
    jaxpr = jax.make_jaxpr(
        lambda p, k, v: llama.decode_step(
            p, cfg, k, v, _i(R), _i(R), _i(R, CB), jnp.ones((R,), bool)
        )
    )(params, cache, cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    eqn = scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carried = [v.aval.shape for v in eqn.invars[n_consts:n_consts + n_carry]]
    scanned = [v.aval.shape for v in eqn.invars[n_consts + n_carry:]]
    assert carried.count(cache.shape) == 2
    assert cache.shape not in scanned
    assert (cfg.num_layers,) in scanned  # the layer index
    assert [v.aval.shape for v in eqn.outvars[n_carry:]] == []
