"""One transfer and one launch a step (docs/ENGINE_PIPELINE.md "Dispatch
contract"): the sampling keys are made inside the step programs, the
per-slot inputs of a dispatch cross to the device as ONE newly allocated
int32 pack per half, and `xllm_engine_dispatch_h2d_total` counts the
puts. What is pinned here:

  * keys made under a trace are `make_step_keys`' eager bits;
  * the pack round-trips every field bit for bit (floats and seeds ride
    as their int32 bits);
  * a decode dispatch makes 1 put, a mixed dispatch 2, an optional
    feature one more, and no eager device program runs between the
    engine's `_observe_host_gap` and the launch (a profiler session on
    the CPU backend);
  * the pack is FRESH: scribbling over every persistent array of the
    engine right after a dispatch entry point returns changes no token
    (XLA:CPU reads a host array in place after the put returns).
"""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import sampling as sampling_ops
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import (
    DEC_FIELDS,
    PF_FIELDS,
    ModelExecutor,
    PrefillItem,
    SamplingBatch,
    pack_rows,
    unpack_rows,
)

TOP = 2**32 - 1


# ------------------------------------------------------------------ keys


def _seed_step_cases():
    rng = np.random.RandomState(34)
    cases = {
        "edges": (
            np.array([0, 1, 2**31 - 1, 2**31, TOP, TOP], np.uint32),
            np.array([0, 1, 2**31 - 1, 0, 0, 2**31 - 1], np.int32),
        ),
    }
    for i in range(3):
        cases[f"random{i}"] = (
            rng.randint(0, 2**32, size=64, dtype=np.uint64).astype(np.uint32),
            rng.randint(0, 2**31, size=64, dtype=np.int64).astype(np.int32),
        )
    return cases


_KEY_CASES = _seed_step_cases()


@pytest.mark.parametrize("case", sorted(_KEY_CASES))
def test_traced_keys_are_the_eager_keys(case):
    seeds, steps = _KEY_CASES[case]
    eager = np.asarray(
        sampling_ops.make_step_keys(jnp.asarray(seeds), jnp.asarray(steps))
    )
    traced = np.asarray(jax.jit(sampling_ops.make_step_keys)(seeds, steps))
    assert eager.dtype == traced.dtype == np.uint32
    assert np.array_equal(eager, traced)
    # and through the pack, as the step programs get them
    n = len(seeds)
    zeros = np.zeros((n,), np.int32)
    cols = [zeros] * len(DEC_FIELDS)
    cols[DEC_FIELDS.index("seeds")] = seeds.view(np.int32)
    cols[DEC_FIELDS.index("steps")] = steps
    pack = pack_rows(cols, np.zeros((n, 2), np.int32))
    cut = n // 3  # as two halves of one program: one call, split back
    keys = jax.jit(
        lambda p: ModelExecutor._row_keys(
            *(unpack_rows(h, DEC_FIELDS)[0] for h in (p[:cut], p[cut:]))
        )
    )(pack)
    assert [len(k) for k in keys] == [cut, n - cut]
    assert np.array_equal(eager, np.concatenate([np.asarray(k) for k in keys]))


def test_verify_keys_follow_the_sequential_schedule():
    seeds, steps = _KEY_CASES["random0"]
    steps = steps // 2  # room for + j
    S = 4
    keys = np.asarray(
        jax.jit(ModelExecutor._verify_keys, static_argnums=2)(seeds, steps, S)
    )
    assert keys.shape == (len(seeds), S, 2)
    for j in range(S):
        want = sampling_ops.make_step_keys(
            jnp.asarray(seeds), jnp.asarray(steps + j)
        )
        assert np.array_equal(keys[:, j], np.asarray(want))


# ------------------------------------------------------------------ pack

_FLOATS = np.array(
    [0.0, 1.0, -0.0, 1e-45, -1e-40, 0.7, np.inf, 3.4028235e38], np.float32
)  # zero temperature, top_p 1.0, negative zero, two denormals, ...
_SEEDS = np.array(
    [0, 1, 2**31, TOP, 2**31 + 7, 0x80000001, 12345, 2**31 - 1], np.uint32
)
_INTS = np.array(
    [0, 1, -1, 2**31 - 1, -(2**31), 151935, 2047, 7], np.int32
)


def _host_columns(fields):
    n = len(_FLOATS)
    cols, want = [], {}
    for i, name in enumerate(fields):
        if name in ("temperature", "top_p", "presence", "frequency"):
            v = np.roll(_FLOATS, i)
            cols.append(v.view(np.int32))
        elif name == "seeds":
            v = np.roll(_SEEDS, i)
            cols.append(v.view(np.int32))
        elif name in ("fresh_mask", "active"):
            v = (np.arange(n) + i) % 2 == 0
            cols.append(v)
        else:
            v = np.roll(_INTS, i)
            cols.append(v)
        want[name] = v
    return cols, want


@pytest.mark.parametrize(
    "fields,widths", [(DEC_FIELDS, (4,)), (PF_FIELDS, (32, 2))],
    ids=["decode", "prefill"],
)
def test_pack_round_trips_every_field(fields, widths):
    cols, want = _host_columns(fields)
    n = len(_FLOATS)
    rng = np.random.RandomState(3)
    blocks = [
        rng.randint(-(2**31), 2**31, size=(n, w), dtype=np.int64).astype(
            np.int32
        )
        for w in widths
    ]
    pack = pack_rows(cols, *blocks)
    assert pack.dtype == np.int32 and pack.flags.owndata
    assert pack.shape == (n, len(fields) + sum(widths))
    got, rest = jax.jit(lambda p: unpack_rows(p, fields))(pack)
    assert set(got) == set(fields)
    for name in fields:
        g, w = np.asarray(got[name]), want[name]
        assert g.dtype == w.dtype, name
        # bit for bit: -0.0 and the denormals survive (== would not tell)
        assert g.tobytes() == w.tobytes(), name
    assert np.array_equal(np.asarray(rest), np.concatenate(blocks, axis=1))
    if fields is PF_FIELDS:
        _, chunk, tables = jax.jit(
            lambda p: ModelExecutor._pf_rows(p, widths[0])
        )(pack)
        assert np.array_equal(np.asarray(chunk), blocks[0])
        assert np.array_equal(np.asarray(tables), blocks[1])


def test_pack_is_a_new_array_each_time():
    cols, _ = _host_columns(DEC_FIELDS)
    block = np.zeros((len(_FLOATS), 2), np.int32)
    a, b = pack_rows(cols, block), pack_rows(cols, block)
    assert a is not b and not np.shares_memory(a, b)
    assert not any(np.shares_memory(a, c) for c in cols + [block])


# ---------------------------------------------------- puts per dispatch

R, BS = 4, 16


def _cfg(**kw):
    base = dict(
        model="llama3-tiny", dtype="float32", block_size=BS, num_blocks=64,
        max_running_requests=R, max_seq_len=256,
        prefill_buckets=[32, 64, 128, 256],
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def executor():
    return ModelExecutor(_cfg(), init_seed=0)


def _batch(**kw):
    return SamplingBatch(
        temperature=np.array([0.0, 0.9, 0.7, 0.0], np.float32),
        top_k=np.array([0, 20, 0, 0], np.int32),
        top_p=np.array([1.0, 1.0, 0.9, 1.0], np.float32),
        seeds=np.array([0, 7, TOP, 2**31], np.uint32),
        steps=np.array([0, 3, 5, 1], np.int32),
        **kw,
    )


def _decode_args():
    tables = np.zeros((R, 16), np.int32)
    tables[:, 0] = np.arange(1, R + 1)
    return (
        np.array([5, 6, 7, 8], np.int32),  # fresh tokens
        None, None,  # fresh mask, device feedback
        np.array([3, 9, 1, 0], np.int32),  # positions
        tables,
        np.array([True, True, True, False]),
    )


def _items(n):
    table = np.zeros((16,), np.int32)
    table[:2] = (10, 11)
    return [
        PrefillItem(
            token_ids=np.arange(1, 21, dtype=np.int32) + i, start_pos=0,
            block_table=table + 2 * i, temperature=0.8, seed=TOP - i, step=0,
        )
        for i in range(n)
    ]


def test_decode_dispatch_is_one_put(executor):
    ex = executor
    before = ex.dispatch_h2d
    ex.decode_start(*_decode_args(), _batch())
    assert ex.dispatch_h2d - before == 1


def test_mixed_dispatch_is_two_puts(executor):
    ex = executor
    before = ex.dispatch_h2d
    tokens, logprobs, feed = ex.mixed_start(
        _items(2), *_decode_args(), _batch()
    )
    assert ex.dispatch_h2d - before == 2
    assert tokens.shape == (R + 2,) and feed.shape == (R,)
    assert np.array_equal(np.asarray(tokens)[:R], np.asarray(feed))


def test_a_lone_chunk_runs_the_largest_decode_bucket(executor):
    """A mixed step none of whose decode rows is live reads no table: it
    runs the program of the largest decode bucket, not one of its own
    per prefill bucket (lowered inside a measured window when every
    sequence of an open loop happened to prefill at once)."""
    ex = executor
    tok, mask, fb, pos, tables, active = _decode_args()
    deep = np.array([3, 9, 1, 255], np.int32)  # 16 blocks: the largest
    ex.mixed_start(_items(1), tok, mask, fb, deep, tables, active | True,
                   _batch())
    lowered = ex.lowering_count()
    ex.mixed_start(_items(1), tok, mask, fb, pos, tables, active & False,
                   _batch())
    assert ex.lowering_count() == lowered


def test_an_optional_feature_costs_its_own_put(executor):
    ex = executor
    before = ex.dispatch_h2d
    ex.decode_start(
        *_decode_args(), _batch(min_p=np.full((R,), 0.05, np.float32))
    )
    assert ex.dispatch_h2d - before == 2
    before = ex.dispatch_h2d
    ex.decode_start(
        *_decode_args(),
        _batch(
            bias_ids=np.zeros((R, 2), np.int32),
            bias_vals=np.zeros((R, 2), np.float32),
        ),
    )
    assert ex.dispatch_h2d - before == 3


def test_penalties_ride_the_pack(executor):
    """presence / frequency are columns of the pack, not puts: a
    penalized batch dispatches the same program with the same one put,
    and the penalty reaches the sampler."""
    ex = executor
    args = _decode_args()
    greedy = dict(
        temperature=np.zeros((R,), np.float32),
        top_k=np.zeros((R,), np.int32), top_p=np.ones((R,), np.float32),
        seeds=np.zeros((R,), np.uint32), steps=np.zeros((R,), np.int32),
    )
    plain, _ = ex._fetch(*ex.decode_start(*args, SamplingBatch(**greedy)))
    ex.seed_slot_counts(0, [int(plain[0])])
    lowered = ex.lowering_count()
    before = ex.dispatch_h2d
    out = ex.decode_start(*args, SamplingBatch(
        **greedy,
        presence=np.full((R,), 1e9, np.float32),
        frequency=np.zeros((R,), np.float32),
    ))
    assert ex.dispatch_h2d - before == 1
    assert ex.lowering_count() == lowered
    penalized, _ = ex._fetch(*out)
    assert penalized[0] != plain[0]


class _C:
    def __init__(self):
        self.tokens, self.done = [], False

    def __call__(self, out):
        for so in out.outputs:
            self.tokens.extend(so.token_ids)
        if out.finished:
            self.done = True
        return True


def _add(eng, n=3, max_new=10, plen=37, seed=5):
    rng = np.random.RandomState(seed)
    cols = []
    for i in range(n):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=max_new)
            if i % 2 == 0 else SamplingParams(
                temperature=0.9, top_k=20, seed=TOP - i,
                max_new_tokens=max_new + i,
            )
        )
        c = _C()
        cols.append(c)
        eng.add_request(EngineRequest(
            f"r{i}", list(rng.randint(0, 500, size=plen + 7 * i)), sp, c,
        ))
    return cols


def _drive(eng, max_steps=2000):
    for _ in range(max_steps):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work() and eng._inflight is None


def _counter(eng, name):
    for line in eng.metrics.render().splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(name + " not rendered")


def test_engine_counts_one_put_a_decode_step_two_a_mixed_step():
    cfg = _cfg()
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    cols = _add(eng)
    _drive(eng)
    assert all(c.done for c in cols)
    steps = _counter(eng, "xllm_engine_decode_steps_total")
    puts = _counter(eng, "xllm_engine_dispatch_h2d_total")
    assert eng.mixed_steps > 0 and steps > eng.mixed_steps
    assert puts == eng.executor.dispatch_h2d == steps + eng.mixed_steps


def test_no_eager_program_between_host_gap_and_launch(tmp_path):
    """A profiler session on the CPU backend: from the engine's
    `_observe_host_gap` to the start of the executor's `launch` leaf the
    engine thread runs no device program (a `PjitFunction(...)` host
    event: `_threefry_seed`, `dynamic_slice`, `convert_element_type`
    before this change), and each launch is one step program."""
    cfg = _cfg()
    eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
    cols = _add(eng, max_new=6)
    _drive(eng)  # compile outside the session
    lowered = eng.executor.lowering_count()
    gap = eng._observe_host_gap

    def marked():
        with jax.profiler.TraceAnnotation("test.host_gap"):
            gap()

    eng._observe_host_gap = marked
    jax.profiler.start_trace(str(tmp_path))
    try:
        # the same shapes over other prompts (no prefix hit)
        cols += _add(eng, max_new=6, seed=6)
        _drive(eng)
    finally:
        jax.profiler.stop_trace()
    assert all(c.done for c in cols)
    # one program a shape: a step from an idle engine is no variant
    assert eng.executor.lowering_count() == lowered
    files = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(files[0])
    per_line = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events]
            if any(n == "test.host_gap" for _, _, n in ev):
                per_line.append(sorted(ev))
    assert len(per_line) == 1  # the engine's thread
    events = per_line[0]
    marks = [s for s, _, n in events if n == "test.host_gap"]
    launches = [(s, e) for s, e, n in events if n == "xllm.executor.launch"]
    programs = [(s, n) for s, _, n in events if n.startswith("PjitFunction(")]
    assert len(marks) >= 6 and len(launches) >= len(marks)
    assert programs
    for m in marks:
        start, end = min((s, e) for s, e in launches if s >= m)
        before = [n for s, n in programs if m <= s < start]
        assert before == [], before
        # (the profiler shows every call twice)
        inside = {n for s, n in programs if start <= s < end}
        assert len(inside) == 1 and inside < {
            "PjitFunction(_decode_impl)", "PjitFunction(_mixed_impl)",
        }, inside


# ------------------------------------------------------ the fresh pack


def _persistent_arrays(eng):
    out = [
        v for k, v in vars(eng).items()
        if k.startswith("_ps_") and isinstance(v, np.ndarray)
    ]
    assert len(out) >= 12
    return out + [eng._block_tables, eng._fresh]


@pytest.mark.parametrize("sync", [False, True], ids=["overlap", "sync"])
def test_scribbling_after_dispatch_changes_no_token(sync):
    """The fresh-pack contract on XLA:CPU: right after a dispatch entry
    point returns, every persistent per-slot array of the engine is
    overwritten with garbage, left so while the step runs, and put
    back. The streams are the undisturbed engine's."""
    streams = {}
    for scribble in (False, True):
        cfg = _cfg(sync_engine=sync)
        eng = InferenceEngine(cfg, executor=ModelExecutor(cfg, init_seed=0))
        if scribble:
            ex = eng.executor

            def wrap(fn):
                def spy(*a, **k):
                    out = fn(*a, **k)
                    arrays = _persistent_arrays(eng)
                    saved = [a_.copy() for a_ in arrays]
                    for a_ in arrays:
                        a_[...] = 1 if a_.dtype == bool else 3
                    time.sleep(0.03)
                    for a_, s_ in zip(arrays, saved):
                        a_[...] = s_
                    return out
                return spy

            # sync mode fetches inside decode(): scribble before the read
            ex.decode_start = wrap(ex.decode_start)
            ex.mixed_start = wrap(ex.mixed_start)
        cols = _add(eng, n=4, max_new=8)
        _drive(eng)
        assert all(c.done for c in cols)
        streams[scribble] = [c.tokens for c in cols]
    assert streams[True] == streams[False]
