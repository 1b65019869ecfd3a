"""On-hardware validation + microbenchmark for the Pallas kernels
(decode paged attention, MLA decode, flash prefill, MLA flash prefill)
against their jnp oracles.

Run on a real TPU:  python scripts/validate_kernel_tpu.py            # all cases
                    python scripts/validate_kernel_tpu.py --case 7  # one case
                    python scripts/validate_kernel_tpu.py --case cell-decode-batch,mq-bf16
                    python scripts/validate_kernel_tpu.py --list

Prints one line per shape: max-abs-err vs oracle, kernel vs oracle time,
and achieved HBM bandwidth (decode is bandwidth-bound: 2*R*ctx*Hkv*D*2 bytes
of KV traffic dominates). Per-case invocation lets one chip call run a
chosen subset.
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.ops.attention import paged_attention_gather
from xllm_service_tpu.ops.pallas.paged_attention import paged_attention_kernel


def bench(fn, iters=32):
    """Per-call execution time. Force a host fetch to drain the queue and
    difference two iteration counts to cancel the fetch/dispatch fixed
    cost. Repeat the differencing and take the median — single-shot
    differencing went negative on-chip when a host stall landed in the
    short leg."""
    fn()  # compile
    # warmup: flush autotune/cache effects out of the timed region
    for _ in range(3):
        out = fn()
    float(out.sum())

    def timed(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        float(out.sum())
        return time.perf_counter() - t0

    short = max(1, iters // 4)
    est = []
    for _ in range(3):
        ts = timed(short)
        tf = timed(iters + short)
        est.append((tf - ts) / iters)
    return float(np.median(est))


def device_us(fn, name, calls=4):
    """(us, launches): the mean device time of the ops whose HLO name
    starts with `name` in a profiler trace of `calls` runs of `fn`: a
    kernel's own time, without the XLA ops and the loop around it, as the
    benchmark's `breakdown.device_ops` counts it."""
    import glob
    import tempfile

    from benchmarks.harness.trace_reduce import OPS_LINE, read_planes

    float(fn().sum())
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn()
            float(out.sum())
        (path,) = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        durs = [
            dur for plane in read_planes(path)
            for ev, _, dur in plane["lines"].get(OPS_LINE, [])
            if ev.startswith("%" + name)
        ]
    return float(np.mean(durs)) / 1e3, len(durs)


def run_case(R, Hq, Hkv, D, BS, MB, ctx, dtype=jnp.bfloat16, chunk=None,
             int8=False, window=0):
    rng = np.random.default_rng(0)
    N = R * MB + 1  # block 0 reserved garbage
    q = jnp.asarray(rng.standard_normal((R, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        k = kvc.quantize_pool(k)
        v = kvc.quantize_pool(v)
    bt = jnp.asarray(
        1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32
    )
    lens = jnp.asarray(
        np.clip(rng.integers(ctx // 2, ctx + 1, R), 1, MB * BS), jnp.int32
    )
    scale = 1.0 / D**0.5

    ker = lambda: paged_attention_kernel(
        q, k, v, bt, lens, scale, chunk=chunk, window=window
    )
    gat = lambda: paged_attention_gather(
        q, k, v, bt, lens, scale, window=window
    )

    out_k = np.asarray(ker().astype(jnp.float32))
    out_g = np.asarray(gat().astype(jnp.float32))
    err = float(np.max(np.abs(out_k - out_g)))

    tk = bench(ker)
    tg = bench(gat)
    # KV bytes actually needed (true lens): element bytes + f32 group
    # scales (G=8 sub-channel groups per GQA row, kv_cache.py).
    row_bytes = D * (1 if int8 else dtype.dtype.itemsize) + (32 if int8 else 0)
    kv_bytes = 2 * float(np.sum(np.asarray(lens))) * Hkv * row_bytes
    bw = kv_bytes / tk / 1e9
    print(
        f"R={R:3d} Hq={Hq} Hkv={Hkv} D={D} BS={BS} MB={MB} ctx~{ctx} "
        f"{'int8' if int8 else 'bf16'} "
        f"err={err:.4f} kernel={tk*1e6:8.1f}us gather={tg*1e6:8.1f}us "
        f"speedup={tg/tk:5.2f}x bw={bw:6.1f}GB/s"
    )
    return err


def run_cell_case(R, Hq, Hkv, D, BS, MB, L, N, live, ctx_lo, ctx_hi,
                  int8=False, chunk=None, window=0, Dv=None, sink=False):
    """The decode kernel as a benchmark cell's step program calls it: one
    launch a layer over an L-layer stacked pool of N blocks, `live` of the
    R rows holding contexts drawn from [ctx_lo, ctx_hi] (the rest
    seq_len 0, scattered), table tails at garbage block 0. Prints us a
    CALL (the L launches of one program / L) and the share of 819 GB/s
    the live K and V bytes make of it (inside `window`, where there is
    one); error against the gather oracle on the first, a middle and the
    last layer. `Dv` value lanes and a `sink` logit a head: the window
    layers of mimo-v2-flash."""
    from xllm_service_tpu.ops import kv_cache as kvc

    rng = np.random.default_rng(0)
    kk, kv_, kq = jax.random.split(jax.random.key(0), 3)
    draw = lambda key, lanes: jax.jit(lambda: jax.lax.map(
        lambda k_: jax.random.normal(k_, (N, Hkv, BS, lanes), jnp.bfloat16),
        jax.random.split(key, L),  # a layer at a time on the chip
    ))()
    k, v = draw(kk, D), draw(kv_, Dv or D)
    sinks = jnp.linspace(-1.0, 1.0, Hq) if sink else None
    if int8:
        k, v = kvc.quantize_pool(k), kvc.quantize_pool(v)
    q = jax.random.normal(kq, (R, Hq, D), jnp.bfloat16)
    lens = np.zeros(R, np.int32)
    rows = np.sort(rng.choice(R, live, replace=False))
    lens[rows] = rng.integers(ctx_lo, ctx_hi + 1, live)
    bt = np.zeros((R, MB), np.int32)
    free = iter(1 + rng.permutation(N - 1))
    for r in rows:
        nb = -(-int(lens[r]) // BS)
        bt[r, :nb] = [next(free) for _ in range(nb)]
    bt, lens_d = jnp.asarray(bt), jnp.asarray(lens)
    scale = 1.0 / D**0.5

    # (the pools are arguments: a closed-over array is a constant of the
    # program, and 2 x 2.3 GB of constants do not lower)
    jstack = jax.jit(
        lambda q_, k_, v_: jax.lax.map(
            lambda l: paged_attention_kernel(
                q_, k_, v_, bt, lens_d, scale, layer=l, chunk=chunk,
                window=window, sinks=sinks,
            ),
            jnp.arange(L, dtype=jnp.int32),
        )
    )
    stack = lambda q_: jstack(q_, k, v)
    out = np.asarray(stack(q).astype(jnp.float32))
    assert np.all(np.isfinite(out)) and not out[:, lens == 0].any()
    err = 0.0
    for l in sorted({0, L // 2, L - 1}):
        at = lambda c: jax.tree.map(lambda a: a[l], c)
        ref = paged_attention_gather(
            q, at(k), at(v), bt, lens_d, scale, window=window, sinks=sinks
        )
        ref = np.asarray(ref.astype(jnp.float32))
        err = max(err, float(np.max(np.abs(out[l] - ref)[lens > 0])))
    tk = bench(lambda: stack(q), iters=8) / L
    name = "window_" * bool(window) + "paged_attention_kernel"
    td, n = device_us(lambda: stack(q), name)
    row_bytes = (D + (Dv or D)) * (1 if int8 else 2) + (64 if int8 else 0)
    seen = np.minimum(lens, window) if window else lens
    kv_bytes = float(seen.sum()) * Hkv * row_bytes
    print(
        f"CELL R={R} live={live} Hq={Hq} Hkv={Hkv} D={D} BS={BS} MB={MB} "
        f"L={L} N={N} chunk={chunk} window={window} ctx={ctx_lo}-{ctx_hi} "
        f"(mean {lens[rows].mean():.0f}) "
        f"{'int8' if int8 else 'bf16'} err={err:.4f} "
        f"call={tk*1e6:8.1f}us kernel={td:7.2f}us (x{n}) "
        f"row={td/live:6.3f}us/live-row "
        f"bw={kv_bytes/tk/1e9:6.1f}GB/s "
        f"hbm_share={100*kv_bytes/tk/819e9:5.1f}%"
    )
    return err


def run_kv_write_case(S, live, L, N, Hc, D, BS, CB=16, ctx=1024):
    """`kv_write_kernel` as a decode program calls it (ops/kv_write.py:
    one plan a step, one launch a layer): one new row of K and of V for
    `live` of the S slots (scattered; the rest write nothing) into the
    L-layer stacked pools of N blocks, which ride a layer scan's carry.
    us a CALL (the L launches / L) and a live unit; the pools against
    the rows placed by one plain scatter, outside garbage block 0."""
    from xllm_service_tpu.ops import kv_write as kvw

    rng = np.random.default_rng(0)
    length = np.zeros(S, np.int32)
    rows = np.sort(rng.choice(S, live, replace=False))
    length[rows] = 1
    start = np.where(length > 0, rng.integers(1, ctx, S), 0).astype(np.int32)
    tables = np.zeros((S, CB), np.int32)
    free = iter(1 + rng.permutation(N - 1))
    for r in rows:
        tables[r, : start[r] // BS + 1] = [
            next(free) for _ in range(start[r] // BS + 1)
        ]
    tables, start, length = map(jnp.asarray, (tables, start, length))
    new = jax.random.normal(jax.random.key(2), (2, L, S, Hc, D), jnp.bfloat16)
    pool = lambda: jnp.zeros((L, N, Hc, BS, D), jnp.bfloat16)

    def run(k, v, new):
        plan = kvw.write_plan(k, tables, start, length, 1)
        assert plan.units is not None  # the Pallas route, not the scatter

        def body(c, l):
            return kvw.write_kv(*c, plan, new[0, l], new[1, l], l), None

        return jax.lax.scan(body, (k, v), jnp.arange(L, dtype=jnp.int32))[0]

    kern = jax.jit(run, donate_argnums=(0, 1))
    got = kern(pool(), pool(), new)
    blk, off = tables[rows, start[rows] // BS], start[rows] % BS
    err = 0.0
    for g, rows_new in zip(got, new):  # each live row placed by hand
        for l in sorted({0, L // 2, L - 1}):
            want = jnp.zeros_like(g[l]).at[blk, :, off].set(rows_new[l, rows])
            err = max(err, float(jnp.abs(
                (g[l, 1:] - want[1:]).astype(jnp.float32)
            ).max()))
    state = [got]

    def once():
        state[0] = kern(*state[0], new)
        return state[0][0][0, 0, 0, 0]

    tk = bench(once, iters=8) / L
    td, n = device_us(once, "kv_write_kernel")
    print(
        f"KV-WRITE S={S} live={live} L={L} N={N} Hc={Hc} D={D} BS={BS} "
        f"err={err:.4f} call={tk*1e6:8.1f}us kernel={td:7.2f}us (x{n}) "
        f"unit={td/max(live, 1):6.3f}us/live-unit"
    )
    return err


def run_packed_case(R, Hq, Hkv, D, BS, MB, ctx, dtype=jnp.bfloat16,
                    int8=False):
    """Packed-pair decode (head_dim < 128, llama3-1b class): cache rows
    carry P = 128/D heads; queries embed block-diagonally. Kernel vs the
    unpacking gather oracle."""
    from xllm_service_tpu.ops import kv_cache as kvc
    from xllm_service_tpu.ops.attention import kernel_io_for, unpack_outputs

    rng = np.random.default_rng(0)
    P = 128 // D
    hc, dc = Hkv // P, D * P
    N = R * MB + 1
    q = jnp.asarray(rng.standard_normal((R, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, hc, BS, dc)), dtype)
    v = jnp.asarray(rng.standard_normal((N, hc, BS, dc)), dtype)
    if int8:
        k, v = kvc.quantize_pool(k), kvc.quantize_pool(v)
    bt = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32)
    lens = jnp.asarray(
        np.clip(rng.integers(ctx // 2, ctx + 1, R), 1, MB * BS), jnp.int32
    )
    scale = 1.0 / D**0.5
    pk, kvh, qp = kernel_io_for(k, q)

    ker = lambda: unpack_outputs(
        paged_attention_kernel(qp, k, v, bt, lens, scale), pk, kvh
    )
    gat = lambda: paged_attention_gather(q, k, v, bt, lens, scale)
    err = float(
        np.max(np.abs(np.asarray(ker().astype(jnp.float32))
                      - np.asarray(gat().astype(jnp.float32))))
    )
    tk, tg = bench(ker), bench(gat)
    row_bytes = dc * (1 if int8 else dtype.dtype.itemsize) + (32 if int8 else 0)
    kv_bytes = 2 * float(np.sum(np.asarray(lens))) * hc * row_bytes
    bw = kv_bytes / tk / 1e9
    print(
        f"PACKED R={R:3d} Hq={Hq} Hkv={Hkv} D={D} (P={pk}) BS={BS} MB={MB} "
        f"ctx~{ctx} {'int8' if int8 else 'bf16'} err={err:.4f} "
        f"kernel={tk*1e6:8.1f}us gather={tg*1e6:8.1f}us "
        f"speedup={tg/tk:5.2f}x bw={bw:6.1f}GB/s"
    )
    return err


def run_mq_case(R, S, Hq, Hkv, D, BS, MB, ctx, dtype=jnp.bfloat16,
                int8=False):
    """Multi-query decode (speculative verify) kernel vs the blockwise
    prefill oracle on hardware."""
    from xllm_service_tpu.ops.attention import prefill_attention
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    rng = np.random.default_rng(0)
    N = R * MB + 1
    q = jnp.asarray(rng.standard_normal((R, S, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        k = kvc.quantize_pool(k)
        v = kvc.quantize_pool(v)
    bt = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32)
    lens = jnp.asarray(
        np.clip(rng.integers(ctx // 2, ctx + 1, R), 1, MB * BS - S), jnp.int32
    )
    scale = 1.0 / D**0.5
    start_pos = jnp.maximum(lens - 1, 0)
    true_len = jnp.full((R,), S, jnp.int32)

    ker = lambda: multiquery_paged_attention_kernel(
        q, k, v, bt, lens, scale
    )
    orc = lambda: prefill_attention(
        q, k, v, bt, start_pos, true_len, scale, use_kernel=False
    )
    err = float(
        np.max(np.abs(np.asarray(ker().astype(jnp.float32))
                      - np.asarray(orc().astype(jnp.float32))))
    )
    tk, tg = bench(ker), bench(orc)
    row_bytes = D * (1 if int8 else dtype.dtype.itemsize) + (32 if int8 else 0)
    kv_bytes = 2 * float(np.sum(np.asarray(lens))) * Hkv * row_bytes
    bw = kv_bytes / tk / 1e9
    print(
        f"MQ R={R:3d} S={S} Hq={Hq} Hkv={Hkv} D={D} BS={BS} MB={MB} "
        f"ctx~{ctx} {'int8' if int8 else 'bf16'} err={err:.4f} "
        f"kernel={tk*1e6:8.1f}us blockwise={tg*1e6:8.1f}us "
        f"speedup={tg/tk:5.2f}x bw={bw:6.1f}GB/s"
    )
    return err


def run_mla_mq_case(R, S, Hq, kvr, dr, BS, MB, ctx, dtype=jnp.bfloat16,
                    int8=False):
    """MLA multi-query (speculative verify) kernel vs the blockwise oracle
    on hardware."""
    from xllm_service_tpu.ops.attention import mla_prefill_attention
    from xllm_service_tpu.ops.pallas.mla_attention import (
        mla_multiquery_attention_kernel,
    )

    rng = np.random.default_rng(0)
    C = (kvr + dr + 127) // 128 * 128  # lane-padded like the real pool
    N = R * MB + 1
    q = jnp.asarray(rng.standard_normal((R, S, Hq, kvr + dr)), dtype)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, C - kvr - dr)))
    cache = jnp.asarray(rng.standard_normal((N, 1, BS, kvr + dr)), dtype)
    cache = jnp.pad(cache, ((0, 0), (0, 0), (0, 0), (0, C - kvr - dr)))
    G = 1
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        G = kvc.mla_scale_groups(kvr, dr, C)
        cache = kvc.quantize_pool(cache, G)
    bt = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32)
    lens = jnp.asarray(
        np.clip(rng.integers(ctx // 2, ctx + 1, R), 1, MB * BS - S), jnp.int32
    )
    scale = C**-0.5
    start_pos = jnp.maximum(lens - 1, 0)
    true_len = jnp.full((R,), S, jnp.int32)
    ker = lambda: mla_multiquery_attention_kernel(
        q, cache, bt, lens, scale, kvr
    )
    orc = lambda: mla_prefill_attention(
        q, cache, bt, start_pos, true_len, scale, kvr, use_kernel=False
    )
    err = float(
        np.max(np.abs(np.asarray(ker().astype(jnp.float32))
                      - np.asarray(orc().astype(jnp.float32))))
    )
    tk, tg = bench(ker), bench(orc)
    row_bytes = C + 4 * G if int8 else C * dtype.dtype.itemsize
    bw = float(np.sum(np.asarray(lens))) * row_bytes / tk / 1e9
    print(
        f"MLA-MQ R={R:3d} S={S} Hq={Hq} kvr={kvr} dr={dr} BS={BS} MB={MB} "
        f"ctx~{ctx} {'int8' if int8 else 'bf16'} err={err:.4f} "
        f"kernel={tk*1e6:8.1f}us "
        f"blockwise={tg*1e6:8.1f}us speedup={tg/tk:5.2f}x bw={bw:6.1f}GB/s"
    )
    return err


def run_mla_case(R, Hq, kvr, dr, BS, MB, ctx, dtype=jnp.bfloat16,
                 int8=False):
    """MLA decode kernel vs the MLA gather oracle on hardware."""
    from xllm_service_tpu.ops.attention import mla_paged_attention_gather
    from xllm_service_tpu.ops.pallas.mla_attention import mla_attention_kernel

    rng = np.random.default_rng(0)
    C = (kvr + dr + 127) // 128 * 128  # lane-padded like the real pool
    N = R * MB + 1
    q = jnp.asarray(rng.standard_normal((R, Hq, kvr + dr)), dtype)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, C - kvr - dr)))
    cache = jnp.asarray(rng.standard_normal((N, 1, BS, kvr + dr)), dtype)
    cache = jnp.pad(cache, ((0, 0), (0, 0), (0, 0), (0, C - kvr - dr)))
    G = 1
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        G = kvc.mla_scale_groups(kvr, dr, C)
        cache = kvc.quantize_pool(cache, G)
    bt = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32)
    lens = jnp.asarray(
        np.clip(rng.integers(ctx // 2, ctx + 1, R), 1, MB * BS), jnp.int32
    )
    scale = C**-0.5
    ker = lambda: mla_attention_kernel(q, cache, bt, lens, scale, kvr)
    gat = lambda: mla_paged_attention_gather(q, cache, bt, lens, scale, kvr)
    err = float(
        np.max(np.abs(np.asarray(ker().astype(jnp.float32))
                      - np.asarray(gat().astype(jnp.float32))))
    )
    tk, tg = bench(ker), bench(gat)
    row_bytes = C + 4 * G if int8 else C * dtype.dtype.itemsize
    bw = float(np.sum(np.asarray(lens))) * row_bytes / tk / 1e9
    print(
        f"MLA R={R:3d} Hq={Hq} kvr={kvr} dr={dr} BS={BS} MB={MB} ctx~{ctx} "
        f"{'int8' if int8 else 'bf16'} "
        f"err={err:.4f} kernel={tk*1e6:8.1f}us gather={tg*1e6:8.1f}us "
        f"speedup={tg/tk:5.2f}x bw={bw:6.1f}GB/s"
    )
    return err


def run_prefill_case(P, Lpad, Hq, Hkv, D, BS, MB, dtype=jnp.bfloat16,
                     int8=False, tile_q=128, window=0):
    """GQA flash prefill kernel vs the blockwise oracle on hardware."""
    from xllm_service_tpu.ops.attention import prefill_attention_blockwise
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    rng = np.random.default_rng(0)
    N = P * MB + 1
    q = jnp.asarray(rng.standard_normal((P, Lpad, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    v = jnp.asarray(rng.standard_normal((N, Hkv, BS, D)), dtype)
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        k = kvc.quantize_pool(k)
        v = kvc.quantize_pool(v)
    bt = jnp.asarray(1 + np.arange(P * MB).reshape(P, MB) % (N - 1), jnp.int32)
    sp = jnp.asarray(rng.integers(0, BS, P), jnp.int32)
    tl = jnp.asarray(
        np.clip(rng.integers(Lpad // 2, Lpad + 1, P), 1, Lpad), jnp.int32
    )
    scale = 1.0 / D**0.5

    ker = lambda: flash_prefill_kernel(
        q, k, v, bt, sp, tl, scale, tile_q=tile_q, window=window
    )
    # Jit ONCE (the pjit cache keys on callable identity — a fresh lambda
    # per call would recompile the oracle every timing iteration).
    jorc = jax.jit(
        lambda q_, bt_, sp_, tl_: jax.vmap(
            lambda qi, ti, s_, t_: prefill_attention_blockwise(
                qi, k, v, ti, s_, t_, scale, window=window
            )
        )(q_, bt_, sp_, tl_)
    )
    orc = lambda: jorc(q, bt, sp, tl)

    ok = np.asarray(ker().astype(jnp.float32))
    og = np.asarray(orc().astype(jnp.float32))
    # compare valid rows only
    errs = [
        float(np.max(np.abs(ok[p, :int(tl[p])] - og[p, :int(tl[p])])))
        for p in range(P)
    ]
    err = max(errs)
    tk, tg = bench(ker), bench(orc)
    tok = float(np.sum(np.asarray(tl)))
    print(
        f"PREFILL P={P} L={Lpad} Hq={Hq} Hkv={Hkv} D={D} BS={BS} MB={MB} "
        f"{'int8' if int8 else 'bf16'} err={err:.4f} "
        f"kernel={tk*1e6:8.1f}us blockwise={tg*1e6:8.1f}us "
        f"speedup={tg/tk:5.2f}x tok/s={tok/tk:,.0f}"
    )
    return err


def run_mla_prefill_case(P, Lpad, Hq, kvr, dr, BS, MB, dtype=jnp.bfloat16,
                         int8=False):
    """MLA flash prefill kernel vs the blockwise oracle on hardware."""
    from xllm_service_tpu.ops.attention import mla_prefill_blockwise
    from xllm_service_tpu.ops.pallas.mla_prefill import (
        mla_flash_prefill_kernel,
    )

    rng = np.random.default_rng(0)
    C = (kvr + dr + 127) // 128 * 128  # lane-padded like the real pool
    N = P * MB + 1
    q = jnp.asarray(rng.standard_normal((P, Lpad, Hq, kvr + dr)), dtype)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, C - kvr - dr)))
    cache = jnp.asarray(rng.standard_normal((N, 1, BS, kvr + dr)), dtype)
    cache = jnp.pad(cache, ((0, 0), (0, 0), (0, 0), (0, C - kvr - dr)))
    if int8:
        from xllm_service_tpu.ops import kv_cache as kvc

        G = kvc.mla_scale_groups(kvr, dr, C)
        cache = kvc.quantize_pool(cache, G)
    bt = jnp.asarray(1 + np.arange(P * MB).reshape(P, MB) % (N - 1), jnp.int32)
    sp = jnp.asarray(rng.integers(0, BS, P), jnp.int32)
    tl = jnp.asarray(
        np.clip(rng.integers(Lpad // 2, Lpad + 1, P), 1, Lpad), jnp.int32
    )
    scale = C**-0.5
    ker = lambda: mla_flash_prefill_kernel(
        q, cache, bt, sp, tl, scale, kvr
    )
    jorc = jax.jit(
        lambda q_, bt_, sp_, tl_: jax.vmap(
            lambda qi, ti, s_, t_: mla_prefill_blockwise(
                qi, cache, ti, s_, t_, scale, kvr
            )
        )(q_, bt_, sp_, tl_)
    )
    orc = lambda: jorc(q, bt, sp, tl)
    ok = np.asarray(ker().astype(jnp.float32))
    og = np.asarray(orc().astype(jnp.float32))
    err = max(
        float(np.max(np.abs(ok[p, :int(tl[p])] - og[p, :int(tl[p])])))
        for p in range(P)
    )
    tk, tg = bench(ker), bench(orc)
    print(
        f"MLA-PREFILL P={P} L={Lpad} Hq={Hq} kvr={kvr} dr={dr} BS={BS} "
        f"MB={MB} err={err:.4f} kernel={tk*1e6:8.1f}us "
        f"blockwise={tg*1e6:8.1f}us speedup={tg/tk:5.2f}x"
    )
    return err


def run_mla_prefill_forms_case(Hq, kvr, dn, dr, dv, BS, Lpad, MB, contexts,
                               **kernel_kw):
    """The two forms of the MLA prefill launch as deepseek-v2.doc-steady's
    mixed step calls it (PERF.md, PR 55): ONE chunk of `Lpad` rows, all
    `Hq` heads, a table as wide as the one context bucket (`MB` blocks),
    at each of `contexts` cached tokens before the chunk. ABSORBED:
    `mla_prefill_kernel` over q_lat (the projection into the latent
    space and W_UV after it are XLA's and not in `kernel=`);
    MATERIALISED: `mla_materialised_prefill_kernel`. `kernel=` is the
    op's own device time from a trace, `call=` the host's time a call;
    the error is the largest |difference| of the heads' value-space
    outputs against the blockwise scan in float32 (and its share of the
    oracle's largest value)."""
    from xllm_service_tpu.ops.attention import mla_prefill_blockwise
    from xllm_service_tpu.ops.pallas.mla_prefill import (
        mla_flash_prefill_kernel,
        mla_materialised_prefill_kernel,
    )

    bf = jnp.bfloat16
    C = (kvr + dr + 127) // 128 * 128
    N = MB + 1
    ks = jax.random.split(jax.random.key(0), 5)
    # the heads as the projection writes them ([q_nope | rope part, not
    # roped]: the materialised kernel reads them where they lie), and q_pe
    q = jax.random.normal(ks[0], (1, Lpad, Hq, dn + dr), bf)
    q_nope = q[..., :dn]
    q_pe = jax.random.normal(ks[1], (1, Lpad, Hq, dr), bf)
    w_uk = (jax.random.normal(ks[2], (Hq, kvr, dn)) / kvr**0.5).astype(bf)
    w_uv = (jax.random.normal(ks[3], (Hq, kvr, dv)) / kvr**0.5).astype(bf)
    cache = jax.random.normal(ks[4], (N, 1, BS, kvr + dr), bf)
    cache = jnp.pad(cache, ((0, 0),) * 3 + ((0, C - kvr - dr),))
    bt = jnp.asarray(1 + np.arange(MB)[None], jnp.int32)
    tl = jnp.asarray([Lpad], jnp.int32)
    scale = (dn + dr) ** -0.5

    @jax.jit
    def absorb(qn, qp, wk):
        q_lat = jnp.einsum("plhd,hkd->plhk", qn, wk)
        q_lat = jnp.concatenate([q_lat, qp.astype(q_lat.dtype)], -1)
        return jnp.pad(q_lat, ((0, 0),) * 3 + ((0, C - kvr - dr),))

    up_v = jax.jit(lambda ctx, wv: jnp.einsum("plhk,hkv->plhv", ctx, wv))
    f32 = lambda x: x.astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames="nb")
    def oracle(sp, nb):
        ctx = mla_prefill_blockwise(
            absorb(f32(q_nope), f32(q_pe), f32(w_uk))[0], f32(cache),
            bt[0, :nb], sp[0], tl[0], scale, kvr,
        )
        return up_v(ctx[None], f32(w_uv))

    q_lat = absorb(q_nope, q_pe, w_uk)
    worst = 0.0
    for ctx_len in contexts:
        sp = jnp.asarray([ctx_len], jnp.int32)
        ref = np.asarray(oracle(sp, -(-(ctx_len + Lpad) // BS)))
        forms = {
            "absorbed": (
                "mla_prefill_kernel",
                lambda: mla_flash_prefill_kernel(
                    q_lat, cache, bt, sp, tl, scale, kvr
                ),
                lambda o: up_v(o, w_uv),
            ),
            "materialised": (
                "mla_materialised_prefill_kernel",
                lambda: mla_materialised_prefill_kernel(
                    q, q_pe, w_uk, w_uv, cache, bt, sp, tl, scale,
                    kvr, **kernel_kw
                ),
                lambda o: o,
            ),
        }
        for form, (op, ker, finish) in forms.items():
            err = float(np.max(np.abs(np.asarray(f32(finish(ker()))) - ref)))
            us, n = device_us(ker, op)
            print(
                f"MLA-PREFILL-FORMS {form:12s} cached={ctx_len:5d} L={Lpad} "
                f"Hq={Hq} MB={MB} err={err:.4f} "
                f"({err / float(np.max(np.abs(ref))):.4f} of max) "
                f"kernel={us:8.1f}us ({n} launches) "
                f"call={bench(ker, iters=16)*1e6:8.1f}us",
                flush=True,
            )
            worst = max(worst, err)
    return worst


# Ordered so the never-yet-chip-validated kernels come first (round 3
# queue: int8 scale-DMA decode, MLA decode, flash prefill) — the bf16
# decode cases at the tail were already chip-validated in round 2.
def _mamba_inputs(rows, Lc, H, P, N, seed=0, G=1):
    """Scan inputs in the band the benchmark's weights give: steps
    log-uniform in 1e-3..1e-1, A in 1..16."""
    ks = jax.random.split(jax.random.key(seed), 6)
    lead = (rows,) if Lc is None else (rows, Lc)
    x = jax.random.normal(ks[0], lead + (H, P), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], lead + (H,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
    B = jax.random.normal(ks[3], lead + (G, N), jnp.float32)
    C = jax.random.normal(ks[4], lead + (G, N), jnp.float32)
    return x, dt, A, B, C, jnp.ones((H,), jnp.float32)


def run_mamba_update_case(R, live, L, H, P, N, tile=None, G=1):
    """mamba_update_kernel as a hybrid cell's decode program calls it
    (granite-4.0-h-small: G 1, N 128, P 64; falcon-h1-34b: G 2, N 256,
    P 128): one launch a Mamba layer over the L-layer state pool of R slots,
    `live` of the R rows active (scattered). us a CALL (the L launches of
    one program / L), the share of 819 GB/s the live rows' state bytes
    (read and written) make of it, and the largest error of y and of the
    state against the jax.numpy route on the first and the last layer."""
    from xllm_service_tpu.ops import mamba as mo
    from xllm_service_tpu.ops.pallas import mamba as pm

    if tile:  # lane rows a grid step, whatever the block then weighs
        pm.HEAD_TILE, pm.TILE_BYTES = tile, tile * N * 128 * 4
    rng = np.random.default_rng(0)
    act = np.zeros(R, bool)
    act[rng.choice(R, live, replace=False)] = True
    act = jnp.asarray(act)
    x, dt, A, B, C, D = _mamba_inputs(R, None, H, P, N, G=G)
    shape = mo.state_shapes(L, R, H, P, N, 4, H * P + 2 * G * N)[0]
    S = jax.jit(lambda k: jax.lax.map(
        lambda k_: jax.random.normal(k_, shape[1:], jnp.float32), jax.random.split(k, L)
    ))(jax.random.key(1))

    def step(use):
        def run(S_):
            def body(S_, l):
                y, S_ = mo.decode_update(S_, l, act, x, dt, A, B, C, D, use_kernel=use)
                return S_, y
            return jax.lax.scan(body, S_, jnp.arange(L, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    kern, ref = step(True), step(False)
    S_k, y_k = kern(S + 0.0)
    S_r, y_r = ref(S + 0.0)
    err_y = float(jnp.abs(y_k - y_r).max())
    err_s = max(float(jnp.abs(S_k[l] - S_r[l]).max()) for l in (0, L - 1))
    del S_r, y_r
    state = [S_k]

    def once():
        state[0], y = kern(state[0])
        return y

    tk = bench(once, iters=8) / L
    need = 2 * live * H * P * N * 4
    print(
        f"MAMBA-UPDATE R={R} live={live} L={L} H={H} P={P} N={N} G={G} "
        f"tile={pm.head_tile(shape[2] // G, N * shape[4] * 4)} err_y={err_y:.2e} err_S={err_s:.2e} "
        f"call={tk*1e6:8.1f}us row={tk*1e6/live:6.2f}us/live-row "
        f"bw={need/tk/1e9:6.1f}GB/s hbm_share={100*need/tk/819e9:5.1f}%"
    )
    return max(err_y, err_s) * 1e-2  # float32: the parity bound is main's 0.05


def run_mamba_chunk_case(Lc, L, H, P, N, slots=8, G=1):
    """The chunk form (plain XLA, ops/mamba.py chunk_update) over an
    L-layer pool: one chunk of Lc tokens from a FRESH state, then the next
    from the CARRIED one, against the token-by-token recurrence; us a
    layer's call for each."""
    from xllm_service_tpu.ops import mamba as mo

    x, dt, A, B, C, D = _mamba_inputs(1, 2 * Lc, H, P, N, seed=3, G=G)
    y_ref, S_ref = jax.jit(mo.recurrent_form)(x[0], dt[0], A, B[0], C[0], D)
    shape = mo.state_shapes(L, slots, H, P, N, 4, H * P + 2 * G * N)[0]
    S = jnp.full(shape, 2.0, jnp.float32)
    slot = jnp.array([3], jnp.int32)

    def make(start):
        sl = slice(start, start + Lc)
        def run(S_):
            def body(S_, l):
                y, S_ = mo.chunk_update(S_, l, slot, jnp.array([start]), jnp.array([Lc]),
                                        x[:, sl], dt[:, sl], A, B[:, sl], C[:, sl], D)
                return S_, y
            return jax.lax.scan(body, S_, jnp.arange(L, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    fresh, carried = make(0), make(Lc)
    S, y0 = fresh(S)
    S, y1 = carried(S)
    k = mo.pack_factor(H, P)
    err = max(float(jnp.abs(y0[L - 1, 0] - y_ref[:Lc]).max()),
              float(jnp.abs(y1[0, 0] - y_ref[Lc:]).max()),
              float(jnp.abs(mo.from_pool(S[L - 1, 3], k) - S_ref).max()))
    state = [S]

    def timed(fn):
        def once():
            state[0], y = fn(state[0])
            return y
        return bench(once, iters=8) / L

    t0, t1 = timed(fresh), timed(carried)
    print(f"MAMBA-CHUNK Lc={Lc} L={L} H={H} P={P} N={N} G={G} err={err:.2e} "
          f"fresh={t0*1e6:8.1f}us carried={t1*1e6:8.1f}us a layer")
    return err * 1e-2


def run_kda_gram_case(n, H, d, C=64):
    """The decayed gram of one prefill chunk of a KDA layer (ops/kda.py
    `_decayed_gram`: M[k] and M[q] of n chunks of C tokens, H heads of d
    lanes, as `_chunk_scan` hands them over) with its diagonal sub-blocks
    through `kda_gram_kernel` against the two-fusion `jax.numpy` form: ms a
    call of each, the launch's own device time, and the bytes each form
    moves for the diagonal (the pair tensor `[n, H, C/16, 16, 16, d]`
    written and read again | the kernel's operands and its diagonals)."""
    from xllm_service_tpu.ops import kda as ko

    ks = jax.random.split(jax.random.key(5), 4)
    shape = (n, 1, H, C, d)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k, q = l2(jax.random.normal(ks[0], shape)), l2(jax.random.normal(ks[1], shape)) * d ** -0.5
    # per-token decays of 0.9-0.999 a channel, a head's own scale on top
    g = -jnp.exp(jax.random.uniform(ks[2], shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    G = jnp.cumsum(g * jnp.exp(jax.random.normal(ks[3], (n, 1, H, 1, 1))), axis=-2)
    forms = {
        use: jax.jit(lambda k, q, G, use=use: ko._decayed_gram(jnp.stack([k, q]), k, G, use))
        for use in (True, False)
    }
    err = float(jnp.abs(forms[True](k, q, G) - forms[False](k, q, G)).max())
    tk, tx = (bench(lambda f=forms[use]: f(k, q, G), iters=16) for use in (True, False))
    own, launches = device_us(lambda: forms[True](k, q, G), "kda_gram_kernel")
    tokens = n * C * H
    pair = tokens * ko.BLOCK * d * 4  # [.., 16, 16, d] a token
    xla_bytes = 2 * pair + (4 * d + 2 * ko.BLOCK) * tokens * 4
    kernel_bytes = (4 * d + 2 * ko.BLOCK) * tokens * 4  # G, k, k, q in; two diagonals out
    print(
        f"KDA-GRAM n={n} H={H} d={d} C={C} err={err:.2e} "
        f"kernel_form={tk*1e6:8.1f}us (launch {own:7.1f}us x{launches // 4}, {kernel_bytes/1e6:6.1f} MB "
        f"{100*kernel_bytes/own/819e3:4.1f}% of 819 GB/s) two_fusion_form={tx*1e6:8.1f}us "
        f"({xla_bytes/1e6:6.1f} MB for the diagonal)"
    )
    return err * 1e-2  # float32 sums of 128 terms in another order


# llama-8B-class: Hq=32 Hkv=8 D=128; llama-70B-class: Hq=64 Hkv=8 D=128.
# NOTE: D=64 decode is NOT included — Mosaic rejects the lane-padded HBM
# block slice below one 128-lane tile (tpu.memref_slice verify failure
# on-chip); ops/attention.py falls back to gather there.
def run_kda_chunk_case(Lc, H, d, slots=4, start=512):
    """One prefill chunk of a KDA layer from the convolution's output
    (ops/kda.py `chunk_update`: one row of Lc tokens against a
    carried state) as `kda_chunk_kernel` against the `jax.numpy` chunk
    form with the gram's diagonal as `kda_gram_kernel` (what the mixed
    step held before the kernel) and without it: output and state apart,
    us a call of each with the pool donated, and the launch's own device
    time."""
    from xllm_service_tpu.ops import kda as ko

    ks = jax.random.split(jax.random.key(6), 5)
    qkv = jax.random.normal(ks[0], (1, Lc, 3 * H * d))
    # per-token decays of 0.9-0.999 a channel, a head's own scale on top
    g = -jnp.exp(jax.random.uniform(ks[1], (1, Lc, H, d), jnp.float32, np.log(1e-3), np.log(1e-1)))
    g = g * jnp.exp(jax.random.normal(ks[2], (1, 1, H, 1)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (1, Lc, H)))
    S0 = jax.random.normal(ks[4], (1, slots, H, d, d))
    meta = (jnp.int32(0), jnp.array([1]), jnp.array([start]), jnp.array([Lc - 3]))

    def xla_form(gram):  # the chunk form around `_chunk_scan`, as `chunk_update` hands it over
        def f(S, qkv, g, beta):
            x = (*ko.qkv_heads(qkv, H, d), g, beta)
            o, sT = ko._chunk_scan(*ko._masked(*x, meta[3], ko.CHUNK), S[0, 1][None], ko.CHUNK, gram)
            return o, S.at[0, 1].set(sT[0])
        return f

    forms = {
        "kernel": lambda S, *x: ko.chunk_update(S, *meta, *x, use_kernel=True),
        "xla+gram": xla_form(True), "xla": xla_form(False),
    }
    out, us = {}, {}
    for name, f in forms.items():
        jf = jax.jit(f, donate_argnums=0)
        hold = {"S": S0 + 0.0}

        def call(jf=jf, hold=hold):
            o, hold["S"] = jf(hold["S"], qkv, g, beta)
            return o

        o = call()
        out[name] = (o[:, :Lc - 3], hold["S"][0, 1] + 0.0)
        us[name] = bench(call, iters=16) * 1e6
        if name == "kernel":
            own, launches = device_us(call, "kda_chunk_kernel")
    err_o = float(jnp.abs(out["kernel"][0] - out["xla"][0]).max()) / float(jnp.abs(out["xla"][0]).max())
    err_s = float(jnp.abs(out["kernel"][1] - out["xla"][1]).max()) / float(jnp.abs(out["xla"][1]).max())
    print(
        f"KDA-CHUNK Lc={Lc} H={H} d={d} err_o={err_o:.2e} err_S={err_s:.2e} "
        f"kernel={us['kernel']:8.1f}us (launch {own:7.1f}us x{launches // 4}) "
        f"xla+gram={us['xla+gram']:8.1f}us xla={us['xla']:8.1f}us"
    )
    return max(err_o, err_s) * 1e-2  # float32 sums in another order


def run_lightning_update_case(R, live, L, H, d):
    """lightning_update_kernel as minicpm-sala's decode program calls it:
    one launch a lightning layer over the L-layer state pool of R slots,
    `live` of the R rows active (scattered). us a CALL, the share of 819
    GB/s the live rows' state bytes (read and written) make of it, and the
    largest error of o and of the state against the jax.numpy route."""
    from xllm_service_tpu.ops import lightning as lo

    rng = np.random.default_rng(0)
    act = np.zeros(R, bool)
    act[rng.choice(R, live, replace=False)] = True
    act = jnp.asarray(act)
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (R, H, d), jnp.float32) for kk in ks)
    q = q * d ** -0.5
    log_lam = jnp.asarray(lo.log_decay(H, tuple(range(10, 10 + L)), 32))
    shape = lo.state_shape(L, R, H, d)
    S = jax.jit(lambda kk: jax.lax.map(
        lambda k_: jax.random.normal(k_, shape[1:], jnp.float32), jax.random.split(kk, L)
    ))(jax.random.key(1))

    def step(use):
        def run(S_):
            def body(S_, l):
                o, S_ = lo.decode_update(S_, l, act, q, k, v, log_lam[l], use_kernel=use)
                return S_, o
            return jax.lax.scan(body, S_, jnp.arange(L, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    kern, ref = step(True), step(False)
    S_k, o_k = kern(S + 0.0)
    S_r, o_r = ref(S + 0.0)
    err_o = float(jnp.abs(o_k - o_r).max())
    err_s = max(float(jnp.abs(S_k[l] - S_r[l]).max()) for l in (0, L - 1))
    del S_r, o_r
    need = 2 * live * H * d * d * 4
    out = []
    for name, fn, st in (("kernel", kern, [S_k]), ("xla", ref, [S + 0.0])):
        def once(fn=fn, st=st):
            st[0], o = fn(st[0])
            return o
        t = bench(once, iters=8) / L
        out.append(f"{name}={t*1e6:8.1f}us hbm_share={100*need/t/819e9:5.1f}%")
    print(f"LIGHTNING-UPDATE R={R} live={live} L={L} H={H} d={d} err_o={err_o:.2e} "
          f"err_S={err_s:.2e} " + " ".join(out))
    return max(err_o, err_s) * 1e-2


def run_lightning_chunk_case(Lc, L, H, d, slots=8):
    """The chunked form (plain XLA, ops/lightning.py chunk_update) over an
    L-layer pool: one chunk of Lc tokens from a FRESH state, then the next
    from the CARRIED one, against the token-by-token recurrence; ms a
    layer's call for each."""
    from xllm_service_tpu.ops import lightning as lo

    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 2 * Lc, H, d), jnp.float32) for kk in ks)
    q = q * d ** -0.5
    log_lam = jnp.asarray(lo.log_decay(H, (12,), 32)[0])
    o_ref, S_ref = jax.jit(lo.recurrent_form)(q[0], k[0], v[0], log_lam)
    S = jnp.full(lo.state_shape(L, slots, H, d), 2.0, jnp.float32)
    slot = jnp.array([3], jnp.int32)

    def make(start):
        sl = slice(start, start + Lc)
        def run(S_, q_, k_, v_):
            def body(S_, l):
                o, S_ = lo.chunk_update(S_, l, slot, jnp.array([start]), jnp.array([Lc]),
                                        q_[:, sl], k_[:, sl], v_[:, sl], log_lam)
                return S_, o
            return jax.lax.scan(body, S_, jnp.arange(L, dtype=jnp.int32))
        jitted = jax.jit(run, donate_argnums=0)
        return lambda S_: jitted(S_, q, k, v)

    fresh, carried = make(0), make(Lc)
    S, o0 = fresh(S)
    S, o1 = carried(S)
    scale = float(jnp.abs(o_ref).max())
    err = max(float(jnp.abs(o0[L - 1, 0] - o_ref[:Lc]).max()),
              float(jnp.abs(o1[0, 0] - o_ref[Lc:]).max())) / scale
    err_s = float(jnp.abs(S[L - 1, 3] - S_ref).max()) / float(jnp.abs(S_ref).max())
    state = [S]

    def timed(fn):
        def once():
            state[0], o = fn(state[0])
            return o
        return bench(once, iters=4) / L

    t0, t1 = timed(fresh), timed(carried)
    print(f"LIGHTNING-CHUNK Lc={Lc} L={L} H={H} d={d} rel_err_o={err:.2e} rel_err_S={err_s:.2e} "
          f"fresh={t0*1e3:7.2f}ms carried={t1*1e3:7.2f}ms a layer")
    return max(err, err_s) * 1e-1


def run_sparse_case(R, Hq, Hkv, D, BS, CB, L, N, ctx_lo, ctx_hi, chunk, chunk_start):
    """minicpm-sala's selected-page launches over an L-layer pool of N
    pages: stage 1 + stage 2 of R decode rows at contexts ctx_lo..ctx_hi
    (the decode kernel a KV head a row, against the gather), the
    compressed-key write and the selected attention of one `chunk`-row
    prefill chunk that starts at `chunk_start` (its first ROW_TILE rows
    against the gather; the whole chunk timed). The pools are ARGUMENTS of
    every jitted function: closed over they would be constants of it."""
    from xllm_service_tpu.ops import kv_cache as kvc
    from xllm_service_tpu.ops import sparse_attention as sp

    sel = sp.Selection(BS, 64, 32, 16, 1, 32, 8192)
    ks = jax.random.split(jax.random.key(0), 5)
    mk = lambda kk: jax.jit(lambda k_: jax.lax.map(
        lambda k1: jax.random.normal(k1, (N, Hkv, BS, D), jnp.bfloat16),
        jax.random.split(k_, L)))(kk)
    K, V = mk(ks[0]), mk(ks[1])
    CK = jax.random.normal(ks[2], (L, N, Hkv * sel.per_block, D), jnp.float32) * 0.3
    pools = (K, V, CK)
    paged = lambda x: kvc.PagedKV(x, None)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(np.stack([rng.permutation(N - 1)[:CB] + 1 for _ in range(R)]), jnp.int32)
    positions = jnp.asarray(rng.integers(ctx_lo, ctx_hi, R) - 1, jnp.int32)
    active = jnp.ones((R,), bool)
    q = jax.random.normal(ks[3], (R, Hq, D), jnp.bfloat16)
    scale = D ** -0.5
    layer = jnp.int32(L - 1)
    dec = lambda use: jax.jit(lambda q_, K_, V_, CK_: sp.decode_attention(
        q_, paged(K_), paged(V_), CK_, layer, tables, positions, active, scale, sel,
        use_kernel=use))
    dec_k = dec(True)
    o_k, o_r = dec_k(q, *pools), dec(False)(q, *pools)
    err = float(jnp.abs(o_k.astype(jnp.float32) - o_r.astype(jnp.float32)).max())
    del o_r
    t_dec = bench(lambda: dec_k(q, *pools), iters=8)
    stage1 = jax.jit(lambda q_, CK_: sp.virtual_tables(
        q_, CK_, layer, tables, positions, active, scale, sel, sel.dense_blocks)[0])
    t_s1 = bench(lambda: stage1(q, CK), iters=8)
    need = R * Hkv * 64 * BS * 2 * D * 2
    print(f"SPARSE-DECODE R={R} ctx={ctx_lo}-{ctx_hi} err={err:.3e} stage1+2={t_dec*1e6:8.1f}us "
          f"stage1={t_s1*1e6:8.1f}us stage2_hbm_share={100*need/max(t_dec-t_s1,1e-9)/819e9:5.1f}%",
          flush=True)
    # one prefill chunk past dense_len
    qc = jax.random.normal(ks[4], (chunk, Hq, D), jnp.bfloat16)
    pos = chunk_start + jnp.arange(chunk, dtype=jnp.int32)
    live = jnp.ones((chunk,), bool)
    n = sp.ROW_TILE
    sel_fn = lambda use, rows: jax.jit(lambda q_, K_, V_, CK_: sp.chunk_selected_attention(
        q_, paged(K_), paged(V_), CK_, layer, tables[0], pos[:rows], live[:rows], scale, sel,
        use_kernel=use))
    whole = sel_fn(True, chunk)
    o_c = whole(qc, *pools)
    first = sel_fn(False, n)(qc[:n], *pools)
    err_c = float(jnp.abs(o_c[:n].astype(jnp.float32) - first.astype(jnp.float32)).max())
    del first
    t_c = bench(lambda: whole(qc, *pools), iters=4)
    s1 = jax.jit(lambda q_, CK_: jax.lax.map(lambda xs: sp.virtual_tables(
        xs[0], CK_, layer, tables[0], xs[1], xs[2], scale, sel, sel.topk)[0],
        (q_.reshape(-1, n, Hq, D), pos.reshape(-1, n), live.reshape(-1, n))))
    t_c1 = bench(lambda: s1(qc, CK), iters=4)
    # the selection alone (scores made once) at the three widths stage 1 switches between:
    # 16 tiles of ROW_TILE rows, each tile the last rows the width reaches
    pick = jax.jit(lambda s, p: jax.lax.map(lambda s1_: sp.select_blocks(s1_, p, sel), s))
    t_sel = {}
    for cols in (CB // 4, CB // 2, CB):
        sc = jax.random.uniform(ks[4], (16, n, Hkv, cols), jnp.float32)
        p_t = cols * BS - n + jnp.arange(n, dtype=jnp.int32)
        t_sel[cols] = bench(lambda: pick(sc, p_t), iters=8) / 16
    print("SPARSE-SELECT rows=%d x %d KV heads, us a tile: " % (n, Hkv)
          + " ".join(f"cols={c}:{t*1e6:7.1f}" for c, t in t_sel.items()), flush=True)
    wr = jax.jit(lambda ck, K_: sp.write_compressed(
        ck, paged(K_), layer, tables[:1], jnp.array([chunk_start]), jnp.array([chunk]), chunk,
        sel), donate_argnums=0)
    ck_box = [CK + 0.0]

    def write_once():
        ck_box[0] = wr(ck_box[0], K)
        return ck_box[0][0, 0]

    t_w = bench(write_once, iters=8)
    need_c = chunk * Hkv * 64 * BS * 2 * D * 2
    print(f"SPARSE-CHUNK rows={chunk} start={chunk_start} err={err_c:.3e} "
          f"stage1+2={t_c*1e3:7.2f}ms stage1={t_c1*1e3:7.2f}ms write={t_w*1e6:7.1f}us "
          f"stage2_hbm_share={100*need_c/max(t_c-t_c1,1e-9)/819e9:5.1f}%", flush=True)
    return max(err, err_c)


def run_moe_case(rows, X, E, F, K=8, L=4):
    """Both grouped expert kernels (`moe_grouped_kernel`,
    `moe_grouped_down_kernel`) as a step program calls them: the layers'
    stacked leaves of X held experts of width F and a layer index, `rows`
    token rows x K pairs sorted by expert (every token K DISTINCT experts,
    uniformly: no selection bias). Error against `expert_product_reference`
    (every expert over every row) on the first and the last layer; us a
    call, the two launches' own device time, the experts touched and the
    share of the roofline (the touched experts' matrices once, each pair's
    row in and out, over 819 GB/s; or the pairs' FLOPs over 197 TFLOP/s)."""
    from xllm_service_tpu.ops.moe import expert_product_reference
    from xllm_service_tpu.ops.pallas.moe_dispatch import moe_grouped_kernel

    rng = np.random.default_rng(0)
    chosen = np.argsort(rng.random((rows, X)), axis=1)[:, :K]
    sizes = np.bincount(chosen.reshape(-1), minlength=X).astype(np.int32)
    M = rows * K
    keys = jax.random.split(jax.random.key(0), 4)
    draw = lambda key, a, b: jax.jit(lambda: jax.lax.map(
        lambda k_: (jax.random.normal(k_, (X, a, b), jnp.float32) / np.sqrt(a)).astype(jnp.bfloat16),
        jax.random.split(key, L),  # a layer at a time on the chip
    ))()
    wg, wu, wd = draw(keys[0], E, F), draw(keys[1], E, F), draw(keys[2], F, E)
    xs = jax.random.normal(keys[3], (M, E), jnp.bfloat16)
    gs = jnp.asarray(sizes)
    # (the stacks are arguments: a closed-over array is a constant of the program)
    jker = jax.jit(lambda x, a, b, c, l: moe_grouped_kernel(x, gs, a, b, c, layer=l))
    ker = lambda l=0: jker(xs, wg, wu, wd, jnp.int32(l))
    jref = jax.jit(lambda x, a, b, c: expert_product_reference(x, gs, a, b, c))
    err = 0.0
    for l in (0, L - 1):
        got = np.asarray(ker(l).astype(jnp.float32))
        ref = np.asarray(jref(xs, wg[l], wu[l], wd[l]).astype(jnp.float32))
        err = max(err, float(np.max(np.abs(got - ref))))
    tk = bench(ker)
    up, n_up = device_us(ker, "moe_grouped_kernel")
    down, n_down = device_us(ker, "moe_grouped_down_kernel")
    touched = int((sizes > 0).sum())
    need_bytes = touched * 3 * E * F * 2 + 2 * M * E * 2
    floor = max(need_bytes / 819e9, M * 6 * E * F / 197e12)
    print(
        f"MOE rows={rows} pairs={M} X={X} E={E} F={F} L={L} touched={touched} "
        f"pairs/expert={M / X:.1f} err={err:.4f} call={tk*1e6:8.1f}us "
        f"gate_up={up:7.1f}us (x{n_up}) down={down:7.1f}us (x{n_down}) "
        f"floor={floor*1e6:7.1f}us roofline={100*floor/((up + down)*1e-6):5.1f}%"
    )
    return err


CASES = [
    # The decode kernel at the benchmark cells' own shapes (PERF.md, PR 40):
    # qwen2.5-3b.decode-batch (125 of 128 rows live, 256-768 tokens, table
    # bucket 8) and qwen2.5-3b.chat-steady (10 live, 256-2048, bucket 16),
    # each over the 36-layer stack of the cell's 958-block pool.
    ("cell-decode-batch", run_cell_case,
     dict(R=128, Hq=16, Hkv=2, D=128, BS=128, MB=8, L=36, N=958, live=125,
          ctx_lo=256, ctx_hi=768)),
    ("cell-chat-steady", run_cell_case,
     dict(R=128, Hq=16, Hkv=2, D=128, BS=128, MB=16, L=36, N=958, live=10,
          ctx_lo=256, ctx_hi=2048)),
    # The window launch of mimo-v2-flash.longmix-steady (PR 50): 18 of 64
    # rows live, 8 KV heads of 256 (192 padded) | 128 lanes, window 128 with a sink, over
    # the cut's 6 window layers; and the step's cache write at the two
    # qwen2.5-3b cells' own shapes: one new row for 9 and for 125 of 128
    # slots into the 36-layer pool.
    ("cell-longmix-window", run_cell_case,
     dict(R=64, Hq=64, Hkv=8, D=256, Dv=128, BS=128, MB=32, L=6, N=700,
          live=18, ctx_lo=512, ctx_hi=4096, window=128, sink=True)),
    ("kv-write-chat-steady", run_kv_write_case,
     dict(S=128, live=9, L=36, N=958, Hc=2, D=128, BS=128)),
    ("kv-write-decode-batch", run_kv_write_case,
     dict(S=128, live=125, L=36, N=958, Hc=2, D=128, BS=128)),
    # The Mamba-2 state pool at granite-4.0-h-small's widths (PERF.md, PR
    # 42): the decode update over the cell's 9-layer pool of 64 slots with
    # 44 rows live, and one 256-token chunk from a fresh and from a carried
    # state (the chunk form is plain XLA).
    ("mamba-update-cell", run_mamba_update_case,
     dict(R=64, live=44, L=9, H=128, P=64, N=128)),
    ("mamba-update-tile8", run_mamba_update_case,
     dict(R=64, live=44, L=9, H=128, P=64, N=128, tile=8)),
    ("mamba-update-tile32", run_mamba_update_case,
     dict(R=64, live=44, L=9, H=128, P=64, N=128, tile=32)),
    ("mamba-chunk", run_mamba_chunk_case, dict(Lc=256, L=9, H=128, P=64, N=128)),
    # falcon-h1-34b.dialog-steady's launches (PERF.md, PR 53): the update
    # kernel at TWO B/C groups, state 256 and one 128-lane head a lane row
    # (a 128 KiB plane) over the cut's 9 layers with 32 of 64 rows live,
    # at 8 (head_tile's choice) and 16 lane rows a grid step (Mosaic takes
    # no tile under 8 sublanes of the per-head operands); the chunk
    # form at that shape; and the attention launches at a query group of 5
    # (20 / 4 heads of 128): decode over contexts of 256-3840, the cache
    # write, and one 256-token chunk through the flash-prefill kernel.
    ("mamba-update-dialog", run_mamba_update_case,
     dict(R=64, live=32, L=9, H=32, P=128, N=256, G=2)),
    ("mamba-update-dialog-tile16", run_mamba_update_case,
     dict(R=64, live=32, L=9, H=32, P=128, N=256, G=2, tile=16)),
    ("mamba-chunk-dialog", run_mamba_chunk_case,
     dict(Lc=256, L=9, H=32, P=128, N=256, G=2)),
    ("cell-dialog-decode", run_cell_case,
     dict(R=64, Hq=20, Hkv=4, D=128, BS=128, MB=32, L=9, N=800, live=32,
          ctx_lo=256, ctx_hi=3840)),
    ("kv-write-dialog", run_kv_write_case,
     dict(S=64, live=32, L=9, N=800, Hc=4, D=128, BS=128)),
    ("prefill-group5", run_prefill_case,
     dict(P=1, Lpad=256, Hq=20, Hkv=4, D=128, BS=128, MB=24)),
    # minicpm-sala.longdoc-steady's launches (PERF.md, PR 56): the lightning
    # update kernel over the cut's 6 lightning layers with 20 of 32 rows
    # live against its XLA route; the chunked form at the cell's 4,096-row
    # chunk against the recurrence; and the selected-page path: 32 decode
    # rows at contexts of 9k-49k and one 4,096-row chunk at 28,672 through
    # the decode kernel a KV head a row (a query group of 16, pages of 64,
    # tables of 1,024 columns), with stage 1 and the compressed-key write
    # timed apart.
    ("lightning-update-longdoc", run_lightning_update_case,
     dict(R=32, live=20, L=6, H=32, d=128)),
    ("lightning-chunk-longdoc", run_lightning_chunk_case,
     dict(Lc=4096, L=6, H=32, d=128)),
    ("sparse-longdoc", run_sparse_case,
     dict(R=32, Hq=32, Hkv=2, D=128, BS=64, CB=1024, L=2, N=20000, ctx_lo=9000,
          ctx_hi=49000, chunk=4096, chunk_start=28672)),
    # laguna-xs.2.agent-steady's launches (PERF.md, PR 60): the decode kernel
    # at a query group of 6 (48 / 8 heads of 128, padded to 8 sublanes) over
    # the cut's 2 full layers, 16 of 64 rows live at contexts of 1k-8k (a
    # 72-column table: the gather oracle reads every column of every row, and
    # the cell's 264 would be 4 GB of it); the window launch at 64 / 8 heads over the 3 window
    # layers, a window of 512 positions (FOUR blocks) and no sink; one
    # 512-token chunk through the flash kernel at each geometry; and both
    # grouped expert kernels at 256 held experts of width 512: a decode step
    # of 64 rows (2 pairs an expert, ~221 touched) and a mixed step of 576
    # (18 an expert: 36 row tiles met by 256 experts).
    ("cell-agent-full", run_cell_case,
     dict(R=64, Hq=48, Hkv=8, D=128, BS=128, MB=72, L=2, N=1200, live=16,
          ctx_lo=1024, ctx_hi=8192)),
    ("cell-agent-window", run_cell_case,
     dict(R=64, Hq=64, Hkv=8, D=128, BS=128, MB=72, L=3, N=1200, live=16,
          ctx_lo=1024, ctx_hi=8192, window=512)),
    ("prefill-group6", run_prefill_case,
     dict(P=1, Lpad=512, Hq=48, Hkv=8, D=128, BS=128, MB=24)),
    ("prefill-window4", run_prefill_case,
     dict(P=1, Lpad=512, Hq=64, Hkv=8, D=128, BS=128, MB=24, window=512)),
    ("moe-agent-decode", run_moe_case, dict(rows=64, X=256, E=2048, F=512)),
    ("moe-agent-mixed", run_moe_case, dict(rows=576, X=256, E=2048, F=512)),
    # solar-open2-250b.think-steady's chunk (PERF.md, PR 54): the decayed
    # gram of one 512-token prefill chunk of a KDA layer, 64 heads of 128
    # lanes in 8 chunks of 64: its diagonal sub-blocks as `kda_gram_kernel`
    # against the two XLA fusions with the 268 MB pair tensor between them.
    ("kda-gram-think", run_kda_gram_case, dict(n=8, H=64, d=128)),
    # ... and the whole chunk form of that chunk against a carried state:
    # `kda_chunk_kernel` against the XLA form around the gram kernel
    ("kda-chunk-think", run_kda_chunk_case, dict(Lc=512, H=64, d=128)),
    # int8 KV cache (scale DMA + column folding) at production block size
    ("dec-int8-a", run_case,
     dict(R=64, Hq=32, Hkv=8, D=128, BS=128, MB=16, ctx=2048, int8=True)),
    ("dec-int8-b", run_case,
     dict(R=64, Hq=24, Hkv=8, D=128, BS=128, MB=16, ctx=2048, int8=True)),
    # MLA decode kernel (DeepSeek-V3 geometry: kvr=512, dr=64, Hq=128)
    ("mla-dec-v3", run_mla_case,
     dict(R=32, Hq=128, kvr=512, dr=64, BS=128, MB=16, ctx=2048)),
    ("mla-dec-sm", run_mla_case,
     dict(R=8, Hq=16, kvr=160, dr=32, BS=128, MB=32, ctx=4096)),
    # Flash prefill kernels: llama-8B-class chunked prefill at the
    # production block size, bf16 + int8, and the MLA (V3) prefill
    ("prefill-a", run_prefill_case,
     dict(P=4, Lpad=512, Hq=32, Hkv=8, D=128, BS=128, MB=8)),
    ("prefill-b", run_prefill_case,
     dict(P=8, Lpad=1024, Hq=32, Hkv=8, D=128, BS=128, MB=12)),
    ("prefill-int8", run_prefill_case,
     dict(P=4, Lpad=512, Hq=32, Hkv=8, D=128, BS=128, MB=8, int8=True)),
    ("mla-prefill", run_mla_prefill_case,
     dict(P=2, Lpad=512, Hq=128, kvr=512, dr=64, BS=128, MB=8)),
    # deepseek-v2.doc-steady's chunk (PERF.md, PR 55): one 512-row chunk
    # of 128 heads under the cell's one context bucket (64 blocks), in
    # the absorbed and in the materialised form, at five cached lengths.
    ("mla-prefill-forms", run_mla_prefill_forms_case,
     dict(Hq=128, kvr=512, dn=128, dr=64, dv=128, BS=128, Lpad=512, MB=64,
          contexts=(0, 512, 1536, 3584, 7168))),
    # Multi-query decode (speculative verify) at production shapes
    ("mq-bf16", run_mq_case,
     dict(R=64, S=4, Hq=32, Hkv=8, D=128, BS=128, MB=16, ctx=2048)),
    ("mq-int8", run_mq_case,
     dict(R=64, S=4, Hq=32, Hkv=8, D=128, BS=128, MB=16, ctx=2048,
          int8=True)),
    ("mq-mla", run_mla_mq_case,
     dict(R=32, S=4, Hq=128, kvr=512, dr=64, BS=128, MB=16, ctx=2048)),
    # int8 latent caches through the MLA kernels (VMEM dequant via the
    # scale-expansion matmul)
    ("mla-dec-int8", run_mla_case,
     dict(R=32, Hq=128, kvr=512, dr=64, BS=128, MB=16, ctx=2048,
          int8=True)),
    ("mq-mla-int8", run_mla_mq_case,
     dict(R=32, S=4, Hq=128, kvr=512, dr=64, BS=128, MB=16, ctx=2048,
          int8=True)),
    ("mla-prefill-int8", run_mla_prefill_case,
     dict(P=2, Lpad=512, Hq=128, kvr=512, dr=64, BS=128, MB=8,
          int8=True)),
    # Sliding-window attention (round-4 flash-prefill window + the
    # decode kernel's window path — masking AND the below-window block
    # skip have never run on silicon)
    ("prefill-swa", run_prefill_case,
     dict(P=4, Lpad=512, Hq=32, Hkv=8, D=128, BS=128, MB=8, window=256)),
    ("dec-swa", run_case,
     dict(R=64, Hq=32, Hkv=8, D=128, BS=128, MB=16, ctx=2048, window=512)),
    # Packed-pair head_dim-64 decode (llama3-1b geometry: Hq=32 Hkv=8)
    ("dec-packed-bf16", run_packed_case,
     dict(R=64, Hq=32, Hkv=8, D=64, BS=128, MB=16, ctx=2048)),
    ("dec-packed-int8", run_packed_case,
     dict(R=64, Hq=32, Hkv=8, D=64, BS=128, MB=16, ctx=2048, int8=True)),
    # bf16 decode (re-validated round 2; re-run last)
    ("dec-bf16-prod", run_case,
     dict(R=64, Hq=32, Hkv=8, D=128, BS=128, MB=16, ctx=2048)),
    ("dec-bf16-r8", run_case,
     dict(R=8, Hq=32, Hkv=8, D=128, BS=16, MB=64, ctx=1024)),
    ("dec-bf16-r32", run_case,
     dict(R=32, Hq=32, Hkv=8, D=128, BS=16, MB=64, ctx=1024)),
    ("dec-bf16-r64", run_case,
     dict(R=64, Hq=32, Hkv=8, D=128, BS=16, MB=128, ctx=2048)),
    ("dec-bf16-h64", run_case,
     dict(R=32, Hq=64, Hkv=8, D=128, BS=16, MB=64, ctx=1024)),
    ("dec-bf16-4k", run_case,
     dict(R=16, Hq=32, Hkv=8, D=128, BS=16, MB=256, ctx=4096)),
]


def main(argv):
    if "--list" in argv:
        for i, (name, _, _) in enumerate(CASES):
            print(i, name, 0 if name.startswith("dec-bf16") else 1)
        return
    sel = range(len(CASES))
    if "--case" in argv:
        # one index or name, or several joined by commas
        names = [name for name, _, _ in CASES]
        try:
            sel = [
                int(a) if a.isdigit() else names.index(a)
                for a in argv[argv.index("--case") + 1].split(",")
            ]
            assert all(0 <= i < len(CASES) for i in sel)
        except (IndexError, ValueError, AssertionError):
            sys.exit(
                f"usage: --case N[,N...] with 0 <= N < {len(CASES)}, or names "
                f"of --list"
            )
    print(f"backend={jax.default_backend()} device={jax.devices()[0]}",
          flush=True)
    assert jax.default_backend() == "tpu"
    errs = []
    for i in sel:
        name, fn, kw = CASES[i]
        print(f"[case {i} {name}]", flush=True)
        errs.append(fn(**kw))
    assert max(errs) < 0.05, f"parity FAIL: {errs}"
    print("PARITY OK", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
