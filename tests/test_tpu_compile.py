"""Compile the main serving path for a DESCRIBED TPU v5e chip, no chip
attached: the TPU compiler is installed in the sandbox and refuses what
the chip's compiler would refuse (misaligned slices, VMEM overflow, a
program that does not fit HBM). Nothing runs, so these say nothing about
results or times — chip_smoke.py does that on the chip.

llama3-3b widths throughout (Hq 24, Hkv 8, D 128, block 128, bf16). The
topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports every
test file), compiles happen in this process, and the persistent compile
cache is off around them (a described-device executable cannot be read
back without a chip).
"""

import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import get_model_config
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops import kv_write as kvw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("llama3-3b")
HQ, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
BS, NB = 128, 512
R = 32  # decode slots
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The dispatchers ask the attached backend, which is the CPU here:
    steer them onto the kernel branch the chip would take."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    # a tp > 1 executor built earlier on this worker's thread leaves its
    # mesh declared (the context is per thread and read at trace time); the
    # programs here are one chip's unless a test declares its own
    attention.set_shard_context(None)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _assert_pool_still(fn, args, cache, shards=1):
    """Compile a step with its caches donated, as the executor does, and
    hold the chip's program to what tests/test_kv_pool_still.py holds the
    CPU's to: the new rows go in through kv_write_kernel, nothing has a
    layer- or stack-shaped result but that write and the loop's own
    plumbing, and the temporaries are smaller than one layer of one pool.
    On a tp mesh the compiled module is one shard's: the pool holds
    1/`shards` of the heads there (a gathered pool would have the whole
    shape, so that is looked for too). Returns the HLO text."""
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kv_write_kernel" in text
    data = kvc.raw(cache)  # (an int8 pool's scale planes are a 32nd of it)
    whole, stack = tuple(data.shape), list(data.shape)
    stack[2] //= shards
    shapes = {
        ",".join(map(str, s)) for s in (whole, whole[1:], stack, stack[1:])
    }
    _assert_nothing_moves(text, shapes)
    layer_bytes = data.dtype.itemsize * NB * (HKV // shards) * BS * D
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    return text


def _assert_nothing_moves(text, shapes):
    """No op of the compiled program has a result of one of `shapes`
    (comma-joined dims) but the loop's own plumbing and the kernels."""
    import re

    plumbing = {
        "parameter", "get-tuple-element", "tuple", "bitcast", "while",
        "custom-call",
    }
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        dims = m and re.match(r"\w+\[([\d,]*)\]", m.group(1))
        if dims and dims.group(1) in shapes and m.group(2) not in plumbing:
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)


def _kernel_shapes(one_chip):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = s((NB, HKV, BS, D))
    return s, cache


def _scan_body_lists(text, kernel):
    """Lines of the compiled program's computation that launches `kernel`
    (the layer scan's body) whose op makes a list: a sort, a running sum
    (`reduce-window`) or a scatter."""
    import re

    for m in re.finditer(r"^%?[\w.\-]+ \([^\n]*\{\n(.*?)^\}", text, re.S | re.M):
        if re.search(rf"%{kernel}(?:\.\d+)? = [^\n]* custom-call\(", m.group(1)):
            return [
                line.strip()[:120] for line in m.group(1).splitlines()
                if re.search(r"[\])}] (sort|reduce-window|scatter)\(", line)
            ]
    raise AssertionError(f"no computation launches {kernel}")


def _kernel_calls(text, name):
    """Custom calls of the compiled program whose Pallas name is `name`."""
    import re

    return len(re.findall(rf"%{name}(?:\.\d+)? = [^\n]* custom-call\(", text))


@pytest.mark.parametrize(
    "rows,hq,hkv,table,quantized",
    [(R, HQ, HKV, 16, False), (128, 16, 2, 8, False), (128, 16, 2, 16, False),
     (R, HQ, HKV, 16, True), (R, HQ, 1, 4, False)],
    ids=["llama3-3b", "decode-batch-cell", "chat-steady-cell", "int8",
         "one-head-shard"],
)
def test_decode_kernel_compiles(
    one_chip, no_persistent_cache, rows, hq, hkv, table, quantized
):
    """Mosaic takes the decode kernel's schedule (SMEM hand-over scratch,
    both grid axes "arbitrary", two heads a descriptor, DMAs under
    `pl.when`) at llama3-3b's widths, at the two qwen2.5-3b cells' own
    (128 rows, 16/2 heads, table buckets 8 and 16), on an int8 pool and on
    a shard that holds one KV head (no fold)."""
    from xllm_service_tpu.ops.pallas.paged_attention import (
        paged_attention_kernel,
    )

    s, _ = _kernel_shapes(one_chip)
    if quantized:
        cache = kvc.PagedKV(
            s((NB, hkv, BS, D), jnp.int8),
            s((NB, hkv, kvc.GQA_SCALE_GROUPS, BS), jnp.float32),
        )
    else:
        cache = s((NB, hkv, BS, D))
    text = _compile(
        lambda q, k, v, bt, sl: paged_attention_kernel(q, k, v, bt, sl, SCALE),
        s((rows, hq, D)), cache, cache,
        s((rows, table), jnp.int32), s((rows,), jnp.int32),
    )
    assert "tpu_custom_call" in text
    assert _kernel_calls(text, "paged_attention_kernel") == 1


def test_window_decode_kernel_compiles_at_longmix_widths(
    one_chip, no_persistent_cache
):
    """The window launch of mimo-v2-flash.longmix-steady: 64 rows of 8 KV
    heads, key rows of 256 lanes (192 padded) beside value rows of 128, a
    window of 128 tokens with a sink a head, tables 128 blocks wide. Its
    q and o tiles ride blocks of 16 rows, so the index maps read the list
    of walked rows (a dynamic grid bound AND a scalar-prefetch read in an
    index map are what Mosaic has to take)."""
    from xllm_service_tpu.ops.pallas import paged_attention as pa

    s, _ = _kernel_shapes(one_chip)
    assert pa._row_block(64, 8 * 8 * 256 * 2) == 16
    text = _compile(
        lambda q, k, v, bt, sl, sinks: pa.paged_attention_kernel(
            q, k, v, bt, sl, SCALE, window=128, layer=jnp.int32(3),
            sinks=sinks,
        ),
        s((64, 64, 256)), s((6, 700, 8, BS, 256)), s((6, 700, 8, BS, 128)),
        s((64, 128), jnp.int32), s((64,), jnp.int32), s((64,), jnp.float32),
    )
    assert _kernel_calls(text, "window_paged_attention_kernel") == 1


def test_multiquery_verify_kernel_compiles(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.paged_attention import (
        multiquery_paged_attention_kernel,
    )

    s, cache = _kernel_shapes(one_chip)
    text = _compile(
        lambda q, k, v, bt, sl: multiquery_paged_attention_kernel(
            q, k, v, bt, sl, SCALE
        ),
        s((R, 4, HQ, D)), cache, cache,
        s((R, 16), jnp.int32), s((R,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_prefill_kernel_compiles(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    s, cache = _kernel_shapes(one_chip)
    text = _compile(
        lambda q, k, v, bt, sp, tl: flash_prefill_kernel(
            q, k, v, bt, sp, tl, SCALE
        ),
        s((4, 512, HQ, D)), cache, cache,
        s((4, 16), jnp.int32), s((4,), jnp.int32), s((4,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "pools,rows,width",
    [
        (((36, 958, 2, BS, D),) * 2, 128, 1),
        (((5, 2900, 1, BS, 640),), 64, 1),
        (((5, 2900, 1, BS, 640),), 1, 512),
        (((6, 700, 8, BS, 256), (6, 700, 8, BS, 128)), 64, 1),
    ],
    ids=["qwen-cells-decode", "latent-decode", "latent-chunk",
         "window-pools-decode"],
)
def test_kv_write_kernel_compiles_at_the_cells_shapes(
    one_chip, no_persistent_cache, as_on_tpu, pools, rows, width
):
    """The write as the benchmark's cells launch it: 128 slots into the
    qwen2.5-3b cells' K and V stacks, 64 slots and one 512-token chunk
    into deepseek-v2's one latent stack (640 lanes, one head), 64 slots
    into mimo-v2-flash's window pools (256 | 128 lanes, 8 heads). The grid
    is bounded by the live units and the new rows' tiles are indexed
    through the plan's order: Mosaic takes both for every shape."""
    s, _ = _kernel_shapes(one_chip)
    caches = tuple(s(shape) for shape in pools)

    def write(caches, tables, start, length, new):
        plan = kvw.write_plan(caches[0], tables, start, length, width)
        assert plan.units is not None
        return kvw.write_rows(caches, plan, new, jnp.int32(1))

    new = tuple(s((rows * width, sh[2], sh[4])) for sh in pools)
    text = jax.jit(write, donate_argnums=0).lower(
        caches, s((rows, 64), jnp.int32), s((rows,), jnp.int32),
        s((rows,), jnp.int32), new,
    ).compile().as_text()
    assert _kernel_calls(text, "kv_write_kernel") == 1


@pytest.mark.parametrize(
    "quantized,rows,width", [(False, R, 1), (False, 2, 256), (True, R, 4)],
    ids=["decode-bf16", "chunk-bf16", "verify-int8"],
)
def test_kv_write_kernel_compiles(
    one_chip, no_persistent_cache, as_on_tpu, quantized, rows, width
):
    """The in-place write of a step's rows into the stacked pool
    (ops/pallas/kv_write.py through kv_write.write_kv): Mosaic takes the
    tile shapes of every row layout and both pool dtypes."""
    s, _ = _kernel_shapes(one_chip)
    if quantized:
        cache = kvc.PagedKV(
            s((2, NB, HKV, BS, D), jnp.int8),
            s((2, NB, HKV, kvc.GQA_SCALE_GROUPS, BS), jnp.float32),
        )
    else:
        cache = kvc.PagedKV(s((2, NB, HKV, BS, D)), None)

    def write(k, v, tables, start, length, rows_k, rows_v):
        plan = kvw.write_plan(k, tables, start, length, width)
        assert plan.units is not None
        return kvw.write_kv(k, v, plan, rows_k, rows_v, jnp.int32(1))

    new = s((rows * width, HKV, D))
    text = jax.jit(write, donate_argnums=(0, 1)).lower(
        cache, cache, s((rows, 16), jnp.int32), s((rows,), jnp.int32),
        s((rows,), jnp.int32), new, new,
    ).compile().as_text()
    assert text.count("kv_write_kernel") >= (2 if quantized else 1)


def _model_shapes(one_chip, layers=2):
    """llama3-3b at full widths cut to `layers` layers: abstract params
    and caches placed on the described chip (no arrays exist)."""
    cfg = dataclasses.replace(CFG, num_layers=layers)

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree,
        )

    params = place(
        jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0), jnp.bfloat16)
        )
    )
    cache = jax.ShapeDtypeStruct(
        (layers, NB, HKV, BS, D), jnp.bfloat16, sharding=one_chip
    )

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return cfg, params, cache, s


def _step_case(step, cfg, s):
    """(function of (params, k, v, *rest), rest) for one of the four
    cache-threading steps of models/llama.py, at R decode or verify rows
    and two 256-token chunks."""
    P, Lpad, S = 2, 256, 4
    dec = (s((R,)), s((R,)), s((R, 16)), s((R,), jnp.bool_))
    pf = (s((P, Lpad)), s((P,)), s((P,)), s((P, 16)))
    fn, rest = {
        "decode": (llama.decode_step, dec),
        "mixed": (llama.mixed_step, dec + pf),
        "prefill": (llama.prefill_batch_step, pf),
        "mixed-verify": (
            llama.mixed_verify_step,
            (s((R, S)), s((R,)), s((R,)), s((R, 16))) + pf,
        ),
    }[step]
    return (lambda p, k, v, *a: fn(p, cfg, k, v, *a)), rest


@pytest.mark.parametrize(
    "step,quantized",
    [("decode", False), ("mixed", False), ("prefill", False),
     ("mixed-verify", False), ("decode", True), ("mixed-verify", True)],
    ids=["decode", "mixed", "prefill", "mixed-verify", "decode-int8",
         "mixed-verify-int8"],
)
def test_step_compiles_and_keeps_the_pool_still(
    one_chip, no_persistent_cache, as_on_tpu, step, quantized
):
    cfg, params, cache, s = _model_shapes(one_chip)
    if quantized:
        cache = kvc.PagedKV(
            s(cache.shape, jnp.int8),
            s((*cache.shape[:3], kvc.GQA_SCALE_GROUPS, BS), jnp.float32),
        )
    fn, rest = _step_case(step, cfg, s)
    text = _assert_pool_still(fn, (params, cache, cache) + rest, cache)
    if step == "decode":
        # one launch a scanned layer: the device trace's readers
        # (`paged_attention_roofline.batch`) find the kernel by this name
        assert _kernel_calls(text, "paged_attention_kernel") == 1
    # The lists the two launches walk (the plan's live-first order, the
    # decode kernel's rows) are made once a step: nothing of a sort, a
    # running sum or a scatter rides the layer scan's body.
    listed = _scan_body_lists(text, "kv_write_kernel")
    assert not listed, "\n".join(listed)


def _tp4_shapes(topo, layers=2):
    """_model_shapes on the described 2x2 host: tp = 4 over its four
    chips, parameters and pools sharded as the executor shards them."""
    from xllm_service_tpu.parallel import mesh as mesh_lib, sharding

    cfg = dataclasses.replace(CFG, num_layers=layers)
    mesh = mesh_lib.build_mesh(tp=4, devices=topo.devices)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0), jnp.bfloat16)
    )
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, sharding.param_shardings(cfg, mesh),
    )
    cache = jax.ShapeDtypeStruct(
        (layers, NB, HKV, BS, D), jnp.bfloat16,
        sharding=sharding.kv_cache_sharding(mesh),
    )

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=mesh_lib.replicated(mesh)
        )

    return cfg, mesh, params, cache, s


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_tp4_step_compiles(topo, no_persistent_cache, as_on_tpu, step):
    """The tensor-parallel programs of a 2x2 host (no cell runs them yet):
    the pool is sharded over its KV heads, the attention kernels and the
    in-place write launch once per shard on the shard's stack (shard_map),
    and the shard's pool stays as still as the one chip's."""
    cfg, mesh, params, cache, s = _tp4_shapes(topo)
    fn, rest = _step_case(step, cfg, s)
    attention.set_shard_context(mesh)
    try:
        with mesh:
            text = _assert_pool_still(
                fn, (params, cache, cache) + rest, cache, shards=4
            )
    finally:
        attention.set_shard_context(None)
    assert "all-reduce" in text
    # per shard: the kernels' cache operand is the shard's stack
    shard = f"bf16[2,{NB},{HKV // 4},{BS},{D}]"
    assert any(
        "tpu_custom_call" in line and shard in line
        for line in text.splitlines()
    )


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py with no TPU (and no rehearsal option) fails before it
    builds a model, says so, and never prints the ok line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
    assert "building" not in proc.stdout


# ---- the power-retention family (models/brumby.py), brumby-14b's widths


def _brumby_case(one_chip, step, layers=2, slots=16):
    from xllm_service_tpu.models import brumby

    cfg = dataclasses.replace(get_model_config("brumby-14b"), num_layers=layers)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: brumby.init_params(cfg, jax.random.key(0), jnp.bfloat16)
        ),
    )

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, z = (s(sh, jnp.float32) for sh in brumby.state_shapes(cfg, slots))
    dec = (s((slots,)), s((slots,)), s((slots, 1)), s((slots,), jnp.bool_))
    pf = (s((1, 256)), s((1,)), s((1,)), s((1, 1)))
    fn, rest = {
        "decode": (brumby.decode_step, dec),
        "mixed": (brumby.mixed_step, dec + pf),
    }[step]
    return (lambda p, S, z, *a: fn(p, cfg, S, z, *a)), (params, S, z) + rest, S


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_brumby_step_compiles_and_keeps_the_state_pool_still(
    one_chip, no_persistent_cache, as_on_tpu, step
):
    """The decode and mixed programs of the state-pool family at
    brumby-14b's widths (2 layers, 16 slots): Mosaic takes both retention
    kernels, the pool goes in and out through them alone (nothing else
    has a pool- or layer-shaped result), and the temporaries are under
    one layer of the pool."""
    fn, args, S = _brumby_case(one_chip, step)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "retention_update_kernel" in text
    assert ("retention_chunk_kernel" in text) == (step == "mixed")
    whole = tuple(S.shape)
    _assert_nothing_moves(text, {",".join(map(str, sh)) for sh in (whole, whole[1:])})
    layer_bytes = 4
    for n in whole[1:]:
        layer_bytes *= n
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


# ---- PR 39: the MLA kernels and the grouped expert kernels at DeepSeek-V2's
# published widths (128 heads x 640 lanes against a one-head latent row;
# 40 held experts of 5120 x 1536), and the cut configuration's whole mixed
# step with its latent stack donated.

MLA_H, MLA_C, MLA_KVR, MLA_L = 128, 640, 512, 5


def _mla_shapes(one_chip):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return s, s((MLA_L, NB, 1, BS, MLA_C))


def test_mla_decode_kernel_compiles_at_published_widths(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.mla_attention import mla_attention_kernel

    s, stack = _mla_shapes(one_chip)
    text = _compile(
        lambda q, c, bt, sl, l: mla_attention_kernel(q, c, bt, sl, 0.1, MLA_KVR, layer=l),
        s((64, MLA_H, MLA_C)), stack, s((64, 64), jnp.int32), s((64,), jnp.int32),
        s((), jnp.int32),
    )
    assert "tpu_custom_call" in text and "mla_paged_attention_kernel" in text


def test_mla_prefill_kernel_compiles_at_published_widths(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.mla_prefill import mla_flash_prefill_kernel

    s, stack = _mla_shapes(one_chip)
    text = _compile(
        lambda q, c, bt, sp, tl, l: mla_flash_prefill_kernel(
            q, c, bt, sp, tl, 0.1, MLA_KVR, layer=l),
        s((1, 512, MLA_H, MLA_C)), stack, s((1, 64), jnp.int32), s((1,), jnp.int32),
        s((1,), jnp.int32), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text and "mla_prefill_kernel" in text


@pytest.mark.parametrize("rows", [256, 512])
def test_mla_materialised_prefill_kernel_compiles_at_published_widths(
    one_chip, no_persistent_cache, rows
):
    """The materialised form (PR 55) at DeepSeek-V2's widths: one chunk of
    `rows` rows (the rule's bound and the cell's bucket), 128 heads whose
    queries lie as the projection wrote them (192 lanes a head: every
    other head's slice starts mid-tile), W_UK / W_UV a layer's, the table
    as wide as the cell's one context bucket."""
    from xllm_service_tpu.ops.pallas.mla_prefill import mla_materialised_prefill_kernel

    s, stack = _mla_shapes(one_chip)
    text = _compile(
        lambda q, qp, wk, wv, c, bt, sp, tl, l: mla_materialised_prefill_kernel(
            q, qp, wk, wv, c, bt, sp, tl, 0.1, MLA_KVR, layer=l),
        s((1, rows, MLA_H, 192)), s((1, rows, MLA_H, 64)), s((MLA_H, MLA_KVR, 128)),
        s((MLA_H, MLA_KVR, 128)), stack, s((1, 64), jnp.int32), s((1,), jnp.int32),
        s((1,), jnp.int32), s((), jnp.int32),
    )
    assert "tpu_custom_call" in text and "mla_materialised_prefill_kernel" in text
    # the absorbed kernel's readers match by prefix: this op is none of theirs
    assert not re.search(r"\bmla_prefill_kernel", text)


@pytest.mark.parametrize("pairs", [384, 3456])
def test_grouped_expert_kernels_compile_at_published_widths(one_chip, no_persistent_cache, pairs):
    """64 decode rows x 6 and a 512-token chunk beside them x 6: the
    layers' stacked leaves and a layer index, no layer sliced out."""
    from xllm_service_tpu.ops.pallas.moe_dispatch import moe_grouped_kernel

    s, _ = _mla_shapes(one_chip)
    text = _compile(
        lambda x, g, a, b, c, l: moe_grouped_kernel(x, g, a, b, c, layer=l),
        s((pairs, 5120)), s((40,), jnp.int32), s((4, 40, 5120, 1536)),
        s((4, 40, 5120, 1536)), s((4, 40, 1536, 5120)), s((), jnp.int32),
    )
    assert "moe_grouped_kernel" in text and "moe_grouped_down_kernel" in text
    _assert_nothing_moves(text, {"4,40,5120,1536", "40,5120,1536", "4,40,1536,5120", "40,1536,5120"})


def test_deepseek_v2_mixed_step_compiles_and_its_stack_stays(one_chip, no_persistent_cache, as_on_tpu):
    """DeepSeek-V2 as the benchmark cuts it (5 layers, experts 0-39 of
    160, 25,600 vocabulary rows): the whole mixed step fits the chip
    beside 10.33 GB of weights, the latent stack and the expert leaves
    are read where they lie, and the temporaries stay under 0.5 GB."""
    from xllm_service_tpu.models import deepseek

    cfg = dataclasses.replace(
        get_model_config("deepseek-v2"), num_layers=5, vocab_size=25600, experts_held=(0, 40)
    )
    s, _ = _mla_shapes(one_chip)
    params = jax.eval_shape(lambda k: deepseek.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    nb, i32 = 2900, jnp.int32
    stack, dummy = s((5, nb, 1, BS, MLA_C)), s((5, 1, 1, 1, 1))
    compiled = jax.jit(
        lambda p, k, v, *a: deepseek.mixed_step(p, cfg, k, v, *a), donate_argnums=(1, 2)
    ).lower(
        params, stack, dummy, s((64,), i32), s((64,), i32), s((64, 64), i32), s((64,), jnp.bool_),
        s((1, 512), i32), s((1,), i32), s((1,), i32), s((1, 64), i32),
    ).compile()
    text = compiled.as_text()
    for kernel in ("kv_write_kernel", "mla_paged_attention_kernel", "mla_materialised_prefill_kernel",
                   "moe_grouped_kernel", "moe_grouped_down_kernel"):
        assert kernel in text, kernel
    # a 512-row chunk over the bf16 stack takes the materialised form (PR
    # 55): no op of the program starts with the absorbed kernel's name, the
    # chunk's queries reach the launch as the projection wrote them
    # (sliced by rows, never re-laid), and nothing as large as the chunk's
    # absorbed queries or latent context is written
    assert not re.search(r"\bmla_prefill_kernel", text)
    launch = next(l for l in text.splitlines() if " custom-call(" in l and "mla_materialised_prefill_kernel" in l)
    q_operand = re.sub(r"/\*.*?\*/", "", launch.split("custom-call(")[1]).split(",")[5].strip().lstrip("%")
    producer = next(l for l in text.splitlines() if re.match(rf"\s*%?{re.escape(q_operand)} = ", l))
    assert re.search(r" (bitcast|slice)\(", producer), producer[:200]
    assert "512,128,640" not in text and "512,128,512" not in text
    _assert_nothing_moves(text, {f"5,{nb},1,128,640", f"{nb},1,128,640", "4,40,5120,1536",
                                 "40,5120,1536", "4,40,1536,5120", "40,1536,5120"})
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
    assert 12.0e9 < mem.argument_size_in_bytes < 13.5e9  # weights + a 2.4 GB stack


def test_granite_mixed_step_compiles_and_its_pools_stay(one_chip, no_persistent_cache, as_on_tpu, monkeypatch):
    """granite-4.0-h-small as the benchmark cuts it (9 Mamba-2 layers + 1
    GQA layer, experts 0-35 of 72, 50,176 vocabulary rows): the whole mixed
    step fits the chip beside 9.51 GB of weights and a 64-slot state pool,
    with the update kernel, the K/V write, both attention kernels and the
    two grouped expert kernels in it; the SSM pool, the convolution pool,
    the K/V stacks and the expert leaves are read and written where they
    lie, and the temporaries stay under 0.5 GB."""
    from xllm_service_tpu.models import granite

    cfg = get_model_config("granite-4.0-h-small")

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    R, nb, i32 = 64, 2000, jnp.int32
    ssm, conv = granite.state_shapes(cfg, R)
    kv = s((1, nb, 8, BS, 128))
    compiled = jax.jit(
        lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a), donate_argnums=(1, 2)
    ).lower(
        params, (kv, s(ssm, jnp.float32)), (kv, s(conv, jnp.float32)),
        s((R,), i32), s((R,), i32), s((R, 32), i32), s((R,), jnp.bool_),
        s((1, 256), i32), s((1,), i32), s((1,), i32), s((1, 17), i32),
    ).compile()
    text = compiled.as_text()
    for kernel in ("mamba_update_kernel", "kv_write_kernel", "paged_attention_kernel",
                   "moe_grouped_kernel", "moe_grouped_down_kernel"):
        assert kernel in text, kernel
    import re

    # dynamic-update-slice writes a row in place; a COPY of a pool or of an
    # expert stack is what must not be there
    pools = {",".join(map(str, sh)) for sh in (ssm, conv, (1, nb, 8, BS, 128),
                                               (10, 36, 4096, 768), (10, 36, 768, 4096))}
    copies = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*%?[\w.\-]+ = \w+\[([\d,]*)\]\S* copy\(", line)) and m.group(1) in pools]
    assert not copies, "\n".join(copies)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5e9
    assert 12.5e9 < mem.argument_size_in_bytes < 13.5e9  # weights + state pool + 1.05 GB of K and V


def test_lightning_update_kernel_compiles_at_published_widths(one_chip, no_persistent_cache):
    """Mosaic takes `lightning_update_kernel` at minicpm-sala's shape: 32
    rows of 32 heads, planes of 128 x 128 float32, a head tile of 16 (1 MiB
    a block), the heads' keys and queries arriving a head a lane and
    broadcast along the lanes inside the body."""
    from xllm_service_tpu.ops import lightning as lo

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile(
        lambda S, act, q, k, v, lam: lo.decode_update(S, jnp.int32(3), act, q, k, v, lam,
                                                      use_kernel=True),
        s(lo.state_shape(6, 32, 32, 128)), s((32,), jnp.bool_),
        s((32, 32, 128)), s((32, 32, 128)), s((32, 32, 128)), s((32,)),
    )
    assert _kernel_calls(text, "lightning_update_kernel") == 1


@pytest.mark.parametrize("kind", ["full", "window"])
def test_laguna_attention_launches_compile_at_published_widths(one_chip, no_persistent_cache, kind):
    """The launches of laguna-xs.2.agent-steady at their new geometry: 64
    decode rows and a 512-row chunk over 8 KV heads of 128 lanes, tables
    264 blocks wide; the full layers at 48 query heads (a group of 6, which
    the decode kernel pads to 8 sublanes and the flash kernel tiles at TQ x
    6 rows), the window layers at 64 heads over a window of 512 positions
    (four blocks) with NO sink."""
    from xllm_service_tpu.ops.pallas import paged_attention as pa
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel

    s, _ = _kernel_shapes(one_chip)
    heads, window, layers = (48, 0, 2) if kind == "full" else (64, 512, 3)
    pool = s((layers, 700, 8, BS, 128))
    text = _compile(
        lambda q, k, v, bt, sl: pa.paged_attention_kernel(
            q, k, v, bt, sl, SCALE, window=window, layer=jnp.int32(1)),
        s((64, heads, 128)), pool, pool, s((64, 264), jnp.int32), s((64,), jnp.int32),
    )
    name = "window_paged_attention_kernel" if window else "paged_attention_kernel"
    assert _kernel_calls(text, name) == 1
    text = _compile(
        lambda q, k, v, bt, sp, tl: flash_prefill_kernel(
            q, k, v, bt, sp, tl, SCALE, window=window, layer=jnp.int32(1)),
        s((1, 512, heads, 128)), pool, pool, s((1, 264), jnp.int32), s((1,), jnp.int32),
        s((1,), jnp.int32),
    )
    name = "window_flash_prefill_kernel" if window else "flash_prefill_kernel"
    assert _kernel_calls(text, name) == 1


@pytest.mark.parametrize("pairs", [512, 4608])
def test_grouped_expert_kernels_compile_at_256_held_experts(one_chip, no_persistent_cache, pairs):
    """laguna-xs.2's expert product: 64 decode rows x 8 (2 pairs an expert)
    and 576 rows x 8 (18 an expert, 36 row tiles met by 256 experts), the
    layers' stacked leaves of 256 experts of width 512 and a layer index,
    no layer and no expert sliced out."""
    from xllm_service_tpu.ops.pallas.moe_dispatch import moe_grouped_kernel

    s, _ = _mla_shapes(one_chip)
    text = _compile(
        lambda x, g, a, b, c, l: moe_grouped_kernel(x, g, a, b, c, layer=l),
        s((pairs, 2048)), s((256,), jnp.int32), s((4, 256, 2048, 512)),
        s((4, 256, 2048, 512)), s((4, 256, 512, 2048)), s((), jnp.int32),
    )
    assert "moe_grouped_kernel" in text and "moe_grouped_down_kernel" in text
    _assert_nothing_moves(text, {"4,256,2048,512", "256,2048,512", "4,256,512,2048", "256,512,2048"})


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_laguna_steps_compile_at_published_widths(one_chip, no_persistent_cache, as_on_tpu, step):
    """laguna-xs.2 as the benchmark cuts it (a dense full layer, three window
    layers, a full layer; ALL 256 experts a layer, the whole vocabulary):
    the decode step of 64 rows and the mixed step with a 512-row chunk,
    every table 2 x 264 blocks wide, fit the chip beside 7.74 GB of
    weights, 3,200 full blocks and the 393 window blocks
    `_decide_window_blocks` gives 64 slots, with both kinds' decode
    launches, the K/V write, both grouped expert kernels and (mixed) both
    kinds' flash launches in them; neither pool is laid out again and the
    temporaries stay under 100 MB."""
    import re

    from xllm_service_tpu.models import granite

    cfg = get_model_config("laguna-xs.2")

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    R, i32, CB = 64, jnp.int32, 264
    (kf, vf), (kw, vw) = granite.pool_shapes(cfg, 3200, 1 + R * 6 + 8, BS)
    pools = ((s(kf), s(kw)), (s(vf), s(vw)))
    dec = (s((R,), i32), s((R,), i32), s((R, 2 * CB), i32), s((R,), jnp.bool_))
    if step == "decode":
        fn, args = granite.decode_step, (params, *pools, *dec)
    else:
        pf = (s((1, 512), i32), s((1,), i32), s((1,), i32), s((1, 2 * CB), i32))
        fn, args = granite.mixed_step, (params, *pools, *dec, *pf)
    compiled = jax.jit(
        lambda p, k, v, *a: fn(p, cfg, k, v, *a), donate_argnums=(1, 2)
    ).lower(*args).compile()
    text = compiled.as_text()
    kernels = ["paged_attention_kernel", "window_paged_attention_kernel", "kv_write_kernel",
               "moe_grouped_kernel", "moe_grouped_down_kernel"]
    for kernel in kernels + ["flash_prefill_kernel", "window_flash_prefill_kernel"] * (step == "mixed"):
        assert kernel in text, kernel
    pools_ = {",".join(map(str, sh)) for sh in (kf, kw)}
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r" (copy|transpose|bitcast-convert)\(", line)
              and any(f"[{p}]" in line.split("=")[1][:80] for p in pools_ if "=" in line)]
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_minicpm_sala_steps_compile_at_published_widths(one_chip, no_persistent_cache, as_on_tpu, step):
    """minicpm-sala as the benchmark cuts it (a sparse layer, six lightning
    layers, a sparse layer; the whole vocabulary): the decode step of 32
    rows and the mixed step with a 4,096-row chunk, every table 1,024
    blocks wide, fit the chip beside 5.64 GB of weights, a 32-slot state
    pool and 30,000 pages of K, V and compressed keys, with the lightning
    update kernel, the K/V write, the decode kernel a KV head a row and
    (mixed) the flash kernel in them; the state pool, the compressed-key
    pool and the K/V stacks are read and written where they lie, and stage
    1's scores and the chunked form's sub-chunks keep the temporaries
    under 1.5 GB."""
    from xllm_service_tpu.models import granite

    cfg = get_model_config("minicpm-sala")

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    R, nb, i32, CB = 32, 30000, jnp.int32, 1024
    state, ck = granite.state_shapes(cfg, R, nb)
    kv = kvc.PagedKV(s((2, nb, 2, 64, 128)), None)
    pools = ((kv, s(state, jnp.float32)), (kv, s(ck, jnp.float32)))
    dec = (s((R,), i32), s((R,), i32), s((R, CB), i32), s((R,), jnp.bool_))
    if step == "decode":
        fn, args = granite.decode_step, (params, *pools, *dec)
    else:
        pf = (s((1, 4096), i32), s((1,), i32), s((1,), i32), s((1, CB + 1), i32))
        fn, args = granite.mixed_step, (params, *pools, *dec, *pf)
    compiled = jax.jit(
        lambda p, k, v, *a: fn(p, cfg, k, v, *a), donate_argnums=(1, 2)
    ).lower(*args).compile()
    text = compiled.as_text()
    kernels = ["lightning_update_kernel", "kv_write_kernel", "paged_attention_kernel"]
    for kernel in kernels + ["flash_prefill_kernel"] * (step == "mixed"):
        assert kernel in text, kernel
    import re

    pools_ = {",".join(map(str, sh)) for sh in (state, ck, (2, nb, 2, 64, 128))}
    copies = [line.strip()[:160] for line in text.splitlines()
              if (m := re.match(r"\s*%?[\w.\-]+ = \w+\[([\d,]*)\]\S* copy\(", line)) and m.group(1) in pools_]
    assert not copies, "\n".join(copies)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9
    assert 10.0e9 < mem.argument_size_in_bytes < 10.5e9  # weights + state pool + 4.2 GB of pages


# ---- PR 45: a weight is read where it lies. The layer scan hands a step
# its layer's leaves by index; a product on a leaf must take that slice as a
# bitcast inside its own fusion. What it must not do is what brumby's `_qkv`
# and deepseek's `_q_heads` did: copy the leaf out of the stack and transpose
# it, every layer of every step, for a head-major dot.

# (XLA's own prefetch of a small leaf into faster memory is an async pair in
# the leaf's own layout, overlapped with compute: not a move of this kind)
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while", "custom-call",
             "copy-start", "copy-done", "slice-start", "slice-done"}


def _weight_leaves_moved(text, params, names, stacks=("layers", "dense_layers"), dtype=None):
    """Instructions of the compiled program, outside its fused
    computations, whose RESULT is one layer of a stacked weight leaf of
    `names` or a re-laying of it: the leaf's element count with the leaf's
    input width among the dims (`[1, in, out]`, `[heads, head_dim, in]`,
    any order or layout; a dynamic-slice INSIDE a product's fusion is the
    leaf read in place and is not looked at). A copy, a transpose, a
    `*dynamic-slice*` fusion, a fusion of any other name that materialises
    it: all count. `dtype` ("bf16") keeps to results of the leaves' own
    type, where a float32 pool has a leaf's element count by chance."""
    import math
    import re

    leaves = {}  # (a layer's element count, its input width) -> the leaves of that size
    for stack in stacks:
        for name, leaf in (params.get(stack) or {}).items():
            if name in names:
                leaves.setdefault((math.prod(leaf.shape[1:]), leaf.shape[1]), set()).add(name)
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    moved, inside = [], None
    for line in text.splitlines():
        if m := re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line):
            inside = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if not m or inside in fused or m.group(2) in _PLUMBING:
            continue
        if dtype and not re.match(rf"\s*(?:ROOT )?%?[\w.\-]+ = {dtype}\[", line):
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        for (count, width), which in leaves.items():
            if math.prod(dims) == count and width in dims:
                moved.append(f"{'|'.join(sorted(which))}: {line.strip()[:150]}")
    return moved


def _in_place_case(one_chip, case):
    """(step function, abstract arguments, the stacked leaves looked for)
    of one of the benchmark's step shapes with 2 scanned layers."""
    i32 = jnp.int32

    def s(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def dec(rows, width):
        return s((rows,)), s((rows,)), s((rows, width)), s((rows,), jnp.bool_)

    if case == "brumby-decode-24":
        fn, args, _ = _brumby_case(one_chip, "decode", slots=24)
        return fn, args, ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    if case.startswith("solar"):
        from xllm_service_tpu.models import granite

        cfg = dataclasses.replace(  # one GQA layer and a scan of two KDA layers
            get_model_config("solar-open2-250b"), num_layers=3,
            layer_types=("attention", "kda", "kda"), vocab_size=8192,
        )
        params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
        state, conv = granite.state_shapes(cfg, 96)
        kv = s((1, 600, 8, BS, 128), jnp.bfloat16)
        pools = ((kv, s(state, jnp.float32)), (kv, s(conv, jnp.float32)))
        names = ("wq", "wk", "wv", "wo", "w_f1", "w_f2", "w_g1", "w_g2", "w_ogate",
                 "w_gate", "w_up", "w_down", "w_sh_gate", "w_sh_up", "w_sh_down")
        if case == "solar-decode-96":
            fn = lambda p, k, v, *a: granite.decode_step(p, cfg, k, v, *a)  # noqa: E731
            return fn, (params, *pools, *dec(96, 64)), names
        pf = (s((1, 512)), s((1,)), s((1,)), s((1, 33)))
        fn = lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(96, 64), *pf), names
    if case.startswith("mimo"):
        from xllm_service_tpu.models import granite

        cfg = dataclasses.replace(  # the dense full layer, a scan of two window layers, a full one
            get_model_config("mimo-v2-flash"), num_layers=4,
            layer_types=("attention", "window", "window", "attention"), vocab_size=8192,
        )
        params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
        (kf, vf), (kw, vw) = granite.pool_shapes(cfg, 600, 201, BS)
        pools = tuple((s(full, jnp.bfloat16), s(win, jnp.bfloat16)) for full, win in ((kf, kw), (vf, vw)))
        names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
        if case == "mimo-decode-64":
            fn = lambda p, k, v, *a: granite.decode_step(p, cfg, k, v, *a)  # noqa: E731
            return fn, (params, *pools, *dec(64, 256)), names
        pf = (s((1, 512)), s((1,)), s((1,)), s((1, 256)))
        fn = lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(64, 256), *pf), names
    if case.startswith("laguna"):
        from xllm_service_tpu.models import granite

        cfg = dataclasses.replace(  # the dense full layer, a scan of two window layers, a full one
            get_model_config("laguna-xs.2"), num_layers=4,
            layer_types=("attention", "window", "window", "attention"), vocab_size=8192,
        )
        params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
        (kf, vf), (kw, vw) = granite.pool_shapes(cfg, 600, 393, BS)
        pools = tuple((s(full, jnp.bfloat16), s(win, jnp.bfloat16)) for full, win in ((kf, kw), (vf, vw)))
        names = ("wq", "wk", "wv", "wo", "w_ogate", "w_gate", "w_up", "w_down",
                 "w_sh_gate", "w_sh_up", "w_sh_down")
        if case == "laguna-decode-64":
            fn = lambda p, k, v, *a: granite.decode_step(p, cfg, k, v, *a)  # noqa: E731
            return fn, (params, *pools, *dec(64, 528)), names
        pf = (s((1, 512)), s((1,)), s((1,)), s((1, 528)))
        fn = lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(64, 528), *pf), names
    if case.startswith("falcon"):
        from xllm_service_tpu.models import granite

        cfg = dataclasses.replace(  # ONE scan of two parallel blocks
            get_model_config("falcon-h1-34b"), num_layers=2, layer_types=("parallel",) * 2,
            vocab_size=8192,
        )
        params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
        state, conv = granite.state_shapes(cfg, 64)
        kv = s((2, 600, 4, BS, 128), jnp.bfloat16)
        pools = ((kv, s(state, jnp.float32)), (kv, s(conv, jnp.float32)))
        names = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate", "w_up", "w_down")
        if case == "falcon-decode-64":
            fn = lambda p, k, v, *a: granite.decode_step(p, cfg, k, v, *a)  # noqa: E731
            return fn, (params, *pools, *dec(64, 32)), names
        pf = (s((1, 256)), s((1,)), s((1,)), s((1, 33)))
        fn = lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(64, 32), *pf), names
    if case.startswith("sala"):
        from xllm_service_tpu.models import granite

        cfg = dataclasses.replace(  # a sparse layer and a scan of two lightning layers
            get_model_config("minicpm-sala"), num_layers=3,
            layer_types=("sparse", "lightning", "lightning"), layer_ids=(9, 10, 11),
            vocab_size=8192,
        )
        params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
        state, ck = granite.state_shapes(cfg, 32, 3000)
        kv = kvc.PagedKV(s((1, 3000, 2, 64, 128), jnp.bfloat16), None)
        pools = ((kv, s(state, jnp.float32)), (kv, s(ck, jnp.float32)))
        names = ("wq", "wk", "wv", "wo", "w_ogate", "w_gate", "w_up", "w_down")
        if case == "sala-decode-32":
            fn = lambda p, k, v, *a: granite.decode_step(p, cfg, k, v, *a)  # noqa: E731
            return fn, (params, *pools, *dec(32, 1024)), names
        pf = (s((1, 2048)), s((1,)), s((1,)), s((1, 1025)))  # (4,096 rows would give activations a square leaf's shape)
        fn = lambda p, k, v, *a: granite.mixed_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(32, 1024), *pf), names
    from xllm_service_tpu.models import deepseek

    cfg = dataclasses.replace(  # 1 dense layer beside the scan of 2
        get_model_config("deepseek-v2"), num_layers=3, vocab_size=25600, experts_held=(0, 40)
    )
    params = jax.eval_shape(lambda k: deepseek.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    pools = (s((3, 600, 1, BS, MLA_C), jnp.bfloat16), s((3, 1, 1, 1, 1), jnp.bfloat16))
    # (not w_dkv: at 576 rows its [5120, 576] is an activation's shape)
    names = ("w_dq", "w_uq", "w_uk", "w_uv", "wo", "w_gate", "w_up", "w_down",
             "w_sh_gate", "w_sh_up", "w_sh_down")
    if case == "deepseek-decode-3":
        fn = lambda p, k, v, *a: deepseek.decode_step(p, cfg, k, v, *a)  # noqa: E731
        return fn, (params, *pools, *dec(3, 64)), names
    pf = (s((1, 512)), s((1,)), s((1,)), s((1, 64)))
    fn = lambda p, k, v, *a: deepseek.mixed_step(p, cfg, k, v, *a)  # noqa: E731
    return fn, (params, *pools, *dec(64, 64), *pf), names


@pytest.mark.parametrize("case", ["brumby-decode-24", "deepseek-decode-3", "deepseek-mixed-576",
                                  "solar-decode-96", "solar-mixed-608", "mimo-decode-64",
                                  "mimo-mixed-576", "falcon-decode-64", "falcon-mixed-320",
                                  "sala-decode-32", "sala-mixed-2080", "laguna-decode-64",
                                  "laguna-mixed-576"])
def test_step_reads_every_weight_leaf_where_it_lies(one_chip, no_persistent_cache, as_on_tpu, case):
    """The brumby decode step at reason-batch's 24 rows and the deepseek
    decode (3 rows) and mixed (64 + 512 rows) steps of doc-steady, at the
    benchmark configurations' widths: no instruction of the compiled
    program has a whole layer of a weight leaf, or a re-laying of one, as
    its result (`bf16[1,5120,5120]`, `[1,5120,1024]`, `[1,1536,24576]`,
    `[128,192,1536]`...). At 576 rows `q_lat` `[128,512,576]` has `w_uq`'s
    element count by chance: it holds no 1536, and is an activation. The
    KDA hybrid's decode (96 rows) and mixed (96 + 512 rows) steps at
    solar-open2-250b's widths: `wq`/`wk`/`wv` of the KDA layers, the two
    low-rank pairs and the GQA layer's gate, whose consumers are all
    head-batched. The window family's decode (64 rows) and mixed (64 + 512
    rows) steps at mimo-v2-flash's widths, every table 128 blocks wide:
    `wq`/`wk`/`wv`/`wo` of both kinds of attention layer (key heads of 192
    lanes, value heads of 128), the dense first layer and the experts,
    with all four attention launches of the cell in the program. The
    parallel family's decode (64 rows) and mixed (64 + 256 rows) steps at
    falcon-h1-34b's widths: both mixers' matrices and the dense MLP's of a
    block, with the update kernel (two B/C groups, state 256), the paged
    decode, flash-prefill and write kernels (a query group of 5) all in
    the ONE layer body. The sparse + lightning family's decode (32 rows)
    and mixed (32 + 2,048 rows) steps at minicpm-sala's widths, every table
    1,024 blocks wide: the sparse layer's matrices and gate (a query group
    of 16), the lightning layers' five square matrices, the dense MLP,
    with the lightning update kernel, the decode kernel a KV head a row,
    the write kernel and (mixed) the flash kernel in the program. The
    Laguna family's decode (64 rows) and mixed (64 + 512 rows) steps at
    laguna-xs.2's widths, every table 264 blocks wide: both kinds'
    matrices at their own query heads (48 and 64) and their per-head
    gates, the dense first layer, the 256 experts' stacks and the shared
    expert, with all four attention launches of the cell in the program."""
    fn, args, names = _in_place_case(one_chip, case)
    text = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernels' branch, as on the chip
    if case.startswith("solar"):
        # (a head tile of the float32 state pool, [1, 64, 128, 128], has w_f2's element count)
        moved = _weight_leaves_moved(text, args[0], names, ("layers", "kda", "attn"), dtype="bf16")
    elif case.startswith("mimo"):
        # (not the full layers' wv: its [4096, 512] is the shape of a chunk's rows)
        moved = _weight_leaves_moved(
            text, args[0], names, ("layers", "dense_layers", "attn_w"), dtype="bf16"
        ) + _weight_leaves_moved(
            text, args[0], tuple(n for n in names if n != "wv"), ("attn",), dtype="bf16")
        launches = ["paged_attention_kernel", "window_paged_attention_kernel", "kv_write_kernel"]
        launches += ["flash_prefill_kernel", "window_flash_prefill_kernel"] * (case == "mimo-mixed-576")
        for name in launches:
            assert f'"{name}"' in text or f"{name}" in text, name
        assert ("window_flash_prefill_kernel" in text) == (case == "mimo-mixed-576")
    elif case.startswith("laguna"):
        # (not the shared expert: its [2048, 512] is the shape of the chunk's rows and of 64
        # rows' 512 pairs; not the window layers' gate: its [2048, 64] is the decode rows')
        shared = ("w_sh_gate", "w_sh_up", "w_sh_down")
        moved = _weight_leaves_moved(
            text, args[0], tuple(n for n in names if n not in shared),
            ("layers", "dense_layers", "attn"), dtype="bf16",
        ) + _weight_leaves_moved(
            text, args[0], tuple(n for n in names if n != "w_ogate"), ("attn_w",), dtype="bf16")
        launches = ["paged_attention_kernel", "window_paged_attention_kernel", "kv_write_kernel",
                    "moe_grouped_kernel"]
        launches += ["flash_prefill_kernel", "window_flash_prefill_kernel"] * (case == "laguna-mixed-576")
        for name in launches:
            assert name in text, name
    elif case.startswith("falcon"):
        moved = _weight_leaves_moved(text, args[0], names, ("layers", "mamba", "attn"), dtype="bf16")
        launches = ["mamba_update_kernel", "paged_attention_kernel", "kv_write_kernel"]
        launches += ["flash_prefill_kernel"] * (case == "falcon-mixed-320")
        for name in launches:
            assert name in text, name
    elif case.startswith("sala"):
        moved = _weight_leaves_moved(text, args[0], names, ("layers", "lightning", "attn"), dtype="bf16")
        launches = ["lightning_update_kernel", "paged_attention_kernel", "kv_write_kernel"]
        launches += ["flash_prefill_kernel"] * (case == "sala-mixed-2080")
        for name in launches:
            assert name in text, name
    else:
        moved = _weight_leaves_moved(text, args[0], names)
    assert not moved, "\n".join(moved)
    if case.startswith("solar"):
        assert "kda_update_kernel" in text
    if case == "solar-mixed-608":
        # the chunk's rows [1, 512, 64 x 128] reach `kda_chunk_kernel` as the convolution
        # leaves them and leave it token-major: no transpose of q, k, v, the decay or the
        # output (the jax.numpy form moved all of them heads-first and back), no gram
        # launch. What is left re-tiles [512, 64, 128] twice, between (token, lane) and
        # (head, lane) tiles: the decay inside the fusion that masks it, and the output for
        # the norm over a head's lanes
        assert "kda_chunk_kernel" in text and "kda_gram_kernel" not in text
        moved = _rows_moved(text, 512 * 64 * 128)
        assert not [line for line in moved if " transpose(" in line], "\n".join(moved)
        assert len(moved) <= 2, "\n".join(moved)


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_solar_tiny_steps_compile_and_neither_pool_is_laid_out_again(one_chip, no_persistent_cache, as_on_tpu, step):
    """The three step programs of `solar-tiny` for a described v5e, its KDA
    heads widened to 128 lanes so that the state is whole tiles as the
    benchmark's is (2 heads of 128 x 128; at 16 lanes XLA re-tiles any
    small array): the delta-rule pool `[3, 8, 2, 128, 128]` and the
    convolution pool `[3, 8, 2304]` go in and come out in the layout they
    are held in: no copy and no transpose of either on the way into or
    out of the program or of its layer scans, and the decode update is the
    kernel."""
    import re

    from xllm_service_tpu.models import granite

    cfg = dataclasses.replace(get_model_config("solar-tiny"), kda_n_heads=2, kda_d_head=128)
    i32 = jnp.int32

    def s(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: granite.init_params(cfg, k, jnp.float32), jax.random.key(0))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype), params)
    state, conv = granite.state_shapes(cfg, 8)
    assert state == (3, 8, 2, 128, 128) and conv == (3, 8, 2304)
    kv = s((1, 40, 2, 16, 16), jnp.float32)
    pools = ((kv, s(state, jnp.float32)), (kv, s(conv, jnp.float32)))
    dec = (s((8,)), s((8,)), s((8, 8)), s((8,), jnp.bool_))
    pf = (s((2, 32)), s((2,)), s((2,)), s((2, 9)))
    fn, args = {
        "decode": (granite.decode_step, dec), "prefill": (granite.prefill_batch_step, pf),
        "mixed": (granite.mixed_step, dec + pf),
    }[step]
    kw = {"use_kernel": False} if step == "decode" else {}  # (the paged kernel wants 128-lane K/V)
    text = jax.jit(lambda p, k, v, *a: fn(p, cfg, k, v, *a, **kw), donate_argnums=(1, 2)).lower(
        params, *pools, *args).compile().as_text()
    # a pool-shaped copy or transpose in any layout but the one the pools are
    # held in (row-major, (8, 128) tiles) is a re-layout; XLA's prefetch of
    # so small a pool into its faster memory space (`S(1)`) keeps the layout
    shapes = {",".join(map(str, sh)) for sh in (state, conv)}
    held = {len(sh): ",".join(map(str, reversed(range(len(sh))))) for sh in (state, conv)}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\{([\d,]*):T\(8,128\)(?:S\(\d\))?\} "
                     r"(copy|transpose)\(", line)
        generic = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* (copy|transpose)\(", line)
        if generic and generic.group(1) in shapes:
            if not m or m.group(2) != held[generic.group(1).count(",") + 1] or generic.group(2) == "transpose":
                moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    assert ("kda_update_kernel" in text) == (step == "mixed")
    # where a chunk is, its whole chunk form is the kernel: the gram's pair
    # tensor never reaches HBM, and no second launch forms the gram
    assert ("kda_chunk_kernel" in text) == (step != "decode")
    assert "kda_gram_kernel" not in text
    assert not _pair_tensors(text)


def _pair_tensors(text):
    """Instructions of a compiled text with a float32 result shaped
    [..., 16, 16, 128]: the decayed gram's pair terms (sub-block row,
    sub-block column, key lane), in any leading shape."""
    import re

    return [line.strip()[:160] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \(?f32\[(?:\d+,)*16,16,128\]", line)]


def test_the_chunk_form_at_think_steady_holds_no_pair_tensor(one_chip, no_persistent_cache):
    """`_chunk_scan` alone at the cell's shape (one row of 512 tokens, 64
    heads of 128 lanes, chunks of 64): with the gram's diagonal as
    `kda_gram_kernel` no `f32[8,64,4,16,16,128]` (268 MB a layer and
    chunk) is in the program; the `jax.numpy` route still has it, so the
    search finds what it looks for."""
    from xllm_service_tpu.ops import kda

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    rows = s(1, 512, 64, 128)
    args = (rows, rows, rows, rows, s(1, 512, 64), s(1, 64, 128, 128))
    text = _compile(lambda *a: kda._chunk_scan(*a, 64, use_kernel=True), *args)
    assert "kda_gram_kernel" in text and not _pair_tensors(text)
    text = _compile(lambda *a: kda._chunk_scan(*a, 64), *args)
    assert "kda_gram_kernel" not in text and _pair_tensors(text)


def _rows_moved(text, elements):
    """Instructions of a compiled text that lay `elements` float32 values
    out again: a copy or a transpose (alone, or as what a fusion is named
    for) with a result of that many elements."""
    import math
    import re

    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = f32\[([\d,]+)\]\S* (copy|transpose|fusion)\(", line)
        if m and math.prod(map(int, m.group(2).split(","))) == elements and (
                m.group(3) != "fusion" or re.search(r"copy|transpose", m.group(1))):
            moved.append(line.strip()[:160])
    return moved


def test_the_chunk_kernel_at_think_steady_reads_the_rows_where_they_lie(one_chip, no_persistent_cache):
    """`chunk_update` at the cell's shape (one row of 512 tokens, 64
    heads of 128 lanes, the 96-slot pool of 6 layers donated) between
    arrays that lie as the projections leave them and the out-projection
    takes them (`[tokens, H d]`: (8, 128) tiles of (token, lane)): as
    `kda_chunk_kernel` the program holds no copy and no transpose of q, k,
    v, the decay or the output (a head is 128 lanes of the kernel's
    blocks) and no gram launch; the `jax.numpy` form moves
    them heads-first and back, so the search finds what it looks for."""
    from xllm_service_tpu.ops import kda

    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    one = s(1, dtype=jnp.int32)
    args = (s(6, 96, 64, 128, 128), s(dtype=jnp.int32), one, one, one,
            s(1, 512, 3 * 64 * 128), s(1, 512, 64 * 128), s(1, 512, 64))

    def text(use_kernel):
        def fn(S, layer, slots, start, length, qkv, g, beta):
            o, S = kda.chunk_update(S, layer, slots, start, length, qkv,
                                         g.reshape(1, 512, 64, 128), beta, use_kernel=use_kernel)
            return o.reshape(1, 512, 64 * 128), S
        return jax.jit(fn, donate_argnums=0).lower(*args).compile().as_text()

    kernel = text(True)
    assert "kda_chunk_kernel" in kernel and "kda_gram_kernel" not in kernel
    assert not _rows_moved(kernel, 512 * 64 * 128)
    assert _rows_moved(text(False), 512 * 64 * 128)


# ---- PR 51: one context bucket wherever attention runs as the kernels
# (`ModelExecutor._ctx_bucket`). The executors below are built on the CPU
# with the dispatchers steered as on the chip, so their report resolves to
# the kernels; no kernel can run here, so every step program is a stand-in
# that lowers and compiles each NEW signature for the described chip and
# hands back zeros of the program's outputs.


def _described_step_programs(monkeypatch, one_chip):
    """Patch `ModelExecutor._step_jit`: {program: {signature: out_info}} of
    what the executors built afterwards dispatch."""
    import numpy as np

    from xllm_service_tpu.runtime.executor import ModelExecutor

    seen = {}
    real_step_jit = ModelExecutor._step_jit

    def step_jit(self, impl, **jit_kw):
        real = real_step_jit(self, impl, **jit_kw)
        mine = seen.setdefault(impl.__name__, {})

        def call(*a, **kw):
            a, kw = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
                if isinstance(x, (jax.Array, np.ndarray, np.generic)) else x,
                (a, kw),
            )
            key = str((a, kw))
            if key not in mine:
                lowered = real.lower(*a, **kw)
                assert "tpu_custom_call" in lowered.compile().as_text()
                mine[key] = lowered.out_info
            out = jax.tree.map(lambda o: jnp.zeros(o.shape, o.dtype), mine[key])
            return out[0] if self.cfg.is_moe else out  # (the router's counts beside it)

        call._cache_size = lambda: len(mine)
        return call

    monkeypatch.setattr(ModelExecutor, "_step_jit", step_jit)
    return seen


@pytest.mark.parametrize("model", ["llama3-shard-tiny", "granite-tiny"])
def test_one_decode_and_one_mixed_program_serve_every_context(
    one_chip, no_persistent_cache, as_on_tpu, monkeypatch, model
):
    """On a v5e the tiny llama and the tiny hybrid executors (K/V heads of
    128 lanes, so that the launches are the kernels) lower exactly ONE
    decode and ONE mixed program over contexts from one block to
    `max_seq_len`, each of which the chip's compiler takes with the whole
    table as its scalar operand; `prewarm_programs()` enumerates that
    smaller family by itself: a program a prefill bucket (and group size,
    left out here), none a context bucket."""
    import numpy as np

    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.runtime.executor import ModelExecutor, PrefillItem, SamplingBatch

    seen = _described_step_programs(monkeypatch, one_chip)
    R, bs, MB = 4, 16, 16
    ecfg = EngineConfig(
        model=model, dtype="bfloat16", block_size=bs, num_blocks=80, max_running_requests=R,
        max_seq_len=bs * MB, max_prefill_tokens=32, prefill_buckets=[32],
    )
    ex = ModelExecutor(ecfg, model_cfg=dataclasses.replace(get_model_config(model), head_dim=128))
    try:
        rep = ex.kernel_report()
        assert (rep["decode"], rep["prefill"]) == ("paged", "flash") and ex.whole_table
        assert ex.max_blocks_per_seq == MB
        batch = SamplingBatch(
            temperature=np.zeros(R, np.float32), top_k=np.zeros(R, np.int32),
            top_p=np.ones(R, np.float32), seeds=np.zeros(R, np.uint32), steps=np.zeros(R, np.int32),
        )
        tables = np.zeros((R, MB), np.int32)
        active = np.array([True, True, False, False])
        zeros = np.zeros((R,), np.int32)
        for blocks in range(1, MB + 1):  # the longest row's context, in blocks
            positions = np.array([blocks * bs - 1, 3, 0, 0], np.int32)
            ex.decode_start(zeros, None, None, positions, tables, active, batch)
            n = 32 if blocks > 1 else 9  # a chunk that ends in block `blocks`
            item = PrefillItem(token_ids=np.zeros((n,), np.int32), start_pos=blocks * bs - n - 1,
                               block_table=np.zeros((MB,), np.int32), slot=2)
            ex.mixed_start([item], zeros, None, None, positions[::-1].copy(), tables, active[::-1].copy(), batch)
        assert {p: len(sigs) for p, sigs in seen.items() if sigs} == {"_decode_impl": 1, "_mixed_impl": 1}
        assert ex.lowering_count() == 2

        # the whole family: the split prefill and the mixed step of every
        # prefill bucket at one row, and the decode step
        report = ex.prewarm_programs(p_groups=False)
        assert report["families"]["split"] == report["families"]["mixed"] == len(ex.prefill_buckets)
        assert {p: len(sigs) for p, sigs in seen.items() if sigs} == {
            "_decode_impl": 1, "_prefill_impl": len(ex.prefill_buckets),
            "_mixed_impl": len(ex.prefill_buckets)}
    finally:
        attention.set_shard_context(None)
