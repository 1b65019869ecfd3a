"""Device regions (docs/OBSERVABILITY.md "Device regions"): the models name
the regions of a step program (`obs.spans.region`), the compiled text
keeps the names, `obs.regions.parse_regions` reads `{op key: region}` out
of it and `attribute` joins a profile's own nanoseconds against the maps.

Three layers of tests:
  * the parser and the join on hand-written HLO lines (no JAX);
  * `ModelExecutor.program_regions()` on a real engine of each family on
    the attached (CPU) backend: the maps exist after `stop()`, name only
    DEVICE_REGIONS, and leave `lowering_count()` where it was;
  * the executor's decode and mixed step programs of each family compiled
    for a DESCRIBED TPU v5e (as tests/test_tpu_compile.py describes one,
    skipped where that skips): every fusion, convolution and custom call
    has a region, and each Pallas kernel keeps its name and its region.
"""

import dataclasses
import os
import re
import subprocess
import sys
import threading
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from xllm_service_tpu.obs import regions
from xllm_service_tpu.obs.spans import DEVICE_REGIONS, region

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------- the parser

SCOPE = "jit(step)/xllm.stack_slice/while/body/closed_call"
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,64], p1: bf16[64,256]) -> bf16[8,256] {{
  %p0 = f32[8,64]{{1,0}} parameter(0)
  %p1 = bf16[64,256]{{1,0}} parameter(1)
  %mul.1 = f32[8,64]{{1,0}} multiply(%p0, %p0), metadata={{op_name="{SCOPE}/xllm.norm/mul"}}
  %cvt.1 = bf16[8,64]{{1,0}} convert(%mul.1), metadata={{op_name="{SCOPE}/xllm.norm/convert"}}
  ROOT %convolution.1 = bf16[8,256]{{1,0}} convolution(%cvt.1, %p1), dim_labels=bf_io->bf, metadata={{op_name="{SCOPE}/xllm.ffn/dot_general"}}
}}

%fused_computation.2 (p0: bf16[8,256]) -> (f32[8,256], f32[8]) {{
  %p0.1 = bf16[8,256]{{1,0}} parameter(0)
  %exp.1 = f32[8,256]{{1,0}} exponential(%p0.1), metadata={{op_name="jit(step)/xllm.sample/exp"}}
  %red.1 = f32[8]{{0}} reduce(%exp.1, %p0.1), dimensions={{1}}, to_apply=%add_f32, metadata={{op_name="jit(step)/xllm.sample/reduce_sum"}}
  ROOT %tuple.9 = (f32[8,256]{{1,0}}, f32[8]{{0}}) tuple(%exp.1, %red.1)
}}

%body (arg: (s32[], f32[8,64], bf16[4,64,256])) -> (s32[], f32[8,64], bf16[4,64,256]) {{
  %arg = (s32[], f32[8,64]{{1,0}}, bf16[4,64,256]{{2,1,0}}) parameter(0)
  %gte.1 = f32[8,64]{{1,0}} get-tuple-element(%arg), index=1
  %slice_fusion.3 = bf16[64,256]{{1,0:T(8,128)(2,1)}} fusion(%arg), kind=kLoop, calls=%fused_slice, metadata={{op_name="{SCOPE[:-12]}/dynamic_slice"}}
  %copy.7 = bf16[64,256]{{0,1:T(8,128)(2,1)}} copy(%slice_fusion.3)
  %fusion.1 = bf16[8,256]{{1,0}} fusion(%gte.1, %copy.7), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{SCOPE}/xllm.norm/mul"}}
  %attn_kernel.2 = bf16[8,256]{{1,0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{SCOPE}/xllm.attn/pallas_call"}}
  ROOT %tuple.1 = (s32[], f32[8,64]{{1,0}}, bf16[4,64,256]{{2,1,0}}) tuple(%arg)
}}

%cond (arg.1: (s32[], f32[8,64], bf16[4,64,256])) -> pred[] {{
  %arg.1 = (s32[], f32[8,64]{{1,0}}, bf16[4,64,256]{{2,1,0}}) parameter(0)
  ROOT %lt.1 = pred[] compare(%arg.1, %arg.1), direction=LT
}}

ENTRY %main.1 (x: f32[8,64], w: bf16[4,64,256]) -> (f32[8,256], f32[8]) {{
  %x = f32[8,64]{{1,0}} parameter(0)
  %w = bf16[4,64,256]{{2,1,0}} parameter(1)
  %while.1 = (s32[], f32[8,64]{{1,0}}, bf16[4,64,256]{{2,1,0}}) while(%x, %w), condition=%cond, body=%body, metadata={{op_name="jit(step)/xllm.stack_slice/while"}}
  %lone_copy.4 = bf16[8,256]{{1,0}} copy(%while.1)
  %fusion.2 = (f32[8,256]{{1,0}}, f32[8]{{0}}) fusion(%lone_copy.4), kind=kLoop, calls=%fused_computation.2
  %orphan.5 = f32[2]{{0}} copy(%x)
  %mid.6 = f32[8]{{0}} reduce-window(%fusion.2), window={{size=8}}, to_apply=%add_f32, metadata={{op_name="reduce_window_sum"}}
  %mid.7 = f32[8]{{0}} copy(%mid.6)
  %between.8 = f32[8]{{0}} copy(%fusion.2)
  %logits.9 = f32[8]{{0}} add(%between.8, %x), metadata={{op_name="jit(step)/xllm.head/add"}}
  ROOT %out = (f32[8,256]{{1,0}}, f32[8]{{0}}, f32[8]{{0}}, f32[8]{{0}}, f32[8]{{0}}) tuple(%fusion.2, %orphan.5, %between.8, %logits.9, %mid.7)
}}
"""


def test_scope_region_takes_the_innermost_known_scope():
    assert regions.scope_region("jit(f)/xllm.stack_slice/while/body/xllm.ffn/dot") == "ffn"
    assert regions.scope_region("jit(f)/xllm.ffn/xllm.moe_route/top_k") == "moe_route"
    # a scope outside the vocabulary is not a region: the one around it is
    assert regions.scope_region("jit(f)/xllm.head/xllm.madeup/add") == "head"
    assert regions.scope_region("jit(f)/while/body/add") is None


def test_parse_regions_on_handwritten_hlo():
    m = regions.parse_regions(HLO)
    # the scan body's slice falls under the enclosing scope alone
    assert m["slice_fusion.3 bf16[64,256]"] == "stack_slice"
    # a fusion whose root's metadata says `norm` but that holds the matmul
    # belongs to the matmul's region
    assert m["fusion.1 bf16[8,256]"] == "ffn"
    # a custom call keeps its kernel's name in the key
    assert m["attn_kernel.2 bf16[8,256]"] == "attn"
    # no metadata, one consumer: the consumer's region, through a chain
    assert m["copy.7 bf16[64,256]"] == "ffn"
    assert m["lone_copy.4 bf16[8,256]"] == "sample"
    # a multi-output fusion with no metadata of its own: its instructions'
    assert m["fusion.2 (f32[8,256], f32[8])"] == "sample"
    assert m["while.1 (s32[], f32[8,64], bf16[4,64,256])"] == "stack_slice"
    # feeds only the root tuple, no metadata: left out (unnamed)
    assert not any(k.startswith("orphan.5") for k in m)
    # XLA's rewrite of a cumulative sum in mid-sampler lost its scope, and
    # its consumer has none either: the one region its neighbours name
    assert m["mid.6 f32[8]"] == m["mid.7 f32[8]"] == "sample"
    # between two regions (made in `sample`, read by `head` and by the
    # root) nothing is guessed
    assert not any(k.startswith("between.8") for k in m)
    # the insides of a fusion are not ops of their own
    assert not any(k.startswith(("mul.1", "convolution.1", "exp.1")) for k in m)
    assert set(m.values()) <= set(DEVICE_REGIONS)


def test_op_key_joins_a_trace_name_to_the_text():
    text_line = (
        "  %fusion.78 = bf16[32,3072]{1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, "
        "calls=%fused_computation.70"
    )
    trace_name = (
        "%fusion.78 = bf16[32,3072]{1,0:T(8,128)(2,1)} fusion(f32[32,3072]{1,0:T(8,128)} %a, "
        "bf16[3072,3072]{1,0:T(8,128)(2,1)} %b), kind=kOutput, calls=%fused_computation.70"
    )
    assert regions.op_key(text_line) == regions.op_key(trace_name) == "fusion.78 bf16[32,3072]"
    tup = "%k.9 = (bf16[2,512]{1,0:T(8,128)(2,1)}, bf16[2,512]{1,0}) custom-call(bf16[2,512]{1,0} %x)"
    assert regions.op_key(tup) == "k.9 (bf16[2,512], bf16[2,512])"
    assert regions.op_key("%bare_name") == "bare_name"


def test_attribute_sums_to_its_input_and_never_guesses():
    decode = {"fusion.1 bf16[8,256]": "ffn", "fusion.2 f32[8]": "sample", "copy.3 f32[8]": "head"}
    mixed = {"fusion.1 bf16[8,256]": "attn_proj", "fusion.2 f32[8]": "sample"}
    ops = {
        "%fusion.1 = bf16[8,256]{1,0} fusion(f32[8,64]{1,0} %a), kind=kOutput": 700.0,
        "%fusion.2 = f32[8]{0} fusion(f32[8,64]{1,0} %a), kind=kLoop": 200.0,
        "%copy.3 = f32[8]{0} copy(f32[8]{0} %a)": 50.0,
        "%fusion.1 = bf16[8,512]{1,0} fusion(f32[8,64]{1,0} %a), kind=kOutput": 30.0,  # another shape
        "%while.4 = (s32[]) while((s32[]) %t), condition=%c, body=%b": 20.0,
    }
    got = regions.attribute(ops, [decode, mixed])
    assert got == {
        regions.AMBIGUOUS: 700.0, "sample": 200.0, "head": 50.0, regions.UNNAMED: 50.0,
    }
    assert sum(got.values()) == pytest.approx(sum(ops.values()))
    # one program alone: nothing to disagree with
    assert regions.attribute(ops, [decode])["ffn"] == 700.0
    assert regions.attribute(ops, []) == {regions.UNNAMED: 1000.0}
    assert regions.attribute({}, [decode]) == {}


def test_region_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="DEVICE_REGIONS"):
        region("mlp")
    with region("ffn"):
        pass


def test_obs_regions_imports_no_jax():
    code = (
        "import sys; from xllm_service_tpu.obs import regions, region, DEVICE_REGIONS; "
        "assert 'jax' not in sys.modules, 'jax imported'; "
        "assert regions.program_maps() == {}"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True, timeout=120)


# ------------------------------------------ a real executor, attached backend

FAMILY_TINY = {
    "llama": "llama3-tiny", "brumby": "brumby-tiny",
    "deepseek": "deepseek-moe-tiny", "granite": "granite-tiny",
}


def _serve(model: str, n: int = 3):
    """An engine of `model` that has served `n` sampled requests (so both
    the mixed and the decode program ran) and has stopped."""
    from xllm_service_tpu.common.config import EngineConfig
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.runtime.executor import ModelExecutor

    cfg = EngineConfig(
        model=model, dtype="float32", block_size=16, num_blocks=64,
        max_running_requests=4, max_seq_len=256, prefill_buckets=[32, 64],
    )
    ex = ModelExecutor(cfg, init_seed=0)
    eng = InferenceEngine(cfg, executor=ex)
    done = [threading.Event() for _ in range(n)]

    def callback(ev):
        return lambda out: (out.finished and ev.set()) or True

    eng.start()
    try:
        rng = np.random.RandomState(0)
        for i, ev in enumerate(done):
            prompt = [int(t) for t in rng.randint(0, 200, size=20 + i)]
            eng.add_request(EngineRequest(
                f"r{i}", prompt, SamplingParams(temperature=0.7, max_new_tokens=6), callback(ev),
            ))
        for ev in done:
            assert ev.wait(300)
    finally:
        eng.stop()
    return ex


@pytest.mark.parametrize("family", sorted(FAMILY_TINY))
def test_program_regions_after_stop_and_lowering_count_stays(family):
    ex = _serve(FAMILY_TINY[family])
    before = ex.lowering_count()
    assert before >= 2
    maps = ex.program_regions()
    assert ex.lowering_count() == before  # ahead-of-time lowering goes past the dispatch caches
    assert {"_decode_impl", "_mixed_impl"} <= set(maps)
    assert sum(len(v) for v in maps.values()) == before  # one map a program and shape
    named = Counter(r for v in maps.values() for m in v for r in m.values())
    assert set(named) <= set(DEVICE_REGIONS)
    assert {"head", "sample", "ffn", "attn_proj", "stack_slice"} <= set(named)
    mixer = "state_mixer" if family in ("brumby", "granite") else "attn"
    assert named[mixer]
    # memoized, and found through the registry by a caller with no handle
    again = ex.program_regions()
    assert all(a is b for p in maps for a, b in zip(maps[p], again[p]))
    found = regions.program_maps()
    assert all(any(m is f for f in found[p]) for p in maps for m in maps[p])


def test_a_call_that_lowers_nothing_keeps_no_second_signature():
    ex = _serve("llama3-tiny", n=2)
    # a spent budget compiles nothing; what was left out comes later
    assert ex.program_regions(budget_s=-1.0) == {}
    assert sum(len(v) for v in ex.program_regions().values()) == ex.lowering_count()
    kept = {p: len(v) for p, v in ex._step_signatures.items() if v}
    assert sum(kept.values()) == ex.lowering_count()
    for sigs in ex._step_signatures.values():
        for _, args, kwargs in sigs:  # shapes, not buffers
            assert not [x for x in jax.tree.leaves((args, kwargs)) if isinstance(x, jax.Array)]


@pytest.mark.parametrize("model", ["llama3-tiny", "moe-tiny"])
def test_a_stopped_engines_executor_is_collected(model):
    """`_step_jit`'s wrapper must not keep its executor alive: a bound
    method of the jit object stored on it (`call.lower = jitted.lower`) is
    invisible to the cycle collector, and every executor of the process
    then stays for good with its weights, pools and executables (tier-1
    lost a worker in every process that had built enough of them)."""
    import gc
    import weakref

    ref = weakref.ref(_serve(model, n=1))
    gc.collect()
    assert ref() is None


def test_an_executable_from_before_the_scopes_is_compiled_again():
    """The persistent cache's key leaves metadata out, so the call (and
    the ahead-of-time compile after it) can be served an executable that
    was compiled before the scopes were there: its text names no region,
    and `program_regions` compiles once more under another key."""
    from xllm_service_tpu.runtime.executor import ModelExecutor

    calls = []

    class Compiled:
        def __init__(self, text):
            self.text = text

        def as_text(self):
            return self.text

    class Lowered:
        def compile(self, compiler_options=None):
            calls.append(compiler_options)
            scope = "xllm.ffn/" if compiler_options else ""
            return Compiled(
                "ENTRY %main (p: f32[8]) -> f32[8] {\n"
                "  %p = f32[8]{0} parameter(0)\n"
                f'  ROOT %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="jit(f)/{scope}mul"}}\n'
                "}\n"
            )

    class Jitted:
        def lower(self, *a, **kw):
            return Lowered()

    ex = object.__new__(ModelExecutor)
    ex._set_shard_ctx = lambda: None
    ex._step_signatures, ex._region_maps = {"_decode_impl": [(Jitted(), (), {})]}, {}
    assert ex.program_regions()["_decode_impl"][0]["fusion.1 f32[8]"] == "ffn"
    assert calls[0] is None and calls[1]  # the call's own executable first


# -------------------------------------------------- a described TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
    from xllm_service_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


R, P, LPAD, CB, BS, NB = 16, 1, 256, 8, 128, 64
OPS_WITH_A_REGION = ("fusion", "convolution", "custom-call")


def _family_case(family: str, one_chip):
    """(model config cut to a few layers, abstract params, k_cache,
    v_cache) of one family at its published widths, on the described chip."""
    from xllm_service_tpu import models
    from xllm_service_tpu.models.configs import get_model_config

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    if family == "llama":
        cfg = dataclasses.replace(get_model_config("llama3-3b"), num_layers=2)
        kv = s((2, NB, cfg.num_kv_heads, BS, cfg.head_dim))
        caches = (kv, kv)
    elif family == "brumby":
        from xllm_service_tpu.models import brumby

        cfg = dataclasses.replace(get_model_config("brumby-14b"), num_layers=2)
        caches = tuple(s(sh, jnp.float32) for sh in brumby.state_shapes(cfg, R))
    elif family == "deepseek":
        cfg = dataclasses.replace(
            get_model_config("deepseek-v2"), num_layers=2, vocab_size=25600, experts_held=(0, 40),
        )
        caches = (s((2, NB, 1, BS, cfg.mla_cache_dim)), s((2, 1, 1, 1, 1)))
    else:
        from xllm_service_tpu.models import granite

        cfg = get_model_config("granite-4.0-h-small")
        ssm, conv = granite.state_shapes(cfg, R)
        kv = s((cfg.num_attention_layers, NB, cfg.num_kv_heads, BS, cfg.head_dim))
        caches = ((kv, s(ssm, jnp.float32)), (kv, s(conv, jnp.float32)))
    mod = models.get_module(cfg)
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k, jnp.bfloat16), jax.random.key(0))
    return cfg, mod, jax.tree.map(lambda a: s(a.shape, a.dtype), params), caches, s


def _compile_step(family: str, step: str, one_chip, R: int = R) -> str:
    """The optimized text of the EXECUTOR's decode or mixed step program
    (the model's step, the sampler and what the program does around
    them), for the described chip."""
    from xllm_service_tpu.runtime import executor as executor_mod

    cfg, mod, params, (k, v), s = _family_case(family, one_chip)
    ex = object.__new__(executor_mod.ModelExecutor)  # the step bodies read these two
    ex.cfg, ex.model_mod = cfg, mod
    i32 = jnp.int32
    # a state-pool family's table is one slot wide (+ the K/V blocks where it has both)
    cb = {"brumby": 1, "granite": CB + 1}.get(family, CB)
    counts = s((R, cfg.vocab_size), i32)
    pack = s((R, len(executor_mod.DEC_FIELDS) + cb), i32)
    prev = s((R,), i32)
    if step == "decode":
        fn = jax.jit(ex._decode_impl, donate_argnums=(0, 1, 2), static_argnames=("use_kernel",))
        return fn.lower(k, v, counts, params, pack, prev).compile().as_text()
    pf_pack = s((P, len(executor_mod.PF_FIELDS) + LPAD + cb), i32)
    fn = jax.jit(
        ex._mixed_impl, donate_argnums=(0, 1, 2),
        static_argnames=("lpad",),
    )
    return fn.lower(k, v, counts, params, pack, prev, pf_pack, lpad=LPAD).compile().as_text()


# Pallas kernel -> the region its custom call sits in
KERNEL_REGIONS = {
    "paged_attention_kernel": "attn", "prefill_attention_kernel": "attn",
    "mla_paged_attention_kernel": "attn", "mla_prefill_kernel": "attn",
    "mla_materialised_prefill_kernel": "attn",
    "kv_write_kernel": "cache_write",
    "retention_update_kernel": "state_mixer", "retention_chunk_kernel": "state_mixer",
    "mamba_update_kernel": "state_mixer", "mamba_chunk_kernel": "state_mixer",
    "moe_grouped_kernel": "moe_experts", "moe_grouped_down_kernel": "moe_experts",
}
EXPECTED_KERNELS = {
    ("llama", "decode"): {"paged_attention_kernel", "kv_write_kernel"},
    ("llama", "mixed"): {"kv_write_kernel"},
    ("brumby", "decode"): {"retention_update_kernel"},
    ("brumby", "mixed"): {"retention_update_kernel", "retention_chunk_kernel"},
    ("deepseek", "decode"): {"mla_paged_attention_kernel", "kv_write_kernel", "moe_grouped_kernel",
                             "moe_grouped_down_kernel"},
    # a 256-row chunk over a bf16 latent pool: the materialised form (PR 55)
    ("deepseek", "mixed"): {"mla_paged_attention_kernel", "mla_materialised_prefill_kernel",
                            "kv_write_kernel", "moe_grouped_kernel", "moe_grouped_down_kernel"},
    ("granite", "decode"): {"mamba_update_kernel", "paged_attention_kernel", "kv_write_kernel",
                            "moe_grouped_kernel", "moe_grouped_down_kernel"},
    ("granite", "mixed"): {"mamba_update_kernel", "paged_attention_kernel", "kv_write_kernel",
                           "moe_grouped_kernel", "moe_grouped_down_kernel"},
}


def _executed(text: str):
    """The instructions that run as ops of their own."""
    for body in regions._executed(*regions._computations(text)):
        yield from body.values()


@pytest.mark.parametrize("step", ["decode", "mixed"])
@pytest.mark.parametrize("family", sorted(FAMILY_TINY))
def test_every_compiled_op_of_a_step_program_has_a_region(
    one_chip, no_persistent_cache, as_on_tpu, family, step
):
    text = _compile_step(family, step, one_chip)
    got = regions.parse_regions(text)
    assert set(got.values()) <= set(DEVICE_REGIONS)
    ops = [i for i in _executed(text) if i.opcode in OPS_WITH_A_REGION]
    misses = [i.key for i in ops if i.key not in got]
    assert len(ops) > 20
    assert len(misses) <= 0.05 * len(ops), f"{len(misses)} of {len(ops)} without a region: {misses}"
    # each Pallas custom call keeps its kernel's name (the accepted kernel
    # readers and breakdown.device_ops match by it) and sits in its region
    kernels = {}
    for i in ops:
        if i.opcode == "custom-call" and (name := i.key.split(" ")[0].rsplit(".", 1)[0]) in KERNEL_REGIONS:
            kernels[name] = got.get(i.key)
    assert EXPECTED_KERNELS[family, step] <= set(kernels), sorted(kernels)
    assert kernels == {k: KERNEL_REGIONS[k] for k in kernels}
    # a step program holds ONE of the two MLA prefill forms (`lpad` is static)
    assert not {"mla_prefill_kernel", "mla_materialised_prefill_kernel"} <= set(kernels)
    # the weights' matmuls are where the floors of PERF.md hold them
    named = Counter(got.values())
    assert named["ffn"] and named["attn_proj"] and named["head"] and named["sample"]


def test_the_sampler_writes_no_array_of_the_batchs_rows_by_vocabulary(
    one_chip, no_persistent_cache, as_on_tpu
):
    """ops/sampling.py's work follows the rows (ISSUE 48): in the decode
    step program no op of the `sample` region PRODUCES a float32 [R, V]
    array (the scaled logits written out as a conditional's operand and
    the pass-through branch's copy of them were 0.43 ms of a 128-slot
    step), and no conditional anywhere hands one back. The logits
    themselves are the `head` region's, and the loops' and branches'
    tuples only pass them on by reference. R = 40 here: wider than the
    sampler's widest block, so a block is not mistaken for the batch."""
    from xllm_service_tpu.models.configs import get_model_config
    from xllm_service_tpu.ops import sampling

    rows = 40
    assert rows > sampling.BLOCK_ROWS
    text = _compile_step("llama", "decode", one_chip, R=rows)
    whole = ("f32", f"{rows},{get_model_config('llama3-3b').vocab_size}")
    got = regions.parse_regions(text)
    comps, entry = regions._computations(text)
    plumbing = ("tuple", "get-tuple-element", "parameter", "while", "bitcast")
    sample_ops, writers, handed_back = 0, [], []
    for body in regions._executed(comps, entry):
        for ins in body.values():
            # every (dtype, dims) of the result, a tuple's elements too
            is_whole = whole in re.findall(r"\b(\w+)\[([\d,]*)\]", ins.key.split(" ", 1)[1])
            if ins.opcode == "conditional" and is_whole:
                handed_back.append(ins.key)
            if got.get(ins.key) != "sample":
                continue
            sample_ops += 1
            if ins.opcode not in plumbing and is_whole:
                writers.append(ins.key)
    assert sample_ops > 10
    assert not writers, writers
    assert not handed_back, handed_back
