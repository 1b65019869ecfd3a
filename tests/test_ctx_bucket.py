"""One context bucket wherever attention runs as the kernels
(`ModelExecutor._ctx_bucket`, docs/KV_CACHE.md "Context buckets"): a step
takes a table as wide as it needs. The Pallas kernels walk a row's context
and not its table, so there that is the WHOLE table and one decode and one
mixed program serve every context; the gather and blockwise fallbacks read
every column, so there it is the next power of two. What is pinned here,
on the CPU:

  * the rule, from the decision the dispatchers take their branch from
    (`ops.attention.attention_routes` behind the platform seam
    `ops.attention._on_tpu`, over pools given by shape for the routes a CPU
    executor cannot build), and a window family's whole table under either;
  * a seeded mixed workload served with the whole table and with the grid
    gives the same tokens and logprobs, on a GQA and on a hybrid stack
    (the gather reads the wider table; its masked columns weigh nothing);
  * with the whole table `lowering_count()` stays flat over a workload
    that crosses every boundary of the old grid, after the first decode
    and the first mixed step; with the grid it grows.

The programs themselves, lowered for a described v5e, are in
tests/test_tpu_compile.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops import kv_cache as kvc
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

BS = 16


def _cfg(model, **kw):
    base = dict(
        model=model, dtype="float32", block_size=BS, num_blocks=80,
        max_running_requests=4, max_seq_len=256, max_prefill_tokens=32,
        prefill_buckets=[32],
    )
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------------------ the rule


def _stub(routes, paged=True, window=False):
    """An executor as far as the rule reads it: no weights, no pools, the
    decisions (ops.attention.Routes) for the launches over its pools."""
    ex = object.__new__(ModelExecutor)
    ex.has_paged_cache = paged
    ex.window_tables = window
    ex.max_blocks_per_seq = 16 if paged else 1
    ex._attention_routes = lambda: routes
    ex.whole_table = ex._table_costs_nothing()
    return ex


GRID = [1, 2, 4, 4, 8, 8, 8, 8, 16, 16, 16, 16, 16, 16, 16, 16]


def _pool(lanes=128, block=16, int8=False):
    """A pool as far as the decision reads it: its shape and whether it
    is quantized."""
    data = jax.ShapeDtypeStruct((2, 8, 4, block, lanes), jnp.int8 if int8 else jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((2, 8, 4, 8, block), jnp.float32) if int8 else None
    return kvc.PagedKV(data, scale)


@pytest.mark.parametrize(
    "pools,env,whole",
    [
        ([dict()], {}, True),
        ([dict(latent=True)], {}, True),
        ([dict(), dict(sinks=True)], {}, True),  # a second pool, with a sink
        ([dict(on=False)], {}, False),
        ([dict()], {"XLLM_PAGED_ATTENTION_KERNEL": "0"}, False),
        ([dict(lanes=64)], {}, False),  # unpacked narrow rows: the gather on the chip too
        ([dict()], {"XLLM_PREFILL_ATTENTION_KERNEL": "0"}, False),
        ([dict(latent=True, int8=True, block=128)], {}, False),  # blockwise chunks
        # the verify shapes ride the multi-query kernel or go as the chunks
        # do: a speculative engine's table is whole under either
        ([dict()], {"XLLM_MQ_ATTENTION_KERNEL": "1"}, True),
        ([dict()], {"XLLM_MQ_ATTENTION_KERNEL": "0"}, True),
        ([dict(latent=True)], {"XLLM_MQ_ATTENTION_KERNEL": "1"}, True),
        ([dict(), dict(lanes=64)], {}, False),  # one pool of two falls back
    ],
    ids=["gqa-kernels", "mla-kernels", "two-pools", "fallback", "decode-forced-off",
         "unpacked-at-tp", "prefill-forced-off", "mla-int8-prefill", "spec-mq",
         "spec-no-mq", "spec-mla-mq", "second-pool-falls-back"],
)
def test_the_table_is_whole_where_every_launch_is_a_kernel(monkeypatch, pools, env, whole):
    """The rule asks the dispatchers' own decision one yes/no question."""
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    routes = []
    for kw in pools:
        kw = dict(kw)
        monkeypatch.setattr(attention, "_on_tpu", lambda on=kw.pop("on", True): on)
        cache = _pool(**{k: kw.pop(k) for k in ("lanes", "block", "int8") if k in kw})
        routes.append(attention.attention_routes(cache, 8, 128, **kw))
    ex = _stub(routes)
    assert ex.whole_table is whole
    buckets = [ex._ctx_bucket(need) for need in range(1, 17)]
    assert buckets == ([16] * 16 if whole else GRID)
    assert list(ex._decode_cb_walk()) == ([16] if whole else [1, 2, 4, 8, 16])


@pytest.mark.parametrize(
    "rows,pool,on_chip,prefill",
    [
        (512, {}, True, "mla-flash-mat"),
        (256, {}, True, "mla-flash-mat"),
        (128, {}, True, "mla-flash"),  # under the bound: absorbed
        (attention.MQ_MAX_ROWS, {}, True, "mla-flash"),  # the verify shapes
        (0, {}, True, "mla-flash"),  # asked about no launch in particular
        (512, dict(int8=True, block=128), True, "blockwise"),
        (512, {}, False, "blockwise"),  # off the chip: the scan, the oracle
        (512, dict(latent=False), True, "flash"),  # a GQA pool has one form
    ],
    ids=["512", "256", "128", "verify", "unasked", "int8", "cpu", "gqa"],
)
def test_a_latent_pools_prefill_form_goes_by_the_rows_of_a_chunk(
    monkeypatch, rows, pool, on_chip, prefill
):
    """An MLA prefill launch has two forms of one algebra: the materialised
    flash kernel from MLA_MATERIALISE_ROWS rows a chunk on, the absorbed
    one below; an int8 latent pool keeps the blockwise scan. The report
    names the form, and either kernel walks a chunk's context."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_chip)
    pool = dict(pool)
    latent = pool.pop("latent", True)
    routes = attention.attention_routes(
        _pool(**pool), 8, 128, latent=latent, prefill_rows=rows
    )
    assert routes.materialised is (prefill == "mla-flash-mat")
    rep = routes.report()
    assert rep["prefill"] == prefill
    assert rep["mixed"] == f"{rep['decode']}+{prefill}"
    # the verify shapes: the multi-query kernel where it serves (GQA), else
    # what a chunk of a few rows takes, never the materialised form
    assert rep["mq"] == ("mq" if routes.verify else prefill.replace("-mat", ""))
    assert routes.bounded_by_context is (prefill != "blockwise")
    # a caller's own switch forces the kernels: the rows still pick the form
    forced = attention.attention_routes(
        _pool(**pool), 8, 128, latent=latent, prefill_rows=rows, use_kernel=True
    )
    assert forced.materialised is (latent and "int8" not in pool and rows >= 256)
    off = attention.attention_routes(
        _pool(**pool), 8, 128, latent=latent, prefill_rows=rows, use_kernel=False
    )
    assert not off.materialised and off.report()["prefill"] == "blockwise"


@pytest.mark.parametrize("decode", [True, False], ids=["paged", "gather"])
def test_a_window_family_takes_the_whole_table_under_either(decode):
    ex = _stub([attention.Routes(False, decode, False, False, 1, False)], window=True)
    assert ex.whole_table and {ex._ctx_bucket(n) for n in range(1, 17)} == {16}


def test_a_state_pool_alone_has_one_column_and_asks_no_report():
    ex = _stub(None, paged=False)  # (routes of None: never read)
    assert not ex.whole_table and ex._ctx_bucket(1) == 1


@pytest.mark.parametrize(
    "model,on_chip,whole",
    [
        ("llama3-shard-tiny", False, False),
        ("llama3-shard-tiny", True, True),  # 128-lane rows, 16-row blocks: the kernels
        ("llama3-tiny", True, False),  # 64-lane rows: the gather on the chip too
        ("deepseek-tiny", False, False),
        ("deepseek-tiny", True, True),
        ("granite-tiny", True, False),  # 16-lane K/V beside the state pool
        ("mimo-tiny", False, True),  # a window family: on every backend
        ("mimo-tiny", True, True),
        ("brumby-tiny", True, False),  # a state pool alone: one column
    ],
)
def test_an_executor_decides_at_build_time_from_its_own_report(
    cpu_devices, monkeypatch, model, on_chip, whole
):
    """The platform seam: what the dispatchers would do on the attached
    backend decides, once, when the executor is built (no kernel runs
    here: nothing is dispatched)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_chip)
    ex = ModelExecutor(_cfg(model), init_seed=0)
    rep = ex.kernel_report()
    kernels = (rep["decode"], rep["prefill"]) in (("paged", "flash"), ("mla", "mla-flash"))
    assert ex.whole_table is whole and (kernels or ex.window_tables) is whole
    MB = ex.max_blocks_per_seq
    grid = [ModelExecutor._pow2_bucket(n, MB) for n in range(1, MB + 1)]
    buckets = [ex._ctx_bucket(n) for n in range(1, MB + 1)]
    assert buckets == ([MB] * MB if whole else grid)
    # the prewarm walks enumerate the family the rule leaves, by themselves
    assert list(ex._decode_cb_walk()) == sorted(set(buckets))
    assert {cb for _, cb, _, _ in ex._prefill_shape_family()} <= set(buckets)
    if whole:
        assert [b for b, _, _, _ in ex._prefill_shape_family()] == ex.prefill_buckets


# ------------------------------------- the same tokens, and a flat count


def _req(rid, outs, prompt, sampling):
    def cb(o):
        for s in o.outputs:
            outs.setdefault(rid, []).extend(s.token_ids)
            outs.setdefault(rid + "/lp", []).extend(lp.data.logprob for lp in s.logprobs)
        return True

    return EngineRequest(request_id=rid, prompt_token_ids=list(prompt),
                         sampling=sampling, callback=cb)


def _sp(max_new, **kw):
    return SamplingParams(max_new_tokens=max_new, logprobs=True, ignore_eos=True, **kw)


def _serve(model, whole_table):
    """One seeded mixed workload (greedy, seeded, penalized; chunked
    prompts of 1 to 10 blocks admitted while others decode; answers that
    carry a row across the 1-, 2-, 4- and 8-block boundaries) on a CPU
    executor, whose launches are the gather and the blockwise scan, with
    the grid it takes by itself or with the whole table a kernel executor
    takes. Returns (outs, lowerings after the first request's second
    token, lowerings at the end)."""
    cfg = _cfg(model)
    ex = ModelExecutor(cfg, init_seed=0)
    assert not ex.whole_table and ex.max_blocks_per_seq == 16
    ex.whole_table = whole_table
    eng = InferenceEngine(cfg, executor=ex)
    rng = np.random.default_rng(51)
    outs = {}

    def add(rid, n, sampling):
        eng.add_request(_req(rid, outs, rng.integers(0, 500, n), sampling))

    # 11 -> 71 tokens: 1, 2, 4, 8 blocks (penalized, so that the admission's
    # scatter over the slot's histogram is met here too)
    add("first", 11, _sp(60, temperature=0.0, presence_penalty=0.1))
    while len(outs.get("first", ())) < 2:
        eng.step()
    after_first = ex.lowering_count()
    add("seeded", 40, _sp(30, temperature=0.9, top_k=20, seed=5))  # 3 -> 5 blocks
    for _ in range(3):
        eng.step()
    add("long", 150, _sp(12, temperature=0.0))  # five chunks, 2 -> 10 blocks, then 11
    for _ in range(7):  # (one prompt a step: a group of two is a program of its own)
        eng.step()
    add("penal", 100, _sp(40, temperature=0.6, seed=11, presence_penalty=0.4,
                          frequency_penalty=0.2))  # 7 -> 9 blocks
    for _ in range(2000):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()
    assert {r: len(outs[r]) for r in ("first", "seeded", "long", "penal")} == {
        "first": 60, "seeded": 30, "long": 12, "penal": 40}
    return outs, after_first, ex.lowering_count()


@pytest.fixture(scope="module", params=["llama3-shard-tiny", "granite-tiny"])
def served_both_ways(request, cpu_devices):
    return _serve(request.param, False), _serve(request.param, True)


def test_the_whole_table_serves_the_grids_tokens_and_logprobs(served_both_ways):
    (grid, _, _), (whole, _, _) = served_both_ways
    for rid in ("first", "seeded", "long", "penal"):
        assert whole[rid] == grid[rid], rid
        np.testing.assert_allclose(whole[rid + "/lp"], grid[rid + "/lp"], atol=2e-5, err_msg=rid)


def test_lowerings_stay_flat_across_every_old_bucket_boundary(served_both_ways):
    (_, grid_first, grid_end), (_, whole_first, whole_end) = served_both_ways
    # one decode and one mixed program (and the admission's histogram
    # scatter) were there after the first request's second token; nothing
    # the rest of the workload met was new
    assert whole_end == whole_first == 3
    # the grid met a program at (nearly) every boundary it crossed
    assert grid_end >= grid_first + 6


@pytest.mark.parametrize(
    "model", ["llama3-shard-tiny", "deepseek-tiny", "granite-tiny", "mimo-tiny", "brumby-tiny"]
)
def test_every_array_a_step_donates_is_placed_at_build(cpu_devices, model):
    """A step hands its pools and the histogram back COMMITTED to the mesh;
    an unplaced first copy is another signature, and the first step program
    of a new executor compiled twice (the MLA family's one-element V dummy
    until PR 51: a second whole-model mixed program in doc-steady)."""
    import jax

    ex = ModelExecutor(_cfg(model), init_seed=0)
    leaves = jax.tree.leaves((ex.k_cache, ex.v_cache, ex.token_counts))
    assert leaves and all(x.committed for x in leaves)


# ------------------------------- the report is the dispatchers' decision

# attention launch kind -> its Pallas call's name in a traced program (a
# window layer's launches carry "window_" before it)
GQA_KERNELS = {"decode": "paged_attention_kernel", "prefill": "flash_prefill_kernel",
               "verify": "multiquery_paged_attention_kernel"}
MLA_KERNELS = {"decode": "mla_paged_attention_kernel", "prefill": "mla_prefill_kernel",
               "prefill_mat": "mla_materialised_prefill_kernel",
               "verify": "mla_multiquery_attention_kernel"}
ATTENTION_KERNEL = re.compile(r"\b((?:window_)?(?:%s))\b" % "|".join(
    sorted(set(GQA_KERNELS.values()) | set(MLA_KERNELS.values()))))


def _traced_step_programs(monkeypatch):
    """Patch `ModelExecutor._step_jit`: no kernel can run here, so every
    step program is a stand-in that TRACES each new signature, keeps the
    names of the attention kernels its dispatchers launched
    ({program: names}) and hands back zeros of the program's outputs."""
    seen = {}

    def step_jit(self, impl, **jit_kw):
        jitted = jax.jit(impl, **jit_kw)
        mine = seen.setdefault(impl.__name__, set())
        outs = {}

        def call(*a, **kw):
            key = str(jax.tree.map(lambda x: getattr(x, "shape", x), (a, kw)))
            if key not in outs:
                traced = jitted.trace(*a, **kw)
                mine.update(ATTENTION_KERNEL.findall(str(traced.jaxpr)))
                outs[key] = traced.out_info
            return jax.tree.map(lambda o: jnp.zeros(o.shape, o.dtype), outs[key])

        call._cache_size = lambda: len(outs)
        return call

    monkeypatch.setattr(ModelExecutor, "_step_jit", step_jit)
    return seen


def _launch_names(routes, prefix, spec):
    """The attention kernels a pool's launches run as, by the decision."""
    names = MLA_KERNELS if routes.latent else GQA_KERNELS
    kinds = ["decode"] * routes.decode
    if routes.prefill:  # a latent pool's chunk: the form its rows picked
        kinds.append("prefill_mat" if routes.materialised else "prefill")
    if spec and routes.verify:
        kinds.append("verify")
    elif spec and routes.prefill:  # verify shapes go as a few-row (absorbed) prefill
        kinds.append("prefill")
    return {prefix + names[k] for k in kinds}


@pytest.mark.parametrize(
    "model,on_chip,spec,widen,engine",
    [(*case, {}) for case in [
        ("llama3-shard-tiny", False, 0, {}),
        ("llama3-shard-tiny", True, 0, {}),
        ("llama3-tiny", True, 0, {}),
        ("deepseek-tiny", False, 0, {}),
        ("deepseek-tiny", True, 0, {}),
        ("granite-tiny", True, 0, {}),
        ("mimo-tiny", False, 0, {}),
        ("mimo-tiny", True, 0, {}),
        ("brumby-tiny", True, 0, {}),
        # the launches of a speculative engine's verify shapes
        ("llama3-shard-tiny", True, 3, {}),
        ("deepseek-tiny", True, 3, {}),
        # ... and a window family (a sink a window layer) with K/V rows wide
        # enough for the kernels
        ("mimo-tiny", True, 0, dict(head_dim=128, attn_v_head_dim=128)),
    ]] + [
        # a latent pool's chunk of 256 rows: the materialised form, on the
        # chip alone; the verify shapes beside it stay absorbed
        ("deepseek-tiny", on_chip, spec, {},
         dict(max_seq_len=256, max_prefill_tokens=256, prefill_buckets=[256]))
        for on_chip, spec in [(False, 0), (True, 0), (True, 3)]
    ],
)
def test_the_report_is_the_route_each_dispatcher_takes_when_traced(
    cpu_devices, monkeypatch, model, on_chip, spec, widen, engine
):
    """ONE decision: the attention kernels the executor's step programs
    launch when traced (every family prewarm_programs walks: decode,
    split prefill, mixed, verify) are the ones `kernel_report()` and
    `_attention_routes()` name, pool by pool, and `whole_table` follows
    from the same decision."""
    import dataclasses

    from xllm_service_tpu.models.configs import get_model_config

    monkeypatch.setattr(attention, "_on_tpu", lambda: on_chip)
    seen = _traced_step_programs(monkeypatch)
    cfg = _cfg(model, **{"speculative_tokens": spec, "max_seq_len": 64, **engine})  # (a short grid to trace)
    model_cfg = dataclasses.replace(get_model_config(model), **widen) if widen else None
    ex = ModelExecutor(cfg, init_seed=0, model_cfg=model_cfg)
    try:
        ex.prewarm_programs(p_groups=False)
        routes = ex._attention_routes()
        want = set()
        for r, prefix in zip(routes, ("", "window_")):
            want |= _launch_names(r, prefix, spec)
        got = set().union(*seen.values())
        assert got == want, (got, ex.kernel_report())
        assert "_decode_impl" in seen and (spec == 0 or any("verify" in p for p in seen))
        rep = ex.kernel_report()
        if routes:  # the names the records carry say the same
            full = routes[0]
            assert (rep["decode"] in ("paged", "mla")) is full.decode
            assert (rep["prefill"] in ("flash", "mla-flash", "mla-flash-mat")) is full.prefill
            assert (rep["prefill"] == "mla-flash-mat") is full.materialised
            assert full.materialised is (full.latent and on_chip and bool(engine))
            assert rep["mixed"] == f"{rep['decode']}+{rep['prefill']}"
            assert rep["mq"] == ("mla-mq" if full.latent else "mq") if full.verify \
                else rep["mq"] == rep["prefill"].replace("-mat", "")
        if len(routes) > 1:
            w = routes[1]
            assert rep["window"] == f"window-{'pallas' if w.decode and w.prefill else 'xla'}"
            assert not (w.verify and ex.cfg.window_sink)
        assert ex.whole_table is bool(
            ex.window_tables or (routes and all(r.bounded_by_context for r in routes))
        )
    finally:
        attention.set_shard_context(None)


@pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sink"])
def test_verify_shapes_over_a_pool_with_a_sink_go_as_the_report_says(monkeypatch, sinks):
    """The multi-query kernel has no sink logit, so verify shapes over a
    pool whose layers carry one go to the flash kernel, and the report
    says so (a window family refuses to speculate at build, so this is
    the dispatcher alone; until PR 52 the report said `mq` either way)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    k = jnp.zeros((8, 2, BS, 128), jnp.bfloat16)
    tables, rows = jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q: attention.prefill_attention(
            q, k, k, tables, rows, 4 * rows, 0.1,
            sinks=jnp.zeros((4,), jnp.float32) if sinks else None,
        )
    )(jnp.zeros((2, 4, 4, 128), jnp.bfloat16))
    routes = attention.attention_routes(k, 4, 128, sinks=sinks)
    assert routes.verify is not sinks and routes.prefill
    assert set(ATTENTION_KERNEL.findall(str(jaxpr))) == {
        GQA_KERNELS["prefill" if sinks else "verify"]}
    assert routes.report()["mq"] == ("flash" if sinks else "mq")
