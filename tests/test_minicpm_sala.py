"""MiniCPM-SALA on the normal path (models/granite.py, the kinds "sparse"
and "lightning"): block-sparse attention whose pages the QUERY selects (a
compressed-key pool beside K and V, a top-k over block scores, attention
over the selected pages alone) beside Lightning linear-attention layers (a
decayed outer-product state a head, rotary, a decay that is a constant of
the head and of the layer's PUBLISHED index). Everything at
`minicpm-sala-tiny` (blocks of 8, top-4 past 64 tokens), float32, against
the family's plain reference (benchmarks/families/minicpm_sala.py: the
recurrence, materialised attention under a mask made by a plain top_k at
every query position; nothing imported from the program)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.models import get_module, granite
from xllm_service_tpu.models.configs import approx_param_count, get_model_config
from xllm_service_tpu.ops import attention, kv_cache as kvc, lightning as lightning_ops
from xllm_service_tpu.ops import sparse_attention as sparse_ops
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import HybridBlockManager, SparseFamilyUnsupported
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("minicpm-sala-tiny")
SEL = sparse_ops.selection_of(CFG)
BS = 8
T = 112  # past dense_len (64) by six blocks
ATOL = 3e-5  # float32, logits of about 1


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "minicpm-sala-tiny", "family": "minicpm_sala"})


def _rehearse_config():
    with open(os.path.join(ROOT, "benchmarks", "configs", "rehearse-minicpm-sala-tiny.json")) as f:
        return json.load(f)


def _family_config(c=CFG):
    """The tiny preset as a configuration file says it (the rehearsal's
    file, with the preset's own vocabulary)."""
    return {**_rehearse_config(), "vocab_size": c.vocab_size}


def test_the_family_file_reads_the_preset_back():
    fam, m = _family(), _family_config()
    assert fam.model_config("minicpm-sala-tiny", m) == CFG and get_module(CFG) is granite
    want = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    have = jax.eval_shape(lambda: fam.make_weights(m, jax.random.key(0), jnp.float32))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(want) == shape(have)


def test_the_two_kinds_and_their_memory():
    c = CFG
    assert c.layer_types == ("sparse", "lightning", "lightning", "sparse")
    assert c.state_layer_kind == "lightning" and c.num_state_layers == 2
    assert c.num_attention_layers == c.num_sparse_layers == 2 and not c.num_window_layers
    assert c.has_state_pool and c.has_paged_cache and not c.is_moe
    assert c.num_heads // c.num_kv_heads == 2 and c.rotary_dim == 0 and c.qk_norm and c.attn_gate
    # three runs; the two lightning layers are ONE scan
    segs = granite._segments(c)
    assert [(s.kind, s.n, s.kind_first) for s in segs] == [
        ("sparse", 1, 0), ("lightning", 2, 0), ("sparse", 1, 1)]
    assert granite.lightning_layer_ids(c) == (10, 11)
    params = jax.eval_shape(lambda: granite.init_params(c, jax.random.key(0), jnp.float32))
    assert set(params) == {"embed", "final_norm", "layers", "attn", "lightning", "lm_head"}
    assert set(params["attn"]) == {"wq", "wk", "wv", "wo", "w_ogate", "q_norm", "k_norm"}
    assert set(params["lightning"]) == {"wq", "wk", "wv", "w_ogate", "q_norm", "k_norm",
                                        "o_norm", "wo"}
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    small = sum(int(np.prod(params[s][k].shape)) for s, ks in (
        ("layers", ("attn_norm", "mlp_norm")), ("attn", ("q_norm", "k_norm")),
        ("lightning", ("q_norm", "k_norm", "o_norm")),
    ) for k in ks) + c.hidden_size
    assert approx_param_count(c) == n - small
    # the state pool, and the compressed-key pool in the convolution pool's place
    S, ck = granite.state_shapes(c, 3, 40)
    assert S == (2, 3, 4, 16, 16) and ck == (2, 40, 8, 16)
    assert granite.state_shapes(c, 1)[1] == (2, 0, 8, 16)  # a slot holds none of it
    with pytest.raises(ValueError, match="beside lightning layers alone"):
        granite.init_params(dataclasses.replace(
            c, layer_types=("sparse", "attention", "lightning", "sparse")),
            jax.random.key(0), jnp.float32)
    with pytest.raises(ValueError, match="the selection fits under dense_len"):
        granite.init_params(dataclasses.replace(c, sparse_dense_len=24), jax.random.key(0),
                            jnp.float32)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = get_model_config("minicpm-sala")
    assert (c.num_layers, c.vocab_size, c.hidden_size, c.intermediate_size) == (8, 73448, 4096, 16384)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim) == (32, 2, 128, 0)
    assert (c.lightning_n_heads, c.lightning_d_head, c.rope_theta) == (32, 128, 10000.0)
    assert c.layer_types == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert c.layer_ids == tuple(range(9, 17)) and c.published_layers == 32
    assert sparse_ops.selection_of(c) == sparse_ops.Selection(64, 64, 32, 16, 1, 32, 8192)
    assert (c.embedding_multiplier, c.logits_scaling) == (12.0, 16.0)
    assert c.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5)
    assert approx_param_count(c) == 2_820_472_832  # 2,820 M: 5.64 GB in bfloat16
    S, ck = granite.state_shapes(c, 1, 1)
    assert int(np.prod(S)) * 4 == 12_582_912 and S == (6, 1, 32, 128, 128)
    assert int(np.prod(ck)) * 4 == 8192  # a 64-token page: 128 B a token beside 2,048 B of K and V
    assert lightning_ops.kernel_shape_ok(jnp.zeros((1, 1) + S[2:]))
    # six consecutive lightning layers: one scan
    assert [(s.kind, s.n) for s in granite._segments(c)] == [
        ("sparse", 1), ("lightning", 6), ("sparse", 1)]
    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        m = json.load(f)
    assert _family().model_config("minicpm-sala", m) == c
    assert [m["mixer_types"][i] for i in m["layer_ids"]] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])


# ---------------------------------------------------- steps vs the reference


def _pools(cfg=CFG, blocks=40, slots=3):
    ss, cs = granite.state_shapes(cfg, slots, blocks)
    kv = (cfg.num_attention_layers, blocks, cfg.num_kv_heads, BS, cfg.head_dim)
    z = lambda s: jnp.zeros(s, jnp.float32)
    return (kvc.PagedKV(z(kv), None), z(ss)), (kvc.PagedKV(z(kv), None), z(cs))


def _serve(params, cfg, toks, n_prefill, chunk):
    """Logits of every position from n_prefill - 1 on: the prompt in
    chunks into slot 1, then token by token on row 1, through the step
    functions."""
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, cfg, K, V, *a))
    decode = jax.jit(lambda p, K, V, *a: granite.decode_step(p, cfg, K, V, *a))
    K, V = _pools(cfg)
    CB = 16
    table = np.zeros((CB + 1,), np.int32)
    table[:-(-len(toks) // BS)] = 1 + np.arange(-(-len(toks) // BS))
    table[-1] = 2  # slot 1
    outs = []
    for pos in range(0, n_prefill, chunk):
        n = min(chunk, n_prefill - pos)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n] = toks[pos:pos + n]
        lg, K, V = prefill(params, K, V, jnp.asarray(ids), jnp.asarray([pos]), jnp.asarray([n]),
                           jnp.asarray(table)[None])
    outs.append(lg[0])
    for t in range(n_prefill, len(toks)):
        tab = np.zeros((3, CB), np.int32)
        tab[1] = table[:-1]
        lg, K, V = decode(params, K, V, jnp.asarray([0, toks[t], 0]), jnp.asarray([0, t, 0]),
                          jnp.asarray(tab), jnp.asarray([False, True, False]))
        outs.append(lg[1])
    return jnp.stack(outs), (K, V)


@pytest.fixture(scope="module")
def seeded():
    fam, m = _family(), _family_config()
    params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    toks = np.asarray(jax.random.randint(jax.random.key(6), (T,), 0, CFG.vocab_size))
    ref = jax.jit(lambda p, t: fam.forward_logits(p, m, t, jnp.arange(T)))(
        params, jnp.asarray(toks, jnp.int32))
    return fam, m, params, toks, ref


CUTS = {"one-chunk": (100, 128), "three-chunks": (96, 32), "ragged": (50, 16),
        "token-by-token": (8, 8)}


@pytest.fixture(scope="module")
def cut_up(seeded):
    """The same 112 tokens served four ways: one chunk across the switch,
    whole chunks, ragged chunks that end under it, one block and then
    token by token."""
    _, _, params, toks, _ = seeded
    return {name: _serve(params, CFG, toks, *cut)[0] for name, cut in CUTS.items()}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_prefill_then_decode_through_the_caches_equals_the_family_reference(seeded, cut_up, cut):
    ref = seeded[4]
    np.testing.assert_allclose(cut_up[cut], ref[CUTS[cut][0] - 1:], atol=ATOL)


@pytest.mark.parametrize("cut", sorted(set(CUTS) - {"token-by-token"}))
def test_a_tokens_logits_do_not_depend_on_how_its_request_was_cut_up(cut_up, cut):
    """The switch to the selected pages is per QUERY POSITION: a prompt
    served as one chunk (sparse from position 64 on, inside the chunk),
    as several, and token by token gives the same logits."""
    base, first = cut_up["token-by-token"], CUTS[cut][0]
    np.testing.assert_allclose(cut_up[cut], base[first - 8:], atol=ATOL)


def test_the_released_per_call_switch_would_not_survive_chunking(seeded, monkeypatch):
    """What the rule guards against: were a whole call sparse or dense by
    its length (here: every row of a chunk dense), the same tokens would
    read differently by how they were cut."""
    _, _, params, toks, ref = seeded
    sound = sparse_ops.chunk_selected_attention
    monkeypatch.setattr(granite.sparse_ops, "selection_of", lambda cfg: SEL._replace(
        dense_len=10 ** 6))
    got, _ = _serve(params, CFG, toks, 100, 128)
    assert sound is sparse_ops.chunk_selected_attention
    assert float(jnp.abs(got - ref[99:]).max()) > 100 * ATOL


def test_the_programs_selected_blocks_are_the_references(seeded):
    """Stage 1 at every position past dense_len, out of the pools the
    served prompt left behind, against the reference's plain top_k: the
    same block sets, both KV heads, both sparse layers."""
    fam, m, params, toks, _ = seeded
    _, (K, V) = _serve(params, CFG, toks, T, 128)
    CK = V[1]
    f32 = jnp.float32
    x = fam.embed(params, m, jnp.asarray(toks))
    table = jnp.asarray(1 + np.arange(16), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    compared = 0
    for l in range(CFG.num_layers):
        if CFG.layer_types[l] == "sparse":
            a = CFG.layer_types[:l].count("sparse")
            lp = {k: w[a].astype(f32) for k, w in params["attn"].items()}
            u = fam._rms_norm(x, params["layers"]["attn_norm"][l], CFG.rms_norm_eps)
            want = fam.sparse_mixer(u, lp, m, return_blocks=True)  # [T, Hkv, NB]
            q = fam._rms_norm((u @ lp["wq"]).reshape(T, CFG.num_heads, -1), lp["q_norm"],
                              CFG.rms_norm_eps)
            scores = sparse_ops.block_scores(
                q.reshape(T, CFG.num_kv_heads, -1, CFG.head_dim),
                sparse_ops._compressed_context(CK, a, table, SEL.per_block), pos, CFG.head_dim ** -0.5, SEL)
            got = sparse_ops.select_blocks(scores, pos, SEL)  # [T, Hkv, topk]
            for t in range(SEL.dense_len, T):
                for h in range(CFG.num_kv_heads):
                    assert set(np.asarray(got[t, h])) == set(np.nonzero(np.asarray(want[t, h]))[0])
                    assert int(got[t, h, -1]) == t // BS  # the row's own block is the last
                    compared += 1
        x = fam.layer_terms(x, params, l, m)[2]
    assert compared == 2 * 2 * (T - SEL.dense_len)


def test_at_topk_blocks_or_fewer_the_selection_is_the_whole_context(seeded):
    """Context of at most topk blocks: every block the row can see is
    selected, and the reference's selected attention is its dense one."""
    fam, m, params, toks, _ = seeded
    pos = jnp.asarray([0, 7, 8, 20, 31], jnp.int32)
    scores = jax.random.uniform(jax.random.key(0), (5, 2, 16))
    got = np.asarray(sparse_ops.select_blocks(scores, pos, SEL))
    for i, p in enumerate(np.asarray(pos)):
        for h in range(2):
            assert set(range(p // BS + 1)) <= set(got[i, h])
    short = {**m, "sparse_config": {**m["sparse_config"], "dense_len": 8}}
    u = fam._rms_norm(fam.embed(params, m, jnp.asarray(toks[:32])), 1.0, 1e-6)
    lp = {k: w[0] for k, w in params["attn"].items()}
    np.testing.assert_allclose(fam.sparse_mixer(u, lp, short),
                               fam.sparse_mixer(u, lp, short, always_dense=True), atol=1e-6)


PUBLISHED = sparse_ops.Selection(64, 64, 32, 16, 1, 32, 8192)
SCORES = {
    "uniform": lambda k, s: jax.random.uniform(k, s) * 16,
    "nine-tenths-zeros": lambda k, s: jnp.where(
        jax.random.uniform(jax.random.fold_in(k, 1), s) < 0.9, 0.0, jax.random.uniform(k, s)),
    "whole-numbers": lambda k, s: jnp.round(jax.random.uniform(k, s) * 4),
    "all-equal": lambda k, s: jnp.full(s, 0.25),
}
# the first of the 64 rows' positions (65 tokens apart: a block a row), by the table's columns NB
ROWS_AT = {
    "under-dense-len": lambda NB: PUBLISHED.dense_len - 64 * 65,
    "topk-blocks-or-fewer": lambda NB: 0,
    "past-dense-len": lambda NB: max(NB * PUBLISHED.block, PUBLISHED.dense_len + 64 * 65) - 64 * 65,
}


@pytest.mark.parametrize("where", sorted(ROWS_AT))
@pytest.mark.parametrize("scores", sorted(SCORES))
@pytest.mark.parametrize("NB", [32, 256, 512, 1024], ids=lambda n: f"{n}-columns")
def test_the_selection_is_top_k_and_a_sort_of_its_indices_bit_for_bit(NB, scores, where):
    """The threshold and the running counts against the sorts they
    replace, over the same ranked scores: ties (a softmax that underflowed
    to exact zeros, scores that repeat) go to the lower index as
    `lax.top_k`'s do, rows under dense_len included."""
    sel, rows = PUBLISHED, 64
    pos = ROWS_AT[where](NB) + 65 * jnp.arange(rows, dtype=jnp.int32)
    s = SCORES[scores](jax.random.key(NB), (rows, 2, NB)).astype(jnp.float32)
    ranked = sparse_ops.ranked_scores(s, pos, sel)
    assert ranked.shape[-1] == max(NB, sel.topk)
    want = jnp.sort(jax.lax.top_k(ranked, sel.topk)[1].astype(jnp.int32), axis=-1)
    got = jax.jit(sparse_ops.select_blocks, static_argnums=2)(s, pos, sel)
    assert got.dtype == jnp.int32 and got.shape == (rows, 2, sel.topk)
    np.testing.assert_array_equal(got, want)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


def test_stage_one_holds_no_sort():
    """Neither the selection nor the whole of stage 1 (its three width
    branches, the rolled rounds) sorts: on the chip `lax.top_k` of 64 in
    1,024 columns is a full sort of every (row, KV head)."""
    sel = PUBLISHED
    s, pos = jnp.zeros((64, 2, 1024)), jnp.arange(64, dtype=jnp.int32)
    CK = jnp.zeros((1, 8, 2 * sel.per_block, 128))
    q, table = jnp.zeros((64, 32, 128)), jnp.zeros((1024,), jnp.int32)
    for closed in (
        jax.make_jaxpr(lambda s_, p_: sparse_ops.select_blocks(s_, p_, sel))(s, pos),
        jax.make_jaxpr(lambda q_, CK_: sparse_ops.virtual_tables(
            q_, CK_, 0, table, pos, pos >= 0, 0.1, sel, sel.topk))(q, CK),
    ):
        seen = set(_primitives(closed.jaxpr))
        assert "scan" in seen and "dot_general" in seen  # the rolled rounds; the running counts
        assert not {p for p in seen if "sort" in p or "top_k" in p}, seen


# ------------------------------------------------- the compressed-key pool


def test_a_compressed_key_spans_a_page_and_is_invisible_until_whole():
    """Keys written in ragged pieces (13, then 1, 1, 7, 30 tokens): every
    compressed key equals the mean of its 4 keys, the one that starts in
    a block's last 2 tokens and ends in the next page included, in the
    page it STARTS in; a key whose last token is not written yet is not
    written, and stage 1 does not see it."""
    Hkv, D, NBLK = 2, 16, 12
    k = jax.random.normal(jax.random.key(1), (52, Hkv, D))
    table = jnp.asarray([[3, 9, 1, 7, 5, 2, 11, 0]], jnp.int32)
    K = jnp.zeros((2, NBLK, Hkv, BS, D))
    CK = jnp.full((2, NBLK, Hkv * SEL.per_block, D), 99.0)
    start = 0
    for n in (13, 1, 1, 7, 30):
        for t in range(start, start + n):
            K = K.at[1, table[0, t // BS], :, t % BS].set(k[t])
        CK = sparse_ops.write_compressed(
            CK, K, 1, table, jnp.asarray([start]), jnp.asarray([n]), 32, SEL)
        start += n
        whole = (start - SEL.kernel) // SEL.stride + 1 if start >= SEL.kernel else 0
        for j in range(26):
            entry = CK[1, table[0, j // 4]].reshape(Hkv, 4, D)[:, j % 4]
            if j < whole:
                np.testing.assert_allclose(entry, k[2 * j:2 * j + 4].mean(0), atol=1e-6)
            else:
                assert float(entry.min()) == 99.0  # not whole yet: not written
    assert float(CK[0].min()) == 99.0  # the other layer's plane did not move
    j = 3  # tokens 6..9: starts in block 0 (page 3), ends in block 1 (page 9)
    np.testing.assert_allclose(CK[1, 3].reshape(Hkv, 4, D)[:, 3], k[6:10].mean(0), atol=1e-6)
    # visibility: key j is seen from position 2 j + 3 on
    q = jax.random.normal(jax.random.key(2), (3, Hkv, 2, D))
    ctx = sparse_ops._compressed_context(CK, 1, table[0], SEL.per_block)
    pos = jnp.asarray([8, 9, 51], jnp.int32)
    s = sparse_ops.block_scores(q, ctx, pos, 0.25, SEL)
    assert float(s[0, :, 1].max()) == 0.0  # at 8 nothing that overlaps block 1 is whole
    assert float(s[1, :, 1].min()) > 0.0  # at 9 key 3 is
    assert float(s[2, :, 6].min()) > 0.0 and float(s[2, :, 7].max()) == 0.0


def test_a_decode_row_completes_its_compressed_key_out_of_the_pool():
    """A decode row at position p completes key (p - 3) / 2 when that is
    whole, from K rows an earlier CHUNK wrote and its own."""
    Hkv, D = 2, 16
    k = jax.random.normal(jax.random.key(3), (24, Hkv, D))
    table = jnp.asarray([[4, 2, 6, 0], [0, 0, 0, 0]], jnp.int32)
    K = jnp.zeros((1, 8, Hkv, BS, D))
    for t in range(24):
        K = K.at[0, table[0, t // BS], :, t % BS].set(k[t])
    CK = jnp.full((1, 8, Hkv * 4, D), 99.0)
    for p in (8, 9, 10):
        CK = sparse_ops.write_compressed(
            CK, K, 0, table, jnp.asarray([p, 0]), jnp.asarray([1, 0]), 1, SEL)
    np.testing.assert_allclose(CK[0, 4].reshape(Hkv, 4, D)[:, 3], k[6:10].mean(0), atol=1e-6)  # from p = 9
    assert float(CK[0, 2].min()) == 99.0 and float(CK[0, 4].reshape(Hkv, 4, D)[:, :3].min()) == 99.0


# ---------------------------------------------------------------- lightning


def _qkv(key, shape):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(kk, shape) for kk in ks)


def test_lightning_recurrent_and_chunked_forms_agree():
    """The chunked form in sub-chunks of 8 against the recurrence: a chunk
    from an empty state, a ragged one (valid tokens end inside a
    sub-chunk), and a second chunk from the state the first left."""
    H, d, L = 4, 16, 40
    q, k, v = _qkv(jax.random.key(0), (L, H, d))
    log_lam = jnp.asarray(lightning_ops.log_decay(H, (10,), 32)[0])
    want, S_T = lightning_ops.recurrent_form(q, k, v, log_lam)
    S = jnp.zeros(lightning_ops.state_shape(2, 3, H, d))
    sl = jnp.asarray([1])
    o1, S = lightning_ops.chunk_update(
        S, 1, sl, jnp.asarray([0]), jnp.asarray([27]), q[None, :32], k[None, :32], v[None, :32],
        log_lam, sub=8)
    np.testing.assert_allclose(o1[0, :27], want[:27], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(S[1, 1], lightning_ops.recurrent_form(
        q[:27], k[:27], v[:27], log_lam)[1], rtol=1e-5, atol=1e-4)
    o2, S = lightning_ops.chunk_update(
        S, 1, sl, jnp.asarray([27]), jnp.asarray([13]), q[None, 27:], k[None, 27:], v[None, 27:],
        log_lam, sub=8)
    np.testing.assert_allclose(o2[0], want[27:], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(S[1, 1], S_T, rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(S[0]).max()) == 0.0 and float(jnp.abs(S[1, 0]).max()) == 0.0
    # a padding row (length 0) touches no slot; a chunk at position 0 ignores the slot
    _, S2 = lightning_ops.chunk_update(
        S, 1, sl, jnp.asarray([5]), jnp.asarray([0]), q[None, :8], k[None, :8], v[None, :8],
        log_lam, sub=8)
    np.testing.assert_array_equal(S2, S)
    o3, _ = lightning_ops.chunk_update(
        S, 1, sl, jnp.asarray([0]), jnp.asarray([8]), q[None, :8], k[None, :8], v[None, :8],
        log_lam, sub=8)
    np.testing.assert_allclose(o3[0], want[:8], rtol=1e-5, atol=1e-4)


def test_the_decay_is_a_constant_of_the_head_and_of_the_published_layer():
    got = lightning_ops.log_decay(32, (10, 15), 32)
    h = np.arange(1, 33)
    for row, l in zip(got, (10, 15)):
        np.testing.assert_allclose(row, -(2.0 ** (-8 * h / 32)) * (1 - l / 31 + 1e-5), rtol=1e-6)
    assert (got < 0).all() and (got[1] > got[0]).all()  # a deeper layer forgets more slowly
    fam, m = _family(), _family_config()
    np.testing.assert_allclose(np.log(fam.decay(m, 10)),
                               lightning_ops.log_decay(4, (10,), 32)[0], rtol=2e-5)


BROKEN = {
    "decay-of-layer-0": dict(layer_ids=(9, 0, 1, 16)),
    "decay-of-the-cut-depth": dict(published_layers=17),
    "theta-1e6": dict(rope_theta=1e6),
    "no-gate": dict(attn_gate=False),
    "residual-of-the-cut-depth": dict(residual_multiplier=1.4 / 4 ** 0.5),
    "logits-unscaled": dict(logits_scaling=1.0),
    "top-2": dict(sparse_topk=3),
    "no-init-block": dict(sparse_init_blocks=0),
    "no-local-blocks": dict(sparse_window=8),
    "stride-as-kernel": dict(sparse_kernel_size=2),
}


def _patched(monkeypatch, fault):
    """The program wrong in a way that is not a field of its configuration."""
    if fault == "no-rotary":
        monkeypatch.setattr(granite.rope_ops, "apply_rope", lambda x, positions, theta: x)
    elif fault == "dense-in-the-selected-rows-place":
        monkeypatch.setattr(granite.sparse_ops, "selection_of", lambda cfg: SEL._replace(
            dense_len=10 ** 6))
    elif fault == "zero-carry":
        sound = lightning_ops.chunk_update
        monkeypatch.setattr(granite.lightning_ops, "chunk_update", lambda S, l, sl, start, *r:
                            sound(S, l, sl, jnp.zeros_like(start), *r))
    elif fault == "no-qk-norm":
        monkeypatch.setattr(granite, "rms_norm", lambda x, w, eps: x * w)
    elif fault == "stale-compressed-keys":
        monkeypatch.setattr(granite.sparse_ops, "write_compressed", lambda CK, *a: CK)
    else:
        raise KeyError(fault)


PATCHED = ("no-rotary", "dense-in-the-selected-rows-place", "zero-carry", "no-qk-norm",
           "stale-compressed-keys")


@pytest.mark.parametrize("fault", sorted(BROKEN) + sorted(PATCHED))
def test_each_departure_from_the_equations_fails_the_comparison(seeded, fault, monkeypatch):
    """The comparison is tight enough to see each one: the decay of
    another layer or of the cut's depth, another theta, no rotary, the
    dense launch where rows select, a smaller selection, the forced
    blocks left out, stale compressed keys, a state dropped at a chunk
    boundary."""
    _, _, params, toks, ref = seeded
    cfg = CFG
    if fault in BROKEN:
        cfg = dataclasses.replace(CFG, **BROKEN[fault])
        if fault == "no-gate":
            params = {**params, "attn": {k: v for k, v in params["attn"].items() if k != "w_ogate"}}
    else:
        _patched(monkeypatch, fault)
    got, _ = _serve(params, cfg, toks, 96, 32)
    assert float(jnp.abs(got - ref[95:]).max()) > 100 * ATOL


def test_mixed_step_equals_its_split_steps(seeded):
    """Two decode rows past dense_len (slots 0 and 2) beside one prefill
    chunk (slot 1) that straddles it in ONE program: the logits and all
    four pools equal the decode step followed by the prefill step."""
    _, _, params, toks, _ = seeded
    K, V = _pools(blocks=60)
    pre = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, CFG, K, V, *a))
    CB = 16
    tabs = np.zeros((3, CB + 1), np.int32)
    for r in range(3):
        tabs[r, :12] = 1 + 12 * r + np.arange(12)
        tabs[r, -1] = r + 1
    for r in (0, 2):  # 80 tokens of context in rows 0 and 2
        _, K, V = pre(params, K, V, jnp.asarray(toks[None, r:r + 80]), jnp.asarray([0]),
                      jnp.asarray([80]), jnp.asarray(tabs[r:r + 1]))
    _, K, V = pre(params, K, V, jnp.asarray(toks[None, 5:53]), jnp.asarray([0]),
                  jnp.asarray([48]), jnp.asarray(tabs[1:2]))
    dec = (jnp.asarray([toks[20], 0, toks[21]]), jnp.asarray([80, 0, 80]),
           jnp.asarray(tabs[:, :-1] * np.array([[1], [0], [1]])), jnp.asarray([True, False, True]))
    pf = (jnp.asarray(toks[None, 53:85]), jnp.asarray([48]), jnp.asarray([29]), jnp.asarray(tabs[1:2]))
    ld, lp, Km, Vm = granite.mixed_step(params, CFG, K, V, *dec, *pf)
    ld2, K2, V2 = granite.decode_step(params, CFG, K, V, *dec)
    lp2, K2, V2 = granite.prefill_batch_step(params, CFG, K2, V2, *pf)
    np.testing.assert_allclose(ld[jnp.asarray([0, 2])], ld2[jnp.asarray([0, 2])], atol=ATOL)
    np.testing.assert_allclose(lp, lp2, atol=ATOL)
    for a, b in zip(jax.tree.leaves((Km, Vm)), jax.tree.leaves((K2, V2))):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("H,d", [(4, 16), (16, 128)], ids=["tiny", "sixteen-heads-of-128"])
def test_lightning_update_kernel_interpreted_equals_its_twin(H, d):
    """`lightning_update_kernel` in interpret mode against the jax.numpy
    route: live rows only (a dead row's slot does not move), the layer
    it is given alone."""
    L, slots, R = 2, 5, 4
    S = jax.random.normal(jax.random.key(0), lightning_ops.state_shape(L, slots, H, d))
    q, k, v = _qkv(jax.random.key(1), (R, H, d))
    log_lam = jnp.asarray(lightning_ops.log_decay(H, (12,), 32)[0])
    act = jnp.asarray([True, False, True, True])
    o0, S0 = lightning_ops.decode_update(S, 1, act, q, k, v, log_lam, use_kernel=False)
    o1, S1 = lightning_ops.decode_update(S, 1, act, q, k, v, log_lam, use_kernel=True,
                                         interpret=True)
    np.testing.assert_allclose(o1, o0, atol=1e-4)
    np.testing.assert_allclose(S1, S0, atol=1e-5)
    np.testing.assert_array_equal(S1[0], S[0])
    np.testing.assert_array_equal(S1[1, 1], S[1, 1])
    np.testing.assert_array_equal(S1[1, 4], S[1, 4])
    # no live row at all: the pool is copied through
    _, S2 = lightning_ops.decode_update(S, 1, jnp.zeros((R,), bool), q, k, v, log_lam,
                                        use_kernel=True, interpret=True)
    np.testing.assert_array_equal(S2, S)


def test_stage_two_through_the_decode_kernel_interpreted_equals_the_gather():
    """A KV head a row of the decode launch: the pool read as [N Hkv, 1,
    BS, D], a table of its own for each of a row's KV heads, rows at or
    under dense_len riding along with their own tables."""
    Hq, Hkv, D, bs, NBLK, R = 8, 2, 128, 16, 40, 3
    sel = sparse_ops.Selection(bs, 4, 8, 4, 1, 2, 128)
    ks = jax.random.split(jax.random.key(0), 4)
    K = kvc.PagedKV(jax.random.normal(ks[0], (2, NBLK, Hkv, bs, D), jnp.bfloat16), None)
    V = kvc.PagedKV(jax.random.normal(ks[1], (2, NBLK, Hkv, bs, D), jnp.bfloat16), None)
    CK = jax.random.normal(ks[2], (2, NBLK, Hkv * sel.per_block, D))
    q = jax.random.normal(ks[3], (R, Hq, D), jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(0).permutation(NBLK - 1)[:R * 12].reshape(R, 12) + 1,
                         jnp.int32)
    positions = jnp.asarray([150, 37, 190], jnp.int32)  # past, under, past dense_len
    active = jnp.asarray([True, True, True])
    args = (q, K, V, CK, 1, tables, positions, active, D ** -0.5, sel)
    want = sparse_ops.decode_attention(*args, use_kernel=False)
    attention_interpret = attention._interpret
    try:
        attention._interpret = lambda: True
        got = sparse_ops.decode_attention(*args)
    finally:
        attention._interpret = attention_interpret
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=2e-2)
    # and the row under dense_len read its whole context: plain paged attention
    plain = attention.paged_attention_gather(
        q[1:2], K, V, tables[1:2], positions[1:2] + 1, D ** -0.5, layer=1)
    np.testing.assert_allclose(np.asarray(want[1], np.float32), np.asarray(plain[0], np.float32),
                               atol=2e-2)


# ---------------------------------------------------------------- engine


def _engine(R=4, max_seq_len=256, num_blocks=160, **kw):
    kw.setdefault("sync_engine", True)
    kw.setdefault("block_size", BS)
    cfg = EngineConfig(
        model="minicpm-sala-tiny", dtype="float32", max_running_requests=R,
        num_blocks=num_blocks, max_seq_len=max_seq_len, max_prefill_tokens=32,
        prefill_buckets=[32], **kw,
    )
    ex = ModelExecutor(cfg)
    return InferenceEngine(cfg, executor=ex), ex


def _req(rid, outs, prompt, max_new=8, offline=False, **kw):
    def cb(o):
        for s in o.outputs:
            outs.setdefault(rid, []).extend(s.token_ids)
            outs.setdefault(rid + "/lp", []).extend(lp.data.logprob for lp in s.logprobs)
        if o.finished:
            outs.setdefault("_finished", []).append(rid)
        return True

    return EngineRequest(
        request_id=rid, prompt_token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                logprobs=True, ignore_eos=True),
        callback=cb, offline=offline, **kw,
    )


def _drain(eng, steps=3000):
    for _ in range(steps):
        if not eng.has_work():
            return
        eng.step()
    raise AssertionError("the engine did not drain")


def _nothing_held(eng):
    return len(eng._free_slots) == eng.R and eng.block_mgr.num_referenced_blocks == 0


PROMPTS = {"under": 21, "crosses-decoding": 60, "crosses-in-its-third-chunk": 90}


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts served
    concurrently (so chunks ride mixed steps beside decode rows), 12
    greedy tokens each: one stays under dense_len, one crosses it while
    decoding, one in its third chunk."""
    eng, ex = _engine()
    fam, m = _family(), _family_config()
    ex.params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    rng = np.random.default_rng(0)
    prompts = {rid: list(rng.integers(0, 512, n)) for rid, n in PROMPTS.items()}
    outs = {}
    for rid, p in prompts.items():
        eng.add_request(_req(rid, outs, p, max_new=12))
    _drain(eng)
    return eng, ex, fam, m, prompts, outs


@pytest.mark.parametrize("rid", sorted(PROMPTS))
def test_engine_matches_the_family_reference_in_logits(served, rid):
    eng, ex, fam, m, prompts, outs = served
    assert isinstance(eng.block_mgr, HybridBlockManager)
    p, out = prompts[rid], outs[rid]
    assert len(out) == 12
    seq = np.zeros((128,), np.int32)
    seq[:len(p) + len(out)] = p + out
    idx = np.arange(len(p) - 1, len(p) + len(out) - 1)
    rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
    assert [int(t) for t in jnp.argmax(rows, -1)] == out
    lp = jax.nn.log_softmax(rows, axis=-1)[np.arange(len(out)), np.asarray(out)]
    np.testing.assert_allclose(outs[rid + "/lp"], lp, atol=ATOL)


def test_the_three_pools_are_sized_counted_and_reported(served):
    eng, ex = served[0], served[1]
    c = CFG
    slot = 2 * 4 * 16 * 16 * 4  # two lightning layers, 4 heads of 16 x 16, float32
    assert ex.state_slot_bytes == slot and ex.state_pool_bytes == 4 * slot
    assert ex.cache_row_bytes == 2 * 2 * 2 * 16 * 4 and ex.block_size == BS
    assert ex.compressed_block_bytes == 2 * 2 * 4 * 16 * 4  # 4 keys a page, a KV head, a sparse layer
    assert ex.has_state_pool and ex.has_paged_cache and ex.slot_column
    assert ex.k_cache[1].shape == (2, 4, 4, 16, 16)  # the state pool
    assert ex.v_cache[1].shape == (2, 160, 8, 16)  # the compressed-key pool, a page a block
    rep = ex.kernel_report()
    assert rep["state"] == "lightning-xla" and rep["sparse"] == "select-xla+gather"
    assert rep["decode"] == "gather" and rep["prefill"] == "blockwise"
    text = eng.metrics.render()
    # rows by path, booked from the positions: prompts of 21, 60 and 90 and 12 tokens each
    past = sum(max(0, n + 11 - 64) for n in PROMPTS.values())
    total = sum(n + 11 for n in PROMPTS.values())
    pages = sum(-(-(p + 1) // BS) for n in PROMPTS.values() for p in range(64, n + 11))
    for series in (f"xllm_engine_attn_rows_selected_total {past}",
                   f"xllm_engine_attn_rows_dense_total {total - past}",
                   f"xllm_engine_sparse_pages_selected_total {4 * past}",
                   f"xllm_engine_sparse_pages_live_total {pages}",
                   "xllm_engine_state_slot_bytes %d" % slot, "xllm_engine_state_slots_in_use"):
        assert series in text, series
    assert eng.prefix_cached_tokens == 0 and _nothing_held(eng)


def test_pools_are_sized_one_after_the_other():
    eng, ex = _engine(R=4, num_blocks=0)  # auto-size against the nominal 16 GiB
    c = ex.cfg
    block = 2 * c.num_attention_layers * BS * 2 * 16 * 4 + ex.compressed_block_bytes
    left = 16 * 2**30 * 0.9 - approx_param_count(c) * 4 - ex.state_pool_bytes
    assert ex.num_blocks == int(left / 2 // block)
    assert ex.v_cache[1].shape[1] == ex.num_blocks


def test_abort_and_preemption_return_the_slot_the_blocks_and_their_compressed_keys():
    """A preempted sequence past dense_len resumes by recomputing its
    state, its K/V rows AND its compressed keys, and emits what an
    undisturbed run emits; everything is given back."""
    prompt = list(np.random.default_rng(5).integers(1, 400, 70))
    solo = {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("solo", solo, prompt, max_new=30, offline=True))
    _drain(eng)
    outs = {}
    eng, _ = _engine(R=2)
    eng.add_request(_req("victim", outs, prompt, max_new=30, offline=True))
    eng.add_request(_req("gone", outs, prompt[:9], max_new=200, offline=True))
    for _ in range(12):
        eng.step()
    assert len(eng._free_slots) == 0
    eng.cancel("gone")
    for i in range(2):
        eng.add_request(_req(f"on{i}", outs, prompt[:7 + i], max_new=6))
    _drain(eng)
    assert eng.preemptions >= 1 and outs["victim"] == solo["solo"]
    assert _nothing_held(eng)


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens > 0: the verify shapes have no selected-page"),
    (dict(num_host_blocks=8), "prefix cache: .* compressed-key rows and a state snapshot"),
    (dict(tp_size=2), "tp_size/ep_size/sp_size/dp_size > 1: the selected-page path"),
    (dict(block_size=16), "block_size=16: the selection's unit is the pool's page"),
], ids=["speculation", "prefix-tiers", "tp", "page-of-two-blocks"])
def test_named_refusals_at_build(kw, match):
    try:
        with pytest.raises(SparseFamilyUnsupported, match=match):
            _engine(**kw)
    finally:  # a build at tp > 1 declares its mesh for this thread before it refuses
        attention.set_shard_context(None)


def test_named_refusals_at_the_request_and_an_inert_prefix_half():
    eng, ex = _engine(R=2)
    with pytest.raises(SparseFamilyUnsupported, match="PD handoff: .* compressed-key rows"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(SparseFamilyUnsupported, match="PD handoff"):
        eng.import_sequence(_req("pd", {}, [1, 2, 3]), None)
    with pytest.raises(SparseFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])
    with pytest.raises(NotImplementedError, match="no block selection and no lightning state"):
        granite.hidden_dense(ex.params, CFG, jnp.zeros((1, 8), jnp.int32))


def test_the_parameter_tree_has_a_replicated_rule_for_every_leaf():
    from xllm_service_tpu.parallel.mesh import build_mesh
    from xllm_service_tpu.parallel.sharding import param_shardings

    rules = param_shardings(CFG, build_mesh(tp=1))
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(rules) == jax.tree.structure(shapes)
    for rule, leaf in zip(jax.tree.leaves(rules), jax.tree.leaves(shapes)):
        assert len(rule.spec) <= leaf.ndim and all(ax is None for ax in rule.spec)
