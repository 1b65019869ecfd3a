"""Falcon-H1 on the normal path (models/granite.py, the parallel kind): a
Mamba-2 mixer AND a GQA mixer on one normed input in EVERY block, both
added to the residual stream at once; a state slot, convolution rows and
K/V rows written by the same layer body; two B/C groups, a state wider
than a head, a query group that is not a power of two, full rotary at
theta 1e11, a dense MLP in every layer, muP multipliers. Everything at
`falcon-h1-tiny`, float32, against the family's plain reference
(benchmarks/families/falcon_h1.py: the recurrence, materialised attention,
every multiplier unfolded, nothing imported from the program)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.models import get_module, granite
from xllm_service_tpu.models.configs import approx_param_count, get_model_config
from xllm_service_tpu.ops import attention, mamba as mamba_ops, rope as rope_ops
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import HybridBlockManager
from xllm_service_tpu.runtime.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.runtime.executor import ModelExecutor, StateFamilyUnsupported

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_model_config("falcon-h1-tiny")
BS = 8
ATOL = 3e-5  # float32, logits of about 1


def _family():
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    from benchmarks.harness import family

    return family.load({"name": "falcon-h1-tiny", "family": "falcon_h1"})


def _family_config(c=CFG):
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim, "rope_theta": c.rope_theta, "rope_scaling": None,
        "rms_norm_eps": c.rms_norm_eps, "max_position_embeddings": c.max_position_embeddings,
        "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
        "projectors_bias": False, "mamba_conv_bias": True, "tie_word_embeddings": False,
        "mamba_norm_before_gate": False, "mamba_rms_norm": True, "attn_layer_indices": None,
        "mamba_d_conv": c.mamba_d_conv, "mamba_d_head": c.mamba_d_head,
        "mamba_d_ssm": c.mamba_d_inner, "mamba_d_state": c.mamba_d_state,
        "mamba_n_groups": c.mamba_n_groups, "mamba_n_heads": c.mamba_n_heads,
        "embedding_multiplier": c.embedding_multiplier,
        "lm_head_multiplier": c.lm_head_multiplier,
        "attention_in_multiplier": c.attention_in_multiplier,
        "attention_out_multiplier": c.attention_out_multiplier,
        "key_multiplier": c.key_multiplier, "ssm_in_multiplier": c.ssm_in_multiplier,
        "ssm_out_multiplier": c.ssm_out_multiplier,
        "ssm_multipliers": list(c.ssm_multipliers), "mlp_multipliers": list(c.mlp_multipliers),
    }


def test_the_family_file_reads_the_preset_back():
    fam, m = _family(), _family_config()
    assert fam.model_config("falcon-h1-tiny", m) == CFG and get_module(CFG) is granite
    want = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    have = jax.eval_shape(lambda: fam.make_weights(m, jax.random.key(0), jnp.float32))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(want) == shape(have)


def test_the_parallel_kind_gives_every_layer_both_kinds_of_memory():
    c = CFG
    assert c.layer_types == ("parallel",) * 3 and c.state_layer_kind == "mamba"
    assert c.num_state_layers == c.num_attention_layers == c.num_layers == 3
    assert c.has_state_pool and c.has_paged_cache and not c.is_moe and not c.num_window_layers
    # the tiny preset's traps: 2 groups, a state wider than a head, a query
    # group of 3, every multiplier different from 1 and from the others
    assert c.mamba_n_groups == 2 and c.mamba_d_state > c.mamba_d_head
    assert c.num_heads // c.num_kv_heads == 3
    mults = (c.embedding_multiplier, c.lm_head_multiplier, c.attention_in_multiplier,
             c.attention_out_multiplier, c.key_multiplier, c.ssm_in_multiplier,
             c.ssm_out_multiplier, *c.ssm_multipliers, *c.mlp_multipliers)
    assert len(set(mults)) == len(mults) == 14 and 1.0 not in mults
    # ONE run and one scan; the parameter count needs no special case
    (seg,) = granite._segments(c)
    assert (seg.kind, seg.n) == ("parallel", 3) and granite._period([seg]) == ([seg], 1)
    params = jax.eval_shape(lambda: granite.init_params(c, jax.random.key(0), jnp.float32))
    assert set(params) == {"embed", "final_norm", "layers", "attn", "mamba", "lm_head"}
    assert set(params["layers"]) == {"attn_norm", "mlp_norm", "w_gate", "w_up", "w_down"}
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    small = sum(int(np.prod(params[s][k].shape)) for s, ks in (
        ("layers", ("attn_norm", "mlp_norm")), ("mamba", ("conv_b", "dt_bias", "A_log", "D", "gate_norm")),
    ) for k in ks) + c.hidden_size
    assert approx_param_count(c) == n - small
    with pytest.raises(ValueError, match="every layer of the stack is of that kind"):
        granite.init_params(dataclasses.replace(c, layer_types=("parallel", "mamba", "parallel")),
                            jax.random.key(0), jnp.float32)


def test_the_preset_is_the_cut_with_the_published_widths():
    c = get_model_config("falcon-h1-34b")
    assert (c.num_layers, c.vocab_size, c.hidden_size, c.intermediate_size) == (9, 32640, 5120, 21504)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim, c.rope_theta) == (20, 4, 128, 128, 1e11)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups, c.mamba_d_state) == (32, 128, 2, 256)
    assert c.mamba_d_inner == 4096 and c.mamba_conv_dim == 5120
    assert approx_param_count(c) == 4_205_137_920  # 4,205 M: 8.41 GB in bfloat16
    S, conv = granite.state_shapes(c, 1)
    assert (int(np.prod(S)) + int(np.prod(conv))) * 4 == 38_301_696
    assert S == (9, 1, 32, 256, 128) and mamba_ops.kernel_shape_ok(jnp.zeros((1, 1) + S[2:]), 2)
    sys.path.insert(0, ROOT) if ROOT not in sys.path else None
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "falcon-h1-34b.json")) as f:
        m = json.load(f)
    assert _family().model_config("falcon-h1-34b", m) == c


# ---------------------------------------------------- steps vs the reference


def _pools(cfg=CFG, blocks=24, slots=3):
    ss, cs = granite.state_shapes(cfg, slots)
    kv = (cfg.num_attention_layers, blocks, cfg.num_kv_heads, BS, cfg.head_dim)
    z = lambda s: jnp.zeros(s, jnp.float32)
    return (z(kv), z(ss)), (z(kv), z(cs))


def _serve(params, cfg, toks, n_prefill, chunk=8):
    """Logits of every position from n_prefill - 1 on: the prompt in
    chunks into slot 1, then token by token on row 1, through the step
    functions (each one program of this configuration: `cfg` is closed
    over)."""
    prefill = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, cfg, K, V, *a))
    decode = jax.jit(lambda p, K, V, *a: granite.decode_step(p, cfg, K, V, *a))
    K, V = _pools(cfg)
    CB = 8
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
    return jnp.stack(outs)


@pytest.fixture(scope="module")
def seeded():
    fam, m = _family(), _family_config()
    params = jax.jit(lambda k: fam.make_weights(m, k, jnp.float32))(jax.random.key(5))
    toks = np.asarray(jax.random.randint(jax.random.key(6), (40,), 0, CFG.vocab_size))
    with jax.default_matmul_precision("highest"):
        ref = fam.forward_logits(params, m, jnp.asarray(toks, jnp.int32), jnp.arange(40))
    return fam, m, params, toks, ref


@pytest.mark.parametrize("n_prefill", [8, 21, 32], ids=["one-chunk", "ragged", "four-chunks"])
def test_prefill_then_decode_through_both_pools_equals_the_family_reference(seeded, n_prefill):
    _, _, params, toks, ref = seeded
    got = _serve(params, CFG, toks, n_prefill)
    np.testing.assert_allclose(got, ref[n_prefill - 1:], atol=ATOL)


def test_the_dense_oracle_equals_the_family_reference(seeded):
    _, _, params, toks, ref = seeded
    np.testing.assert_allclose(
        granite.forward_dense(params, CFG, jnp.asarray(toks)[None])[0], ref, atol=ATOL)


def _swap(t, i, j):
    t = list(t)
    t[i], t[j] = t[j], t[i]
    return tuple(t)


# one field of the configuration wrong: a multiplier moved to its neighbour
BROKEN = {
    "ssm-B-C-swapped": dict(ssm_multipliers=_swap(CFG.ssm_multipliers, 2, 3)),
    "ssm-z-x-swapped": dict(ssm_multipliers=_swap(CFG.ssm_multipliers, 0, 1)),
    "ssm-dt-as-C": dict(ssm_multipliers=_swap(CFG.ssm_multipliers, 3, 4)),
    "mlp-swapped": dict(mlp_multipliers=_swap(CFG.mlp_multipliers, 0, 1)),
    "key-as-attn-in": dict(key_multiplier=CFG.attention_in_multiplier,
                           attention_in_multiplier=CFG.key_multiplier),
    "outs-swapped": dict(attention_out_multiplier=CFG.ssm_out_multiplier,
                         ssm_out_multiplier=CFG.attention_out_multiplier),
    "ins-swapped": dict(attention_in_multiplier=CFG.ssm_in_multiplier,
                        ssm_in_multiplier=CFG.attention_in_multiplier),
    "head-as-embedding": dict(lm_head_multiplier=CFG.embedding_multiplier,
                              embedding_multiplier=CFG.lm_head_multiplier),
    "half-rotary": dict(rotary_dim=CFG.head_dim // 2),
    "theta-1e4": dict(rope_theta=1e4),
}


def _patched(monkeypatch, fault):
    """The program wrong in a way that is not a field of its configuration."""
    if fault in ("no-attn", "no-state"):
        sound = granite._branch_scales
        monkeypatch.setattr(granite, "_branch_scales", lambda cfg: (
            (0.0, sound(cfg)[1]) if fault == "no-attn" else (sound(cfg)[0], 0.0)))
    elif fault == "groups-swapped":  # group 1's heads read group 0's B and C
        first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)
        dec, chk = mamba_ops.decode_update, mamba_ops.chunk_update
        monkeypatch.setattr(mamba_ops, "decode_update", lambda S, l, act, x, dt, A, B, C, D, **kw:
                            dec(S, l, act, x, dt, A, first(B), first(C), D, **kw))
        monkeypatch.setattr(mamba_ops, "chunk_update", lambda S, l, sl, st, ln, x, dt, A, B, C, D:
                            chk(S, l, sl, st, ln, x, dt, A, first(B), first(C), D))
    elif fault == "norm-before-gate":  # rms(y) * silu(z) for rms(y * silu(z))
        def gated(lp, cfg, y, z):
            g = y.reshape(y.shape[0], cfg.mamba_n_groups, -1)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            g = g.reshape(y.shape) * lp["gate_norm"] * jax.nn.silu(z)
            return jnp.einsum("tf,fe->te", g, lp["w_out"])
        monkeypatch.setattr(granite, "_gated_out", gated)
    elif fault == "two-residual-adds":  # the state branch sees the stream WITH attention added
        sound_mamba = granite._mamba_mixer

        def serial(lp, cfg, h, *rest):
            return sound_mamba(lp, cfg, h * 1.05, *rest)  # a stand-in: another input than u
        monkeypatch.setattr(granite, "_mamba_mixer", serial)
    elif fault == "zero-carry":
        sound = mamba_ops.chunk_update
        monkeypatch.setattr(mamba_ops, "chunk_update", lambda S, l, sl, start, *r:
                            sound(S, l, sl, jnp.zeros_like(start), *r))
    else:
        raise KeyError(fault)


PATCHED = ("no-attn", "no-state", "groups-swapped", "norm-before-gate", "two-residual-adds",
           "zero-carry")


@pytest.mark.parametrize("fault", sorted(BROKEN) + sorted(PATCHED))
def test_each_departure_from_the_equations_fails_the_comparison(seeded, fault, monkeypatch):
    """The comparison is tight enough to see each one: a branch dropped, a
    multiplier moved to its neighbour, the groups swapped, the gate after
    the norm, another input to the second branch: off by 100x the
    tolerance and more."""
    _, _, params, toks, ref = seeded
    cfg = CFG
    if fault in BROKEN:
        cfg = dataclasses.replace(CFG, **BROKEN[fault])
    else:
        _patched(monkeypatch, fault)
    got = _serve(params, cfg, toks, 21)
    assert float(jnp.abs(got - ref[20:]).max()) > 100 * ATOL


def test_mixed_step_equals_its_split_steps(seeded):
    """Two decode rows (slots 0 and 2) beside one prefill chunk (slot 1)
    in ONE program: the logits and all four pools equal the decode step
    followed by the prefill step."""
    _, _, params, toks, _ = seeded
    K, V = _pools()
    pre = jax.jit(lambda p, K, V, *a: granite.prefill_batch_step(p, CFG, K, V, *a))
    CB = 8
    tabs = np.zeros((3, CB + 1), np.int32)
    for r in range(3):
        tabs[r, :4] = 1 + 4 * r + np.arange(4)
        tabs[r, -1] = r + 1
    for r in (0, 2):  # 8 tokens of context in rows 0 and 2
        _, K, V = pre(params, K, V, jnp.asarray(toks[None, r:r + 8]), jnp.asarray([0]),
                      jnp.asarray([8]), jnp.asarray(tabs[r:r + 1]))
    dec = (jnp.asarray([toks[20], 0, toks[21]]), jnp.asarray([8, 0, 8]),
           jnp.asarray(tabs[:, :-1] * np.array([[1], [0], [1]])), jnp.asarray([True, False, True]))
    pf = (jnp.asarray(toks[None, 10:18]), jnp.asarray([0]), jnp.asarray([7]), jnp.asarray(tabs[1:2]))
    ld, lp, Km, Vm = granite.mixed_step(params, CFG, K, V, *dec, *pf)
    ld2, K2, V2 = granite.decode_step(params, CFG, K, V, *dec)
    lp2, K2, V2 = granite.prefill_batch_step(params, CFG, K2, V2, *pf)
    np.testing.assert_allclose(ld[jnp.asarray([0, 2])], ld2[jnp.asarray([0, 2])], atol=ATOL)
    np.testing.assert_allclose(lp, lp2, atol=ATOL)
    for a, b in zip(jax.tree.leaves((Km, Vm)), jax.tree.leaves((K2, V2))):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_rotary_at_theta_1e11_holds_its_angles_in_float32():
    """Full rotary at theta 1e11 against float64 angles, to position
    262,143 (the published context's last): the slowest pair turns
    1.7e-11 x 2.6e5 = 4e-6 rad there, the fastest 2.6e5 rad, whose float32
    angle is off by up to 0.016 rad (2**-6), which is what the program's
    float32 angles cost and the reference's too (it is not a fault of
    either); at the benchmark's 4,096 positions 2.4e-4."""
    D = 16
    x = jax.random.normal(jax.random.key(0), (4, 2, D), jnp.float32)
    for pos, tol in ((4095, 1e-3), (262143, 0.05)):
        positions = jnp.asarray([0, 1, pos // 2, pos], jnp.int32)
        got = np.asarray(rope_ops.apply_partial_rope(x, positions, 1e11, D))
        inv = 1.0 / 1e11 ** (np.arange(0, D, 2, dtype=np.float64) / D)
        ang = np.asarray(positions, np.float64)[:, None] * inv
        x1, x2 = np.asarray(x, np.float64)[..., :D // 2], np.asarray(x, np.float64)[..., D // 2:]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        want = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
        assert np.abs(got - want).max() < tol
        np.testing.assert_allclose(got[:2], want[:2], atol=1e-6)


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("H,P,G,N", [(16, 16, 2, 32), (8, 128, 2, 256), (8, 64, 1, 128)],
                         ids=["tiny-g2-n-over-p", "falcon-g2-n256-p128", "granite-g1-n128-p64"])
def test_update_kernel_interpreted_serves_groups(H, P, G, N):
    """`mamba_update_kernel` in interpret mode against the jax.numpy route:
    two groups (a head tile reads its own group's B and C), a state wider
    than a head, one head a lane row; and Granite's shape as it was. Live
    rows only: a dead row's slot does not move."""
    L, slots, R = 2, 5, 4
    k = mamba_ops.pack_factor(H, P)
    shape, _ = mamba_ops.state_shapes(L, slots, H, P, N, 4, H * P + 2 * G * N)
    ks = jax.random.split(jax.random.key(1), 6)
    S = jax.random.normal(ks[0], shape)
    x, dt = jax.random.normal(ks[1], (R, H, P)), jax.nn.softplus(jax.random.normal(ks[2], (R, H)))
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    B, C = jax.random.normal(ks[4], (R, G, N)), jax.random.normal(ks[5], (R, G, N))
    act = jnp.asarray([True, False, True, True])
    y0, S0 = mamba_ops.decode_update(S, 1, act, x, dt, A, B, C, jnp.ones((H,)), use_kernel=False)
    y1, S1 = mamba_ops.decode_update(S, 1, act, x, dt, A, B, C, jnp.ones((H,)), use_kernel=True,
                                     interpret=True)
    np.testing.assert_allclose(y1, y0, atol=2e-4)
    np.testing.assert_allclose(S1, S0, atol=1e-5)
    np.testing.assert_array_equal(S1[1, 1], S[1, 1])  # the dead row's slot
    np.testing.assert_array_equal(S1[0], S[0])  # the other layer
    if G == 2:  # ... and group 1's heads did NOT read group 0's planes
        first = lambda t: jnp.broadcast_to(t[:, :1], t.shape)
        yw, _ = mamba_ops.decode_update(S, 1, act, x, dt, A, first(B), first(C), jnp.ones((H,)),
                                        use_kernel=False)
        assert float(jnp.abs(yw - y1).max()) > 0.1


def test_the_update_kernels_layout_rule():
    """What `kernel_eligible` asks for: whole (8, 128) tiles, lane rows
    that divide over the groups, and a head tile of whole 8-row blocks
    inside a group (or one group and all of its rows)."""
    from xllm_service_tpu.ops.pallas import mamba as pm

    pool = lambda H, P, N: jnp.zeros(mamba_ops.state_shapes(1, 1, H, P, N, 4, 8)[0])
    assert mamba_ops.kernel_shape_ok(pool(32, 128, 256), 2)  # the cut: 16 lane rows a group
    assert mamba_ops.kernel_shape_ok(pool(128, 64, 128), 1)  # Granite
    assert mamba_ops.kernel_shape_ok(pool(8, 16, 16), 1)  # granite-tiny: one lane row, all of it
    assert not mamba_ops.kernel_shape_ok(pool(16, 16, 32), 2)  # one lane row a group: no 8-row tile
    assert not mamba_ops.kernel_shape_ok(pool(8, 128, 256), 2)  # 4 lane rows a group
    assert not mamba_ops.kernel_shape_ok(pool(32, 128, 256), 3)  # groups do not divide
    assert not mamba_ops.kernel_shape_ok(pool(32, 128, 252), 2)  # N not whole sublane tiles
    assert pm.head_tile(64) == 16 and pm.head_tile(16, 256 * 128 * 4, 2) == 8
    assert pm.head_tile(1) == 1 and pm.head_tile(1, groups=2) == 0
    assert not mamba_ops.kernel_eligible(pool(32, 128, 256), 2)  # not on a chip here
    with pytest.raises(ValueError, match="no tile of whole 8-row blocks"):
        pm.mamba_update_kernel(pool(8, 128, 256), 0, jnp.zeros((1,), jnp.int32), 1,
                               jnp.zeros((1, 8, 128)), jnp.zeros((1, 8, 128)),
                               jnp.zeros((1, 2, 256, 128)), jnp.zeros((1, 2, 256, 128)))


def _attn_case(seed, R=3, Hq=10, Hkv=2, D=128, BS_=16, MB=6, N=24):
    ks = jax.random.split(jax.random.key(seed), 4)
    K = jax.random.normal(ks[0], (1, N, Hkv, BS_, D), jnp.float32)
    V = jax.random.normal(ks[1], (1, N, Hkv, BS_, D), jnp.float32)
    bt = jnp.asarray(1 + np.arange(R * MB).reshape(R, MB) % (N - 1), jnp.int32)
    return K, V, bt, ks


def test_the_attention_kernels_interpreted_at_a_query_group_of_5():
    """The paged decode kernel and the flash-prefill kernel at 10 query
    heads over 2 KV heads (a group of 5, padded to 8 sublanes inside the
    kernels) against their plain twins."""
    from xllm_service_tpu.ops.pallas.flash_prefill import flash_prefill_kernel
    from xllm_service_tpu.ops.pallas.paged_attention import paged_attention_kernel

    K, V, bt, ks = _attn_case(3)
    lens = jnp.asarray([37, 0, 90], jnp.int32)
    q = jax.random.normal(ks[2], (3, 10, 128), jnp.float32)
    got = paged_attention_kernel(q, K, V, bt, lens, 0.09, layer=0, interpret=True)
    want = attention.paged_attention_gather(q, K[0], V[0], bt, lens, 0.09)
    np.testing.assert_allclose(got[jnp.asarray([0, 2])], want[jnp.asarray([0, 2])], atol=2e-5)
    qp = jax.random.normal(ks[3], (2, 16, 10, 128), jnp.float32)
    start, length = jnp.asarray([16, 0], jnp.int32), jnp.asarray([16, 11], jnp.int32)
    got = flash_prefill_kernel(qp, K, V, bt[:2], start, length, 0.09, layer=0, interpret=True)
    want = jax.vmap(lambda q_, bt_, s_, l_: attention.prefill_attention_blockwise(
        q_, K[0], V[0], bt_, s_, l_, 0.09))(qp, bt[:2], start, length)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :11], want[1, :11], atol=2e-5)


# ---------------------------------------------------------------- engine


def _engine(R=4, max_seq_len=256, num_blocks=120, **kw):
    kw.setdefault("sync_engine", True)
    cfg = EngineConfig(
        model="falcon-h1-tiny", dtype="float32", max_running_requests=R, block_size=BS,
        num_blocks=num_blocks, max_seq_len=max_seq_len, max_prefill_tokens=16,
        prefill_buckets=[16], **kw,
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


PROMPTS = {"one-chunk": 13, "two-chunks": 32, "five-chunks": 75}


@pytest.fixture(scope="module")
def served():
    """An engine over the FAMILY's seeded weights, three prompts of 1, 2
    and 5 chunks (a ragged tail on two) served concurrently (so chunks
    ride mixed steps beside decode rows), 12 greedy tokens each."""
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
    with jax.default_matmul_precision("highest"):
        seq = np.zeros((128,), np.int32)
        seq[:len(p) + len(out)] = p + out
        idx = np.arange(len(p) - 1, len(p) + len(out) - 1)
        rows = fam.forward_logits(ex.params, m, jnp.asarray(seq), jnp.asarray(idx))
    assert [int(t) for t in jnp.argmax(rows, -1)] == out
    lp = jax.nn.log_softmax(rows, axis=-1)[np.arange(len(out)), np.asarray(out)]
    np.testing.assert_allclose(outs[rid + "/lp"], lp, atol=ATOL)


def test_both_kinds_of_memory_in_every_layer_are_sized_and_counted(served):
    eng, ex = served[0], served[1]
    c = CFG
    slot = c.num_layers * (c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state
                           + (c.mamba_d_conv - 1) * c.mamba_conv_dim) * 4
    assert ex.state_slot_bytes == slot == 3 * (16 * 16 * 32 + 3 * 384) * 4
    assert ex.state_pool_bytes == 4 * slot
    # K and V in EVERY layer: 3 layers x 2 KV heads x 16 lanes x 2, float32
    assert ex.cache_row_bytes == 2 * 3 * 2 * 16 * 4 and ex.block_size == BS
    assert ex.has_state_pool and ex.has_paged_cache and ex.slot_column
    assert ex.k_cache[0].shape[0] == ex.k_cache[1].shape[0] == 3  # both stacks have L layers
    rep = ex.kernel_report()
    assert rep["state"] == "mamba-xla" and rep["decode"] == "gather" and rep["prefill"] == "blockwise"
    text = eng.metrics.render()
    for series in ("xllm_engine_state_slot_bytes %d" % slot, "xllm_engine_state_slots_in_use",
                   "xllm_engine_state_pool_bytes", "xllm_engine_cache_row_bytes",
                   'xllm_engine_kv_blocks_live{pool="full"} 0',
                   'xllm_engine_kv_block_bytes{pool="full"} %d' % (BS * 2 * 3 * 2 * 16 * 4)):
        assert series in text, series
    assert eng.prefix_cached_tokens == 0 and _nothing_held(eng)
    assert eng.block_mgr.take_cache_event().empty()  # nothing told to the fabric


def test_same_prompt_twice_is_recomputed_not_cached(served):
    eng, _, _, _, prompts, outs = served
    again = {}
    eng.add_request(_req("again", again, prompts["two-chunks"], max_new=12))
    _drain(eng)
    assert again["again"] == outs["two-chunks"] and eng.prefix_cached_tokens == 0
    assert _nothing_held(eng)


def test_abort_and_preemption_return_the_slot_and_the_blocks():
    """A cancelled sequence, a preempted one and the one it was preempted
    for all give back their state slot and every K/V block; the preempted
    sequence resumes by recomputing its state AND its K/V rows and emits
    what an undisturbed run emits."""
    prompt = list(np.random.default_rng(5).integers(1, 400, 21))
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
    assert len(eng._free_slots) == 0 and eng.block_mgr.num_referenced_blocks >= 2
    eng.cancel("gone")
    for i in range(2):
        eng.add_request(_req(f"on{i}", outs, prompt[:7 + i], max_new=6))
    _drain(eng)
    assert eng.preemptions >= 1 and outs["victim"] == solo["solo"]
    assert "gone" not in outs.get("_finished", []) or len(outs["gone"]) < 200
    assert _nothing_held(eng)
    assert "xllm_engine_state_recomputes_total" in eng.metrics.render()


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("kw,match", [
    (dict(speculative_tokens=2), "speculative_tokens"),
    (dict(num_host_blocks=8), "prefix cache"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(checkpoint_path="/nowhere"), "checkpoint_path"),
    (dict(tp_size=2), "tp_size/ep_size/sp_size/dp_size"),
    (dict(ep_size=2), "tp_size/ep_size/sp_size/dp_size"),
], ids=["speculation", "prefix-tiers", "int8-cache", "checkpoint", "tp", "ep"])
def test_named_refusals_at_build(kw, match):
    try:
        with pytest.raises(StateFamilyUnsupported, match=match):
            _engine(**kw)
    finally:  # a build at tp > 1 declares its mesh for this thread before it refuses
        attention.set_shard_context(None)


def test_named_refusals_at_the_request_and_an_inert_prefix_half():
    eng, ex = _engine(R=2)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.add_request(_req("pd", {}, [1, 2, 3], prefill_only=True))
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        eng.import_sequence(_req("pd", {}, [1, 2, 3]), None)
    with pytest.raises(StateFamilyUnsupported, match="PD handoff"):
        ex.export_blocks(np.array([1]))
    eng.block_mgr.commit_block(1, b"h")
    assert eng.block_mgr.match_prefix([1, 2], hashes=[b"h"]) == (0, [])
    assert eng.block_mgr.take_cache_event().empty()


def test_pools_are_sized_one_after_the_other():
    eng, ex = _engine(R=4, num_blocks=0)  # auto-size against the nominal 16 GiB
    c = ex.cfg
    block = 2 * c.num_attention_layers * BS * 2 * 16 * 4  # EVERY layer holds K and V
    left = 16 * 2**30 * 0.9 - approx_param_count(c) * 4 - ex.state_pool_bytes
    assert ex.num_blocks == int(left / 2 // block)


def test_the_parameter_tree_has_a_replicated_rule_for_every_leaf():
    from xllm_service_tpu.parallel.mesh import build_mesh
    from xllm_service_tpu.parallel.sharding import param_shardings

    rules = param_shardings(CFG, build_mesh(tp=1))
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    assert jax.tree.structure(rules) == jax.tree.structure(shapes)
    for rule, leaf in zip(jax.tree.leaves(rules), jax.tree.leaves(shapes)):
        assert len(rule.spec) <= leaf.ndim and all(ax is None for ax in rule.spec)


def test_the_weights_loader_names_what_would_load():
    from xllm_service_tpu.runtime import weights

    names = weights.falcon_h1_name_map(CFG)
    assert names["mamba.w_in"] == "model.layers.{i}.mamba.in_proj.weight"
    assert names["attn.wq"] == "model.layers.{i}.self_attn.q_proj.weight"
    assert names["layers.w_gate"] == "model.layers.{i}.feed_forward.gate_proj.weight"
    shapes = jax.eval_shape(lambda: granite.init_params(CFG, jax.random.key(0), jnp.float32))
    leaves = {f"{s}.{k}" if isinstance(v, dict) else s
              for s, v in shapes.items() for k in (v if isinstance(v, dict) else (None,))}
    assert set(names) == leaves
