"""Static half of the sharded-tier differential suite (docs/SHARDING.md):
the partition-rule matrix over the full model-config family.

`param_shardings` must produce a rule tree that matches every family's
param pytree EXACTLY — a missing rule silently replicates the leaf
across the mesh (tp× HBM on a real pod), an extra rule is a stale row.
`jax.eval_shape` makes the check free at any model size, so the matrix
covers EVERY registered config (70B and deepseek-v3 included) ×
tp ∈ {1, 2, 4, 8} × ep ∈ {1, 2} on the virtual 8-device platform.
`check_tp_divisibility` and `resolve_kv_packing` pin the admission /
downgrade decisions the executor takes before any of it matters. The
graftlint `sharding-rules` pass is the AST-level tripwire for the same
invariant; this is the ground truth it approximates.
"""

import jax
import jax.numpy as jnp
import pytest

from xllm_service_tpu import models
from xllm_service_tpu.models.configs import get_model_config, list_model_configs
from xllm_service_tpu.ops.kv_cache import kv_pack_factor
from xllm_service_tpu.parallel.mesh import build_mesh
from xllm_service_tpu.parallel.sharding import (
    check_tp_divisibility,
    kv_cache_sharding,
    kv_scale_sharding,
    param_shardings,
    resolve_kv_packing,
)


def _divisible(cfg, tp, ep):
    try:
        check_tp_divisibility(cfg, tp, ep)
        return True
    except ValueError:
        return False


def _expect_divisible(cfg, tp, ep):
    """Ground-truth divisibility, restated independently of the
    implementation under test."""
    if cfg.is_mla:
        heads_ok = cfg.num_heads % tp == 0
    else:
        heads_ok = cfg.num_heads % tp == 0 and cfg.num_kv_heads % tp == 0
    if not heads_ok:
        return False
    if cfg.is_moe:
        if ep > 1:
            if cfg.num_experts % ep or cfg.moe_intermediate_size % tp:
                return False
        elif cfg.num_experts % tp:
            return False
        if cfg.first_k_dense_replace > 0 and cfg.intermediate_size % tp:
            return False
        return True
    return cfg.intermediate_size % tp == 0


@pytest.mark.parametrize("name", list_model_configs())
@pytest.mark.parametrize("tp", [1, 2, 4, 8])
@pytest.mark.parametrize("ep", [1, 2, 4])
def test_divisibility_matrix(cpu_devices, name, tp, ep):
    cfg = get_model_config(name)
    assert _divisible(cfg, tp, ep) == _expect_divisible(cfg, tp, ep)


@pytest.mark.parametrize("name", list_model_configs())
@pytest.mark.parametrize("tp", [1, 2, 4, 8])
def test_every_param_leaf_has_a_rule(cpu_devices, name, tp):
    """The rule tree's STRUCTURE equals the param tree's — every leaf
    gets a NamedSharding, no silent replication, no stale rules —
    checked via eval_shape (free at 70B scale)."""
    cfg = get_model_config(name)
    for ep in (1, 2):
        if tp * ep > 8 or not _divisible(cfg, tp, ep):
            continue
        mesh = build_mesh(tp=tp, ep=ep)
        rules = param_shardings(
            cfg, mesh, ep_axis="ep" if ep > 1 else None
        )
        mod = models.get_module(cfg)
        shapes = jax.eval_shape(
            lambda m=mod, c=cfg: m.init_params(
                c, jax.random.key(0), jnp.float32
            )
        )
        assert jax.tree_util.tree_structure(
            shapes
        ) == jax.tree_util.tree_structure(rules), (
            f"param tree vs rule tree mismatch for {name} tp={tp} ep={ep}"
        )
        # Every rule must be applicable to its leaf: same rank bound and
        # tp-divisible extents on the sharded axes.
        def check(leaf, rule):
            spec = rule.spec
            assert len(spec) <= len(leaf.shape), (name, leaf.shape, spec)
            for ax, p in enumerate(spec):
                if p is None:
                    continue
                axes = p if isinstance(p, tuple) else (p,)
                n = 1
                for a in axes:
                    n *= mesh.shape.get(a, 1)
                assert leaf.shape[ax] % n == 0, (
                    f"{name}: axis {ax} of {leaf.shape} not divisible "
                    f"by {p}={n}"
                )

        jax.tree_util.tree_map(check, shapes, rules)


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_big_matmul_leaves_actually_shard(cpu_devices, tp):
    """No-silent-replication, positively stated: the HBM-dominant leaves
    of the GQA family carry the tp axis in their specs."""
    def has_tp(spec):
        return any(
            a == "tp" or (isinstance(a, tuple) and "tp" in a)
            for a in spec
        )

    cfg = get_model_config("llama3-70b")
    mesh = build_mesh(tp=tp)
    rules = param_shardings(cfg, mesh)
    for key in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert has_tp(rules["layers"][key].spec), key
    assert has_tp(rules["lm_head"].spec)
    assert has_tp(kv_cache_sharding(mesh).spec)
    assert has_tp(kv_scale_sharding(mesh).spec)


# A family of the hybrid stack (a state pool, or a window pool beside the
# full one) is served at tp_size = ep_size = 1 only (runtime/executor.py
# refuses the rest by name; its tree is replicated:
# parallel/sharding._hybrid_param_shardings), so it has no expert-axis rule.
MOE_CONFIGS = [
    n for n in list_model_configs()
    if get_model_config(n).is_moe and not get_model_config(n).is_hybrid
]


def _has_axis(spec, axis):
    return any(
        a == axis or (isinstance(a, tuple) and axis in a) for a in spec
    )


@pytest.mark.parametrize("name", MOE_CONFIGS)
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("ep", [1, 2, 4])
def test_moe_expert_axis_matrix(cpu_devices, name, tp, ep):
    """The expert-axis half of the rule matrix (ISSUE 15), over EVERY
    MoE-bearing registered config × ep ∈ {1, 2, 4} × tp ∈ {1, 2}:
    structure equality vs the param tree (eval_shape — free at V3
    scale), per-axis divisibility of every rule, and POSITIVE asserts
    that the expert-carrying leaves actually ride the ep axis while the
    router replicates and the shared experts stay pure-tp (they are
    always-active — sharding them over ep would idle every shard but
    one)."""
    cfg = get_model_config(name)
    if not _divisible(cfg, tp, ep):
        pytest.skip(f"{name}: tp={tp} ep={ep} not divisible")
    mesh = build_mesh(tp=tp, ep=ep)
    rules = param_shardings(cfg, mesh, ep_axis="ep" if ep > 1 else None)
    mod = models.get_module(cfg)
    shapes = jax.eval_shape(
        lambda m=mod, c=cfg: m.init_params(c, jax.random.key(0), jnp.float32)
    )
    assert jax.tree_util.tree_structure(
        shapes
    ) == jax.tree_util.tree_structure(rules), (name, tp, ep)

    def check(leaf, rule):
        spec = rule.spec
        assert len(spec) <= len(leaf.shape), (name, leaf.shape, spec)
        for ax, p in enumerate(spec):
            if p is None:
                continue
            axes = p if isinstance(p, tuple) else (p,)
            n = 1
            for a in axes:
                n *= mesh.shape.get(a, 1)
            assert leaf.shape[ax] % n == 0, (
                f"{name}: axis {ax} of {leaf.shape} not divisible by "
                f"{p}={n}"
            )

    jax.tree_util.tree_map(check, shapes, rules)
    layers = rules["layers"]
    for key in ("w_gate", "w_up", "w_down"):
        if ep > 1:
            # The expert axis (dim 1 of [L, X, ...]) carries ep.
            assert _has_axis(layers[key].spec, "ep"), (name, key)
            assert layers[key].spec[1] == "ep", (name, key)
        else:
            # Pure-TP MoE: experts ride tp instead.
            assert _has_axis(layers[key].spec, "tp") or tp == 1, (
                name, key,
            )
    assert not _has_axis(layers["router"].spec, "ep"), name
    if cfg.topk_method == "noaux_tc":
        assert not _has_axis(layers["router_bias"].spec, "ep"), name
    if cfg.n_shared_experts > 0:
        for key in ("w_sh_gate", "w_sh_up", "w_sh_down"):
            assert not _has_axis(layers[key].spec, "ep"), (name, key)
    # Heterogeneous stacks: the dense prefix never grows an expert axis.
    if cfg.first_k_dense_replace > 0:
        for key in ("w_gate", "w_up", "w_down"):
            assert not _has_axis(
                rules["dense_layers"][key].spec, "ep"
            ), (name, key)


@pytest.mark.parametrize(
    "name,tp,expect_disabled",
    [
        # llama3-1b: Hkv=8, D=64 packs to 4 rows — tp=8 must unpack.
        ("llama3-1b", 2, False),
        ("llama3-1b", 4, False),
        ("llama3-1b", 8, True),
        # packed-tiny: Hkv=2, D=64 packs to ONE row — any tp>1 unpacks.
        ("llama3-packed-tiny", 2, True),
        # D=128 never packs, so nothing to disable.
        ("llama3-shard-tiny", 8, False),
        ("llama3-70b", 8, False),
        # MLA has no packed-pair layout at all.
        ("deepseek-tiny", 4, False),
    ],
)
def test_resolve_kv_packing_matrix(name, tp, expect_disabled):
    cfg = get_model_config(name)
    out = resolve_kv_packing(cfg, tp)
    assert out.kv_pack_disable == expect_disabled
    if expect_disabled:
        # The downgrade is exactly the non-dividing packed-row case.
        pf = kv_pack_factor(cfg.num_kv_heads, cfg.head_dim)
        assert pf > 1 and (cfg.num_kv_heads // pf) % tp != 0
    else:
        assert out is cfg
