"""Tier-1 copy of benchmarks/tests/test_families.py (PERF.md section 7
left it for the first program PR after PR 30): every family file of the
benchmark against the program's parameter tree, and the Llama family's
draws against digests of PR 30's parent. A program PR that changes a
parameter tree, or a benchmark PR that changes a draw, fails here and not
in a cell's set-up on the chip."""
import glob
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import family as family_mod  # noqa: E402


def configurations():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def test_the_benchmark_has_both_families():
    assert {c["family"] for c in configurations()} == {"llama", "brumby"}


@pytest.mark.parametrize("config", configurations(), ids=lambda c: c["name"])
def test_make_weights_gives_the_programs_parameter_tree(config):
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu import models

    fam = family_mod.load(config)
    cfg = fam.model_config(config["name"], config)
    dtype = jnp.dtype(config["engine"]["dtype"])
    want = jax.eval_shape(lambda k: models.get_module(cfg).init_params(cfg, k, dtype),
                          jax.random.key(0))
    have = jax.eval_shape(lambda k: fam.make_weights(config, k, dtype), family_mod.seed_key(1))
    assert (jax.tree.map(lambda a: (a.shape, str(a.dtype)), have)
            == jax.tree.map(lambda a: (a.shape, str(a.dtype)), want))


@pytest.mark.parametrize("config", configurations(), ids=lambda c: c["name"])
def test_model_config_is_the_family_the_program_dispatches_on(config):
    from xllm_service_tpu import models

    cfg = family_mod.load(config).model_config(config["name"], config)
    module = models.get_module(cfg).__name__.rsplit(".", 1)[-1]
    assert module == config["family"]
    assert cfg.is_retention == (config["family"] == "brumby")


def digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


# as benchmarks/tests/test_families.py has them: the parent of PR 30's
# jitted reference.make_weights at rehearse-tiny, on the CPU backend
PARENT_DIGESTS = {
    (21, "float32"): "cc6cc191d4c20ed61c1d25f67fa596080137626e93c0df8764e3cf024810a3ff",
    (21, "bfloat16"): "b807c8597d2e7d1bd566d41a4e632738f48fb11d4ef3378ad0fa99a155abef22",
    (2**31 + 77, "float32"): "fb38b60ad306c6df313268e1d63af372753df446bf49e181af11ab96ca60b6af",
    (2**31 + 77, "bfloat16"): "41c7825204f8ef2e5cce76c8f9a5d0e10bdae5a073e61847af9a3bb3202f9c98",
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_DIGESTS))
def test_the_llama_family_draws_what_the_parent_drew(seed, dtype):
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "rehearse-tiny.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    w = jax.jit(lambda k: fam.make_weights(config, k, jnp.dtype(dtype)))(family_mod.seed_key(seed))
    assert digest(w) == PARENT_DIGESTS[(seed, dtype)]


def test_the_brumby_family_draws_slow_decays_and_leaves_nothing_skippable():
    import jax
    import jax.numpy as jnp

    with open(os.path.join(BENCH, "configs", "rehearse-brumby-tiny.json")) as f:
        config = json.load(f)
    fam = family_mod.load(config)
    w = jax.jit(lambda k: fam.make_weights(config, k, jnp.float32))(family_mod.seed_key(3))
    lay = w["layers"]
    h = jax.random.normal(jax.random.key(0), (4096, config["hidden_size"]))  # unit-RMS rows
    decay = jax.nn.sigmoid(jnp.einsum("te,leh->lth", h, lay["w_ret_gate"]) + lay["b_ret_gate"][:, None])
    assert 0.993 < float(decay.min()) and float(decay.max()) < 0.99995
    assert float(jnp.mean((decay > 0.995) & (decay < 0.9999))) > 0.97
    for name in ("attn_norm", "mlp_norm", "q_head_norm", "k_head_norm"):
        assert 0.02 < float(jnp.std(lay[name])) < 0.2, name  # gains ~ N(1, 0.1), not 1
    assert float(jnp.abs(lay["b_ret_gate"]).min()) > 1.0  # the bias is not 0
