"""Every family file against the program's parameter tree, and the Llama
family's draws against digests written down from the parent of PR 30
(benchmarks/harness/reference.py, commit 07f0a56): a program PR that
changes a tree, or a benchmark PR that changes a draw, fails here on the
CPU and not in a cell's set-up on the chip."""
import glob
import hashlib
import json
import os

import numpy as np
import pytest

from benchmarks.harness import family as family_mod

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def configurations():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def test_every_family_file_is_named_and_every_configuration_names_one():
    files = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(family_mod.FAMILIES, "*.py"))}
    named = {c.get("family") for c in configurations()}
    assert files and files == named, (files, named)


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


# sha256 over every leaf (path, dtype, shape, bytes; sorted by path) of the
# parent's jitted reference.make_weights at rehearse-tiny, on the CPU backend
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
    assert config["family"] == "llama"
    w = jax.jit(lambda k: fam.make_weights(config, k, jnp.dtype(dtype)))(family_mod.seed_key(seed))
    assert digest(w) == PARENT_DIGESTS[(seed, dtype)]
