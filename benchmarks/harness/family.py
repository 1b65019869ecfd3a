"""The seam between the harness and an architecture. A configuration file
names its family (`"family": "llama"`); that resolves to
benchmarks/families/<family>.py, loaded by path as run.py loads a metric's
reader, so a later PR brings a family as one new file. The harness uses
the five names of EXPORTS and nothing else of a family file; what each
must be is in benchmarks/families/llama.py's docstring. Everything the
harness hands a family is here too: the configuration as parsed, and the
PRNG key made from --seed."""

from __future__ import annotations

import importlib.util
import os
from typing import Mapping

import numpy as np

FAMILIES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "families")
EXPORTS = ("model_config", "make_weights", "forward_logits", "LIMITS", "LIMITS_READINGS")
LIMIT_KEYS = ("logprob_mse", "deficit_max")  # what check.compare holds a sample to


class FamilyError(RuntimeError):
    pass


def load(config: Mapping):
    """The family module a configuration names. No default: a
    configuration without the key, a name without a file and a file
    without one of EXPORTS are errors, raised before anything is built."""
    name = config.get("family")
    if not isinstance(name, str) or not name:
        raise FamilyError(
            f"configuration {config.get('name')!r} names no \"family\" "
            f"(a file of {FAMILIES})"
        )
    path = os.path.join(FAMILIES, name + ".py")
    if not os.path.exists(path):
        raise FamilyError(f"family {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [k for k in EXPORTS if not hasattr(mod, k)]
    if not missing:
        missing = [f"LIMITS[{k!r}]" for k in LIMIT_KEYS if k not in mod.LIMITS]
    if missing:
        raise FamilyError(f"family file {path} lacks {', '.join(missing)}")
    return mod


def seed_key(seed: int):
    """A jax PRNG key from any non-negative whole number (seeds beyond
    2**31 included): two uint32 words from numpy's SeedSequence."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, dtype=jnp.uint32))
