"""The products that read a stacked leaf in place (models/llama.py
`_plain_product`, PR 45): brumby's `_qkv` and deepseek's `_q_heads` give
what they gave before the fence, bit for bit, in the dtypes their docstrings
promise, alone and under the `vmap` their callers put them in; at the tiny
configurations and at one layer of the benchmark's widths. What the fence
does to the COMPILED program is tests/test_tpu_compile.py's
`test_step_reads_every_weight_leaf_where_it_lies`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.models import brumby, deepseek, llama
from xllm_service_tpu.models.configs import get_model_config

T = 8


def _weight(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])).astype(jnp.bfloat16)


def _brumby_layer(cfg, key):
    E, D = cfg.hidden_size, cfg.head_dim
    kq, kk, kv, kn = jax.random.split(key, 4)
    return {
        "wq": _weight(kq, (E, cfg.num_heads * D)),
        "wk": _weight(kk, (E, cfg.num_kv_heads * D)),
        "wv": _weight(kv, (E, cfg.num_kv_heads * D)),
        "q_head_norm": 1.0 + 0.1 * jax.random.normal(kn, (D,), jnp.float32),
        "k_head_norm": jnp.ones((D,), jnp.float32),
    }


def _deepseek_layer(cfg, key):
    E, H = cfg.hidden_size, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    kd, ku = jax.random.split(key)
    if cfg.q_lora_rank > 0:
        return {
            "w_dq": _weight(kd, (E, cfg.q_lora_rank)),
            "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
            "w_uq": _weight(ku, (cfg.q_lora_rank, H)),
        }
    return {"w_q": _weight(ku, (E, H))}


FAMILIES = {
    "brumby": (brumby, brumby._qkv, _brumby_layer, jnp.float32),
    "deepseek": (deepseek, deepseek._q_heads, _deepseek_layer, jnp.bfloat16),
}


@pytest.mark.parametrize("batched", [False, True], ids=["rows", "vmapped"])
@pytest.mark.parametrize(
    "family,config",
    [
        ("brumby", "brumby-tiny"), ("brumby", "brumby-14b"),
        ("deepseek", "deepseek-tiny"), ("deepseek", "deepseek-moe-tiny"), ("deepseek", "deepseek-v2"),
    ],
)
def test_fenced_product_gives_the_unfenced_results(monkeypatch, family, config, batched):
    mod, fn, make_layer, dtype = FAMILIES[family]
    cfg = get_model_config(config)
    lp = make_layer(cfg, jax.random.key(45))
    shape = (2, T, cfg.hidden_size) if batched else (T, cfg.hidden_size)
    x = jax.random.normal(jax.random.key(7), shape, jnp.float32).astype(jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(100, 100 + T, dtype=jnp.int32), shape[:-1])

    def run():  # the layer an ARGUMENT: a closed-over leaf would be a 75 MB constant of the program
        def step(lp, x, positions):
            one = lambda hx, pos: fn(lp, cfg, hx, pos)  # noqa: E731
            return (jax.vmap(one) if batched else one)(x, positions)

        return jax.jit(step)(lp, x, positions)

    fenced = run()
    # the parent's text: the same products with no fence on their results
    monkeypatch.setattr(llama, "_plain_product", lambda y: y)
    monkeypatch.setattr(mod, "_plain_product", lambda y: y, raising=False)
    plain = run()
    assert len(fenced) == len(plain)
    for got, want in zip(fenced, plain):
        assert got.dtype == want.dtype == dtype
        assert got.shape == want.shape and got.shape[:-2] == shape[:-1]
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("family,config,fences", [("brumby", "brumby-tiny", 3), ("deepseek", "deepseek-tiny", 1)])
def test_the_fence_is_in_the_traced_products(family, config, fences):
    """Both functions carry the fence (one a product), so a rewrite that
    drops it fails here and not only in the described-chip compile."""
    _, fn, make_layer, _ = FAMILIES[family]
    cfg = get_model_config(config)
    lp = make_layer(cfg, jax.random.key(0))
    x = jnp.zeros((T, cfg.hidden_size), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda hx: fn(lp, cfg, hx, jnp.arange(T, dtype=jnp.int32)))(x))
    assert text.count("optimization_barrier") == fences, text
