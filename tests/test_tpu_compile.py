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
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from xllm_service_tpu.models import llama
from xllm_service_tpu.models.configs import get_model_config
from xllm_service_tpu.ops import attention

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
    monkeypatch.setenv("XLLM_RAGGED_ATTENTION_KERNEL", "1")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kernel_shapes(one_chip):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = s((NB, HKV, BS, D))
    return s, cache


def test_decode_kernel_compiles(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.paged_attention import (
        paged_attention_kernel,
    )

    s, cache = _kernel_shapes(one_chip)
    text = _compile(
        lambda q, k, v, bt, sl: paged_attention_kernel(q, k, v, bt, sl, SCALE),
        s((R, HQ, D)), cache, cache,
        s((R, 16), jnp.int32), s((R,), jnp.int32),
    )
    assert "tpu_custom_call" in text


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


def test_ragged_kernel_compiles(one_chip, no_persistent_cache):
    from xllm_service_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention_kernel,
    )

    s, cache = _kernel_shapes(one_chip)
    seg_lens = (1,) * R + (256, 256)
    B, T = len(seg_lens), sum(seg_lens)
    text = _compile(
        lambda q, k, v, bt, ql, p0: ragged_paged_attention_kernel(
            q, k, v, bt, ql, p0, seg_lens, SCALE
        ),
        s((T, HQ, D)), cache, cache,
        s((B, 16), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


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


def test_decode_step_compiles(one_chip, no_persistent_cache, as_on_tpu):
    cfg, params, cache, s = _model_shapes(one_chip)
    text = _compile(
        lambda p, k, v, t, pos, bt, act: llama.decode_step(
            p, cfg, k, v, t, pos, bt, act
        ),
        params, cache, cache,
        s((R,)), s((R,)), s((R, 16)), s((R,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_mixed_step_compiles(one_chip, no_persistent_cache, as_on_tpu):
    cfg, params, cache, s = _model_shapes(one_chip)
    P, Lpad = 2, 256
    text = _compile(
        lambda p, k, v, t, pos, bt, act, pt, ps, pl_, ptab: llama.mixed_step(
            p, cfg, k, v, t, pos, bt, act, pt, ps, pl_, ptab
        ),
        params, cache, cache,
        s((R,)), s((R,)), s((R, 16)), s((R,), jnp.bool_),
        s((P, Lpad)), s((P,)), s((P,)), s((P, 16)),
    )
    assert "tpu_custom_call" in text


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
