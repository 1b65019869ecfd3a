"""Test harness: force JAX onto a virtual 8-device CPU platform so mesh /
collective / sharding logic is exercised without TPU hardware (SURVEY.md §4).

Must run before jax is imported anywhere."""

import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_cpu_platform  # noqa: E402

_force_cpu_platform(8)

# Persistent XLA compilation cache: CPU test compiles dominate suite wall
# time; warm runs skip them entirely. The cache key includes backend/flags,
# so the virtual-8-device CPU entries never leak into TPU runs. Where
# JAX_COMPILATION_CACHE_DIR places the cache from outside jax has read it
# already and no other directory is set here.
import jax  # noqa: E402

_CACHE_DIR = os.environ.get(
    "XLLM_TEST_JIT_CACHE",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                 ".test-jit-cache"),
)
if _CACHE_DIR != "0":
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

import pytest  # noqa: E402

# Thread-ownership runtime asserts (common/concurrency.py): on for the
# whole suite so an off-engine-thread call to a @thread_owned surface
# fails the test that made it instead of corrupting slot state. Read at
# decoration time, so it must be set before the package is imported.
os.environ.setdefault("XLLM_THREAD_CHECKS", "1")

# Runtime lock-order sanitizer (docs/STATIC_ANALYSIS.md): under
# XLLM_LOCK_TRACE=1, wrap every repo-created lock from here on — before
# any test module imports the package — and assert after each test that
# the fleet-wide acquisition graph stayed cycle-free and no lock was
# held across a fault point. The chaos/differential suites (test_faults,
# test_master_failover, test_prefix_fabric, test_encoder_fabric) are the
# ones that drive real multi-instance interleavings through it.
from xllm_service_tpu.obs import locktrace  # noqa: E402

if locktrace.enabled():
    locktrace.install()


@pytest.fixture(autouse=True)
def _locktrace_guard():
    yield
    if not locktrace.active():
        return
    rep = locktrace.report()
    if rep["cycles"] or rep["point_holds"]:
        # Reset so one violation fails the test that produced it, not
        # every test after it.
        locktrace.reset()
        lines = [
            f"lock-order cycle: {' -> '.join(c)}" for c in rep["cycles"]
        ] + [
            f"lock {site} held across fault point {point!r} ({n} hits)"
            for (point, site), n in sorted(rep["point_holds"].items())
        ]
        pytest.fail(
            "locktrace sanitizer violations:\n  " + "\n  ".join(lines),
            pytrace=False,
        )


@pytest.fixture(autouse=True)
def _no_mesh_left_declared():
    """`ops.attention.set_shard_context` is per THREAD and read at trace
    time: an executor built at tp > 1 on a worker's main thread leaves its
    mesh declared, and a later test on that worker that plans or traces a
    kernel launch itself (tests/test_pallas_kernels.py's cache writes)
    then finds the Pallas route closed, by the order `--dist load` handed
    the tests out (38 such failures in one whole run of PR 53, none in the
    one before it). Every test starts with no mesh declared; an executor
    declares its own before every step it traces."""
    from xllm_service_tpu.ops import attention

    attention.set_shard_context(None)
    yield


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
