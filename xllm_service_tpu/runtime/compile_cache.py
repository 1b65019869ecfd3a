"""Where the persistent XLA compile cache lives, and whether the full
bucket-program prewarm that feeds it is on (ISSUE 18 tentpole b — the
host-side dispatch war).

PR 11 measured a 2.7-4 s recompile ambush on the first post-idle
dispatch of each bucket-program variant. Two layers kill that class:

  * **On-disk persistence** — jax's persistent compilation cache, so a
    restarted instance with the same geometry reloads every compiled
    executable from disk instead of re-running XLA. XLA's own cache key
    (HLO + compile options + backend) already separates geometries, so
    the directory is flat.
  * **Prewarm enumeration** — `ModelExecutor.prewarm_programs()` walks
    the FULL bucket-program family the engine can dispatch (context
    buckets x step builders x spec/guided variants) and compiles each
    through its jit entry point, populating both the in-process jit
    dispatch caches (zero fresh lowerings afterwards — the engine's
    compile-cache hit/miss instruments count against this) and the
    on-disk cache (warm restarts skip the XLA invocations).

The directory can be placed from outside: where `JAX_COMPILATION_CACHE_DIR`
is set jax reads it itself and NO code path of this repo sets any other
directory (`resolve_cache_dir` is the one place that reads it). Where it
is not set, entry points (the instance server, chip_smoke.py, the benches)
configure one fixed path inside the checkout (`DEFAULT_DIR`: a directory
that moves from run to run never hits), and a library caller may pass its
own `EngineConfig.compilation_cache_dir`.

Hatch: `XLLM_COMPILE_CACHE=0` disables the engine-configured cache (and
drops prewarm back to the basic split-step warmup).
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax-compile-cache",
)


def compile_cache_enabled() -> bool:
    """Whether the persistent compile cache (and the full-family prewarm
    that feeds it) is on. Default ON when a cache dir is configured; =0
    always wins."""
    return os.environ.get("XLLM_COMPILE_CACHE", "1") not in (
        "0", "false", "off",
    )


def resolve_cache_dir(configured: str) -> str:
    """The directory compiles persist to, given the one configured
    (`EngineConfig.compilation_cache_dir`; entry points configure
    `DEFAULT_DIR`): the outside placement wins over it; "" (no dir
    anywhere, or XLLM_COMPILE_CACHE=0) means no persistent cache."""
    if not compile_cache_enabled():
        return ""
    return os.environ.get(ENV_DIR) or configured or ""


def cache_entries(path: str) -> int:
    """How many compiled executables the cache directory holds (the
    cold/warm discriminator; -atime bookkeeping files don't count)."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
