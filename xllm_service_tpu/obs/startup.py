"""Start-up timeline: what a process built before it served, from inside
(docs/OBSERVABILITY.md "Start-up timeline").

Two things, both process-wide, because the work is: the executor is built
before any engine or registry exists, and JAX's compile events belong to
the process, not to an engine.

* START-UP PHASES (`startup_phase`): `obs.spans.EnginePhases` over
  `STARTUP_PHASES`, one helper a thread, all adding into one table. Each
  phase is a `TraceAnnotation("xllm.startup.<phase>")` and a child of
  `xllm_engine_startup_seconds{phase}`; `mark_first_step` sets
  `xllm_engine_first_step_seconds` once.

* PROGRAM BUILDS (`install`): listeners of `jax.monitoring`, registered at
  the first `ModelExecutor` and never at import (the master and the load
  generators import `obs` and must not pull JAX in). JAX times tracing
  (`jaxpr_trace_duration`), lowering (`jaxpr_to_mlir_module_duration`) and
  the backend compile (`backend_compile_duration`) by `fun_name`; the
  persistent cache's `cache_hits` / `cache_misses` and its
  `cache_retrieval_time_sec` carry NO name: they fire on the compiling
  thread INSIDE the backend-compile scope and before its duration event,
  so a thread-local carries them to it. A hit is an executable read from
  the cache's directory, a miss one compiled and written there; a compile
  that brought neither (no cache configured, or a result too quick or too
  small for the cache to keep) counts `none`. `compile` is the
  backend-compile seconds WITHOUT the retrieval, so the four stages of a
  program add up to what it cost the process. JAX also announces each
  scope's START (a scalar event): a scope that ends while another is open
  on its thread (a library function traced inside a step program's trace,
  a constant compiled eagerly inside it) is inside the outer one's
  seconds already and books none of its own; its build still counts.
  `program` is a name of `obs.spans.STEP_PROGRAMS`, else `other`.

Every engine's registry shows the same process-wide numbers (`export`).
Nothing here runs on a step's path: the listeners fire when something
traces, lowers or compiles."""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, Tuple

from xllm_service_tpu import IMPORTED_AT
from xllm_service_tpu.obs.spans import (
    STARTUP_PHASES,
    STEP_PROGRAMS,
    EnginePhases,
    annotation,
)

PROGRAM_STAGES = ("trace", "lower", "compile", "cache_read")
KDA_CHUNK_FORMS = ("kernel", "xla")  # ops/kda.py `chunk_update`: which form a traced layer holds
BUILD_CACHE = ("hit", "miss", "none")
OTHER = "other"

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_STAGE_OF = {_TRACE: "trace", _LOWER: "lower", _COMPILE: "compile"}


def program_of(fun_name) -> str:
    """The `program` label of one of JAX's compile events: tracing names
    the function (`_decode_impl`), lowering and compiling its module
    (`jit(_decode_impl)`; `jit__decode_impl` in other versions)."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    elif name.startswith("jit_"):
        name = name[4:]
    return name if name in STEP_PROGRAMS else OTHER


def _annotation(name: str):
    """The phase's annotation where JAX is in the process already: an
    instance over a fake engine (a service-only process) imports none for
    a span no profiler could record."""
    if "jax" not in sys.modules:
        return contextlib.nullcontext()
    return annotation(name)


class _StartupScope(contextlib.ContextDecorator):
    """`with startup_phase(name):` or `@startup_phase(name)`: the phase of
    the calling thread's helper, resolved when the scope opens."""

    def __init__(self, timeline: "StartupTimeline", name: str):
        self._timeline, self._name = timeline, name
        self._scope = None

    def _recreate_cm(self):  # a decorated function may run on many threads
        return _StartupScope(self._timeline, self._name)

    def __enter__(self) -> None:
        self._scope = self._timeline._thread_phases().phase(self._name)
        self._scope.__enter__()

    def __exit__(self, *exc) -> None:
        self._scope.__exit__(*exc)


class StartupTimeline:
    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._mu = threading.Lock()
        self._local = threading.local()
        self._installed = False
        programs = STEP_PROGRAMS + (OTHER,)
        # all guarded by: self._mu (writes); read lock-free at a scrape
        self.phase_seconds: Dict[str, float] = dict.fromkeys(STARTUP_PHASES, 0.0)
        self.first_step_s = 0.0  # 0 until the first step's results were read
        self.program_seconds: Dict[Tuple[str, str], float] = {
            (p, s): 0.0 for p in programs for s in PROGRAM_STAGES
        }
        self.program_builds: Dict[Tuple[str, str], int] = {
            (p, c): 0 for p in programs for c in BUILD_CACHE
        }
        self.kda_chunk_forms: Dict[str, int] = dict.fromkeys(KDA_CHUNK_FORMS, 0)

    def count_kda_chunk_form(self, form: str) -> None:
        """A KDA layer's chunk form was traced into a program as `form`:
        once a layer body and trace, never on a step's path."""
        with self._mu:
            self.kda_chunk_forms[form] += 1

    # ------------------------------------------------------------ phases

    def phase(self, name: str) -> _StartupScope:
        if name not in STARTUP_PHASES:
            raise ValueError(f"{name!r} is not one of STARTUP_PHASES")
        return _StartupScope(self, name)

    def _thread_phases(self) -> EnginePhases:
        phases = getattr(self._local, "phases", None)
        if phases is None:
            phases = self._local.phases = EnginePhases(
                self._add_phase, clock=self._clock, annotate=_annotation,
                names=STARTUP_PHASES, prefix="xllm.startup.",
            )
        return phases

    def _add_phase(self, name: str, seconds: float) -> None:
        with self._mu:
            self.phase_seconds[name] += seconds

    def mark_first_step(self) -> None:
        """The first step program's results are on the host: the restart
        as a caller feels it, from the import of the package. Once a
        process."""
        with self._mu:
            if not self.first_step_s:
                self.first_step_s = self._clock() - IMPORTED_AT

    # ---------------------------------------------------- program builds

    def install(self) -> None:
        """Register the listeners, once a process."""
        with self._mu:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring

        monitoring.register_scalar_listener(self._on_scope_start)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_scope_start(self, event: str, value, **kw) -> None:
        if event in _STAGE_OF:
            self._local.depth = getattr(self._local, "depth", 0) + 1

    def _on_event(self, event: str, **kw) -> None:
        cache = _CACHE.get(event)
        if cache is not None:
            self._local.cache = cache

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        local = self._local
        if event == _CACHE_READ:
            local.read_s = getattr(local, "read_s", 0.0) + seconds
            return
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        # an event nobody announced (another JAX, a test's own) is outermost
        depth = local.depth = max(getattr(local, "depth", 0) - 1, 0)
        program = program_of(kw.get("fun_name", ""))
        if stage != "compile":
            if depth == 0:
                with self._mu:
                    self.program_seconds[program, stage] += seconds
            return
        read_s = min(getattr(local, "read_s", 0.0), seconds)
        cache = getattr(local, "cache", None) or "none"
        local.read_s, local.cache = 0.0, None
        with self._mu:
            self.program_builds[program, cache] += 1
            if depth == 0:
                self.program_seconds[program, "compile"] += seconds - read_s
                self.program_seconds[program, "cache_read"] += read_s

    # ------------------------------------------------------------ export

    def export(self, registry) -> None:
        """The process's timeline as series of one engine's registry."""
        phases = registry.gauge(
            "xllm_engine_startup_seconds",
            "Seconds this process spent in each exclusive start-up phase "
            "(xllm.startup.<phase> annotations), summed over every "
            "executor, engine and instance it built",
            labelnames=("phase",),
        )
        for p in STARTUP_PHASES:
            phases.labels(phase=p).set_function(
                lambda p=p: self.phase_seconds[p]
            )
        registry.gauge(
            "xllm_engine_first_step_seconds",
            "Import of xllm_service_tpu to the first step program's "
            "results read by an engine thread, once a process (0 before)",
        ).set_function(lambda: self.first_step_s)
        seconds = registry.counter(
            "xllm_engine_program_seconds_total",
            "Seconds this process spent tracing, lowering and compiling "
            "each step program and reading it from the persistent compile "
            "cache (jax.monitoring); everything else under other",
            labelnames=("program", "stage"),
        )
        for key in self.program_seconds:
            seconds.labels(program=key[0], stage=key[1]).set_function(
                lambda key=key: self.program_seconds[key]
            )
        builds = registry.counter(
            "xllm_engine_program_builds_total",
            "Backend compiles by step program and by what the persistent "
            "compile cache did: hit (read from it), miss (compiled and "
            "written), none (not asked, or too quick or small to keep)",
            labelnames=("program", "cache"),
        )
        for key in self.program_builds:
            builds.labels(program=key[0], cache=key[1]).set_function(
                lambda key=key: self.program_builds[key]
            )
        forms = registry.counter(
            "xllm_engine_kda_chunk_kernel_total",
            "KDA layer bodies traced into this process's programs by the "
            "form their prefill chunk takes: kernel (kda_chunk_kernel) or "
            "xla (the jax.numpy chunk form); counted when a program is "
            "traced, a layer body once",
            labelnames=("form",),
        )
        for form in KDA_CHUNK_FORMS:
            forms.labels(form=form).set_function(
                lambda form=form: self.kda_chunk_forms[form]
            )


TIMELINE = StartupTimeline()
startup_phase = TIMELINE.phase
