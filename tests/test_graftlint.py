"""graftlint: fixture-based unit tests per pass + the repo-wide
zero-findings run (tier-1) + runtime halves (thread-ownership asserts,
lock-order sanitizer synthetics).

Each pass is exercised against synthetic in-memory projects
(Project.from_sources) with a positive (trips), a negative (clean), and
a waiver case — the analyzers are production code for CI and get the
same coverage discipline as the engine. The final class runs
`scripts/graftlint.py --all` over the real tree and requires exit 0:
the lint landing clean IS the acceptance criterion (ISSUE 10).
"""

import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from xllm_service_tpu.analysis import (  # noqa: E402
    BlockingUnderLockPass,
    FaultPointsPass,
    HatchRegistryPass,
    LockDisciplinePass,
    MetricNamesPass,
    Project,
    ShardingRulesPass,
    SpanStagesPass,
    ThreadJoinsPass,
    ThreadOwnershipPass,
    all_passes,
    run_passes,
)


def proj(src, tests=None, docs=None):
    return Project.from_sources({"pkg/m.py": src}, tests=tests, docs=docs)


def run_one(p, src, **kw):
    return p.run(proj(src, **kw))


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------


class TestLockDiscipline:
    def test_declared_guard_violation_trips(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []  # guarded by: self._mu\n"
            "    def bad(self):\n"
            "        self._q.append(1)\n"
        )
        fs = run_one(LockDisciplinePass(), src)
        assert len(fs) == 1 and "declared guarded by self._mu" in fs[0].message
        assert fs[0].line == 7

    def test_declared_guard_under_lock_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []  # guarded by: self._mu\n"
            "    def good(self):\n"
            "        with self._mu:\n"
            "            self._q.append(1)\n"
            "            self._q = []\n"
        )
        assert run_one(LockDisciplinePass(), src) == []

    def test_locked_suffix_and_holds_annotation_exempt(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []  # guarded by: self._mu\n"
            "    def _drain_locked(self):\n"
            "        self._q.append(1)\n"
            "    def helper(self):  # graftlint: holds=self._mu\n"
            "        self._q.append(2)\n"
        )
        assert run_one(LockDisciplinePass(), src) == []

    def test_init_only_marker_exempts_constructor_extension(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._init_x()\n"
            "    def _init_x(self):  # graftlint: init-only\n"
            "        self._q = []  # guarded by: self._mu\n"
            "        self._q.append(0)\n"
        )
        assert run_one(LockDisciplinePass(), src) == []

    def test_majority_locked_inference_trips_on_straggler(self):
        body = "\n".join(
            f"    def m{i}(self):\n"
            f"        with self._mu:\n"
            f"            self._q.append({i})" for i in range(3)
        )
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []\n"
            f"{body}\n"
            "    def straggler(self):\n"
            "        self._q.append(9)\n"
        )
        fs = run_one(LockDisciplinePass(), src)
        assert len(fs) == 1 and "majority-locked" in fs[0].message

    def test_inference_needs_quorum(self):
        # 2 locked sites < MIN_LOCKED_SITES: no inference, no finding
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []\n"
            "    def a(self):\n"
            "        with self._mu:\n"
            "            self._q.append(1)\n"
            "    def b(self):\n"
            "        with self._mu:\n"
            "            self._q.append(2)\n"
            "    def c(self):\n"
            "        self._q.append(3)\n"
        )
        assert run_one(LockDisciplinePass(), src) == []

    def test_condition_alias_counts_as_lock(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._cv = threading.Condition(self._mu)\n"
            "        self._q = []  # guarded by: self._mu\n"
            "    def good(self):\n"
            "        with self._cv:\n"
            "            self._q.append(1)\n"
        )
        assert run_one(LockDisciplinePass(), src) == []

    def test_waiver_suppresses_and_is_counted(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q = []  # guarded by: self._mu\n"
            "    def bad(self):\n"
            "        self._q.append(1)  # graftlint: allow=lock-discipline -- probe\n"
        )
        res = run_passes([LockDisciplinePass()], proj(src))
        assert res.findings == [] and len(res.waived) == 1


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------


class TestBlockingUnderLock:
    def test_rpc_under_lock_trips(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "    def bad(self):\n"
            "        with self._mu:\n"
            "            post_json(1)\n"
        )
        fs = run_one(BlockingUnderLockPass(), src)
        assert len(fs) == 1 and "post_json" in fs[0].message

    def test_rpc_after_lock_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "    def good(self):\n"
            "        with self._mu:\n"
            "            x = 1\n"
            "        post_json(x)\n"
        )
        assert run_one(BlockingUnderLockPass(), src) == []

    def test_sleep_join_queue_trips(self):
        src = (
            "import threading, time\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "    def bad(self):\n"
            "        with self._mu:\n"
            "            time.sleep(1)\n"
            "            self._thread.join()\n"
            "            self._queue.put(1)\n"
        )
        msgs = [f.message for f in run_one(BlockingUnderLockPass(), src)]
        assert len(msgs) == 3
        assert any("time.sleep" in m for m in msgs)
        assert any(".join()" in m for m in msgs)
        assert any(".put()" in m for m in msgs)

    def test_condition_self_wait_not_flagged(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "    def ok(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait(timeout=1)\n"
        )
        assert run_one(BlockingUnderLockPass(), src) == []

    def test_shared_lock_condition_wait_not_flagged(self):
        # MemoryStore idiom: Condition(self._mu), wait under self._mu
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.RLock()\n"
            "        self._cv = threading.Condition(self._mu)\n"
            "    def ok(self):\n"
            "        with self._mu:\n"
            "            self._cv.wait(timeout=1)\n"
        )
        assert run_one(BlockingUnderLockPass(), src) == []

    def test_foreign_wait_under_lock_trips(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "    def bad(self, ev):\n"
            "        with self._mu:\n"
            "            ev.wait(5)\n"
        )
        fs = run_one(BlockingUnderLockPass(), src)
        assert len(fs) == 1 and ".wait()" in fs[0].message

    def test_nonblocking_queue_and_str_join_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "    def ok(self):\n"
            "        with self._mu:\n"
            "            self._queue.put(1, block=False)\n"
            "            s = ','.join(['a'])\n"
            "            p = os.path.join('a', 'b')\n"
        )
        assert run_one(BlockingUnderLockPass(), src) == []

    def test_module_level_lock_and_waiver(self):
        src = (
            "import threading, time\n"
            "_install_mu = threading.Lock()\n"
            "def bad():\n"
            "    with _install_mu:\n"
            "        time.sleep(1)\n"
        )
        fs = run_one(BlockingUnderLockPass(), src)
        assert len(fs) == 1
        src_waived = src.replace(
            "time.sleep(1)",
            "time.sleep(1)  # graftlint: allow=blocking-under-lock -- probe",
        )
        res = run_passes([BlockingUnderLockPass()], proj(src_waived))
        assert res.findings == [] and len(res.waived) == 1


# ---------------------------------------------------------------------------
# thread-ownership (static)
# ---------------------------------------------------------------------------


class TestThreadOwnershipStatic:
    SRC = (
        "from xllm_service_tpu.common.concurrency import (\n"
        "    claim_thread, thread_owned)\n"
        "class E:\n"
        "    def _loop(self):\n"
        "        claim_thread(self, 'engine')\n"
        "        self._slot_admit(1)\n"
        "    @thread_owned('engine')\n"
        "    def _step(self):\n"
        "        self._slot_admit(2)\n"
        "    @thread_owned('engine')\n"
        "    def _slot_admit(self, s):\n"
        "        pass\n"
        "    def off_thread(self):\n"
        "        self._slot_admit(3)\n"
    )

    def test_unowned_call_site_trips_owned_and_claimer_pass(self):
        fs = run_one(ThreadOwnershipPass(), self.SRC)
        assert len(fs) == 1
        assert "off_thread" in fs[0].message and fs[0].line == 14

    def test_nested_def_does_not_inherit_ownership(self):
        src = (
            "from xllm_service_tpu.common.concurrency import thread_owned\n"
            "class E:\n"
            "    @thread_owned('engine')\n"
            "    def _step(self):\n"
            "        def cb():\n"
            "            self._slot_admit(1)\n"
            "        return cb\n"
            "    @thread_owned('engine')\n"
            "    def _slot_admit(self, s):\n"
            "        pass\n"
        )
        fs = run_one(ThreadOwnershipPass(), src)
        assert len(fs) == 1 and fs[0].line == 6

    def test_engine_chain_is_fully_marked_in_repo(self):
        # the real engine: zero findings means every call site of an
        # owned method is itself owned or the claiming loop
        assert ThreadOwnershipPass().run(Project.load(REPO)) == []


# ---------------------------------------------------------------------------
# thread-joins
# ---------------------------------------------------------------------------


class TestThreadJoins:
    def test_unjoined_self_thread_trips(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "        self._t.start()\n"
        )
        fs = run_one(ThreadJoinsPass(), src)
        assert len(fs) == 1 and "never joins" in fs[0].message

    def test_joined_thread_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "        self._t.start()\n"
            "    def stop(self):\n"
            "        self._t.join(timeout=2)\n"
        )
        assert run_one(ThreadJoinsPass(), src) == []

    def test_waiver(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run)"
            "  # graftlint: allow=thread-joins -- probe\n"
        )
        res = run_passes([ThreadJoinsPass()], proj(src))
        assert res.findings == [] and len(res.waived) == 1


# ---------------------------------------------------------------------------
# hatch-registry
# ---------------------------------------------------------------------------


class TestHatchRegistry:
    DOCS = {"docs/ARCHITECTURE.md": (
        "| Hatch | Gates | Default |\n"
        "|---|---|---|\n"
        "| `XLLM_DOCUMENTED` | a thing | ON |\n"
        "| `XLLM_STALE_ROW` | gone | OFF |\n"
        "| `XLLM_EMPTY_DEFAULT` | a thing | - |\n"
    )}

    def test_undocumented_stale_and_empty_default_trip(self):
        src = (
            "import os\n"
            "a = os.environ.get('XLLM_DOCUMENTED', '')\n"
            "b = os.environ.get('XLLM_UNDOCUMENTED', '')\n"
            "c = os.environ.get('XLLM_EMPTY_DEFAULT', '')\n"
        )
        fs = run_one(HatchRegistryPass(), src, docs=self.DOCS)
        msgs = "\n".join(f.message for f in fs)
        assert len(fs) == 3
        assert "XLLM_UNDOCUMENTED" in msgs and "no row" in msgs
        assert "XLLM_STALE_ROW" in msgs and "stale row" in msgs
        assert "XLLM_EMPTY_DEFAULT" in msgs and "empty Default" in msgs

    def test_kernel_token_reference_requires_row(self):
        # *_KERNEL hatches keep the legacy rule: a bare token reference
        # (helper/dispatch-table form, no environ read) needs a row too,
        # reported once at its first reference.
        src = (
            "HATCHES = ['XLLM_PHANTOM_KERNEL']\n"
            "ALSO = 'XLLM_PHANTOM_KERNEL'\n"
        )
        fs = run_one(HatchRegistryPass(), src, docs=self.DOCS)
        kernel = [f for f in fs if "XLLM_PHANTOM_KERNEL" in f.message]
        assert len(kernel) == 1 and kernel[0].line == 1

    def test_documented_hatch_clean(self):
        src = "import os\nx = os.environ.get('XLLM_DOCUMENTED', '1')\n"
        docs = {"docs/ARCHITECTURE.md": (
            "| Hatch | Gates | Default |\n|---|---|---|\n"
            "| `XLLM_DOCUMENTED` | a thing | ON |\n"
        )}
        assert run_one(HatchRegistryPass(), src, docs=docs) == []

    def test_repo_registry_is_complete(self):
        # every real env read documented, every row live (satellite:
        # the full XLLM_* surface, not just *_KERNEL)
        assert HatchRegistryPass().run(Project.load(REPO)) == []


# ---------------------------------------------------------------------------
# metric-names / fault-points (legacy passes, absorbed)
# ---------------------------------------------------------------------------


class TestLegacyPasses:
    def test_metric_names_static_violations(self):
        src = (
            "reg.counter('xllm_good_total', 'd')\n"
            "reg.counter('xllm_bad_counter', 'd')\n"
            "reg.gauge('xllm_bad_total', 'd')\n"
            "reg.histogram('xllm_bad_bucket', 'd')\n"
            "reg.counter('BadName', 'd')\n"
        )
        fs = run_one(MetricNamesPass(runtime=False), src)
        assert len(fs) == 4
        assert fs[0].line == 2  # first violation anchored to its line

    def test_fault_points_dup_uncovered_required(self):
        src = (
            "faults.point('a.b')\n"
            "faults.point('a.b')\n"
            "faults.point('c.d')\n"
        )
        fs = run_one(FaultPointsPass(), src, tests={"tests/t.py": "a.b"})
        msgs = "\n".join(f.message for f in fs)
        assert "defined at 2 sites" in msgs          # dup (both sites)
        assert "'c.d' is not referenced" in msgs     # uncovered
        assert "required point" in msgs              # REQUIRED_POINTS gone

    def test_fault_points_clean_fixture(self):
        from xllm_service_tpu.analysis import REQUIRED_POINTS
        src = "\n".join(
            f"faults.point('{p}')" for p in sorted(REQUIRED_POINTS)
        )
        tests = {"tests/t.py": " ".join(sorted(REQUIRED_POINTS))}
        assert run_one(FaultPointsPass(), src, tests=tests) == []


# ---------------------------------------------------------------------------
# span-stages (distributed-tracing vocabulary + trace-plane registry)
# ---------------------------------------------------------------------------


class TestSpanStages:
    def _pass(self, planes=()):
        return SpanStagesPass(
            vocab=("admit", "finish", "handoff_send"), planes=planes,
        )

    def test_off_vocabulary_stage_trips(self):
        src = (
            'self._span(srid, "admit", n=1)\n'
            'self._span(srid, "not_a_stage")\n'
            'ring.emit(srid, "handoff_send")\n'
        )
        fs = run_one(self._pass(), src)
        assert len(fs) == 1
        assert fs[0].line == 2
        assert "not_a_stage" in fs[0].message

    def test_all_emit_surfaces_are_scanned(self):
        src = (
            'tracer.stage(srid, "bogus_a")\n'
            'ring.emit(srid, "bogus_b")\n'
            'self.span_hook("", "bogus_c", n=1)\n'
            'self._span_hook(srid, "bogus_d")\n'
        )
        fs = run_one(self._pass(), src)
        assert {f.line for f in fs} == {1, 2, 3, 4}

    def test_non_literal_stage_is_skipped(self):
        src = 'self._tracer.stage(srid, terminal, code=1)\n'
        assert run_one(self._pass(), src) == []

    def test_trace_plane_needle_missing_trips(self):
        planes = (
            ("pkg/m.py", 'fwd["trace"] = ctx', "dispatch plane"),
            ("pkg/gone.py", "x", "vanished plane"),
        )
        src = 'fwd = {}\n'
        fs = run_one(self._pass(planes=planes), src)
        msgs = "\n".join(f.message for f in fs)
        assert "no longer forwards trace context" in msgs
        assert "file is gone" in msgs

    def test_trace_plane_clean_fixture(self):
        planes = (("pkg/m.py", 'fwd["trace"] = ctx', "dispatch plane"),)
        src = 'fwd["trace"] = ctx\n'
        assert run_one(self._pass(planes=planes), src) == []

    def test_repo_vocabulary_is_the_canonical_tuple(self):
        from xllm_service_tpu.obs.spans import ALL_SPAN_STAGES
        assert SpanStagesPass().vocab == frozenset(ALL_SPAN_STAGES)

    @pytest.mark.parametrize("src,bad", [
        ('with self._phases.phase("dispatch"):\n'
         '    with phase("sampling", annotate=False):\n        pass\n',
         "sampling"),
        ('with _leaf("launch"):\n    pass\nwith _leaf("fetch"):\n    pass\n',
         "fetch"),
        ('with region("ffn"):\n    pass\n@obs_spans.region("mlp")\ndef f():\n    pass\n',
         "mlp"),
        ('with startup_phase("pools"):\n    pass\n'
         '@obs_startup.startup_phase("warmup")\ndef f():\n    pass\n',
         "warmup"),
    ], ids=["engine-phase", "executor-leaf", "device-region", "startup-phase"])
    def test_off_vocabulary_phase_or_leaf_trips(self, src, bad):
        fs = run_one(self._pass(), src)
        assert len(fs) == 1 and fs[0].line in (2, 3)
        assert repr(bad) in fs[0].message

    def test_phase_like_names_are_not_phases(self):
        # another object's method, a non-literal, and the def itself
        src = (
            'moon._phase("waxing")\n'
            'with phase(name):\n    pass\n'
            'def phase(self, name):\n    pass\n'
            'window_region("nowhere")\n'
            'my_startup_phase("never")\n'
            'with startup_phase(name):\n    pass\n'
            'with region(MIXER_REGIONS[kind]):\n    pass\n'
        )
        assert run_one(self._pass(), src) == []

    def test_registry_rows_point_at_live_needles(self):
        # The shipped TRACE_PLANES rows must hold on the real tree (the
        # repo-wide run below enforces this too; this pins the registry
        # itself so a row edit can't silently no-op the check).
        from xllm_service_tpu.analysis import TRACE_PLANES
        assert len(TRACE_PLANES) >= 6
        project = Project.load(REPO)
        assert SpanStagesPass(vocab=None).run(project) == []


# ---------------------------------------------------------------------------
# framework: waiver bookkeeping
# ---------------------------------------------------------------------------


class TestFramework:
    def test_stale_waiver_is_a_finding(self):
        src = (
            "import threading\n"
            "x = 1  # graftlint: allow=lock-discipline -- nothing here\n"
        )
        res = run_passes(all_passes(runtime=False), proj(src))
        assert any("stale waiver" in f.message for f in res.stale_waivers)
        assert res.failed

    def test_unknown_pass_waiver_is_a_finding(self):
        src = "x = 1  # graftlint: allow=no-such-pass -- typo\n"
        res = run_passes(all_passes(runtime=False), proj(src))
        assert any("unknown pass" in f.message for f in res.stale_waivers)

    def test_pass_catalog_has_the_contracted_passes(self):
        ids = {p.id for p in all_passes(runtime=False)}
        assert {
            "lock-discipline", "blocking-under-lock", "thread-ownership",
            "thread-joins", "hatch-registry", "metric-names",
            "fault-points", "span-stages",
        } <= ids


# ---------------------------------------------------------------------------
# runtime: thread-ownership asserts
# ---------------------------------------------------------------------------


class TestThreadOwnershipRuntime:
    def _mk(self):
        from xllm_service_tpu.common.concurrency import thread_owned

        class Eng:
            @thread_owned("engine")
            def slot(self):
                return threading.get_ident()

        return Eng()

    def test_unclaimed_passes_anywhere(self):
        eng = self._mk()
        assert eng.slot() == threading.get_ident()

    def test_claimed_blocks_foreign_thread_and_release_reopens(self):
        from xllm_service_tpu.common import concurrency

        if not concurrency.checks_enabled():
            pytest.skip("XLLM_THREAD_CHECKS off in this environment")
        eng = self._mk()
        errs = []
        done = threading.Event()

        def owner():
            concurrency.claim_thread(eng, "engine")
            eng.slot()  # owner passes
            done.wait(5)

        t = threading.Thread(target=owner, daemon=True)
        t.start()
        for _ in range(100):
            if getattr(eng, "_thread_owner_engine", None) is not None:
                break
            time.sleep(0.01)
        with pytest.raises(concurrency.ThreadOwnershipError):
            eng.slot()  # foreign thread trips
        done.set()
        t.join(timeout=5)
        concurrency.release_thread(eng, "engine")
        assert eng.slot() == threading.get_ident()  # released: open again


# ---------------------------------------------------------------------------
# runtime: lock-order sanitizer synthetics
# ---------------------------------------------------------------------------


class TestLocktrace:
    @pytest.fixture()
    def traced(self):
        from xllm_service_tpu.obs import locktrace

        was = locktrace.active()
        if not was:
            locktrace.install()
        with locktrace.isolated():
            yield locktrace
        if not was:
            locktrace.uninstall()

    def test_abba_cycle_trips(self, traced):
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        rep = traced.report()
        assert rep["cycles"], rep
        sites = {s for cyc in rep["cycles"] for s in cyc}
        assert any("test_graftlint.py" in s for s in sites)

    def test_consistent_order_clean(self, traced):
        a = threading.Lock()
        b = threading.Lock()
        for _ in range(3):
            with a:
                with b:
                    pass
        rep = traced.report()
        assert rep["cycles"] == [] and rep["edges"] >= 1

    def test_rlock_reentrancy_is_not_a_self_cycle(self, traced):
        r = threading.RLock()
        with r:
            with r:
                pass
        assert traced.report()["cycles"] == []

    def test_same_class_instances_nested_is_one_self_cycle(self, traced):
        # two locks from ONE creation site = one lockdep class; nesting
        # them is a real order hazard and must report exactly ONE cycle
        a, b = threading.Lock(), threading.Lock()
        with a:
            with b:
                pass
        cycles = traced.report()["cycles"]
        assert len(cycles) == 1 and cycles[0][0] == cycles[0][-1]

    def test_held_across_fault_point_recorded(self, traced):
        from xllm_service_tpu.common import faults

        mu = threading.Lock()
        with mu:
            faults.point("lint.probe")
        rep = traced.report()
        assert any(p == "lint.probe" for p, _ in rep["point_holds"])

    def test_point_without_lock_clean(self, traced):
        from xllm_service_tpu.common import faults

        faults.point("lint.probe2")
        assert traced.report()["point_holds"] == {}

    def test_condition_wait_stack_bookkeeping(self, traced):
        # wait() fully releases the condition's lock; after the with
        # block the thread's held-stack must be empty, so a subsequent
        # acquire records NO cv->l2 edge (a bookkeeping leak here would
        # fabricate edges and eventually false cycles).
        cv = threading.Condition()
        l2 = threading.Lock()

        def waiter():
            with cv:
                cv.wait(timeout=0.05)
            with l2:
                pass

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        t.join(timeout=5)
        rep = traced.report()
        assert rep["edges"] == 0 and rep["cycles"] == [], rep


# ---------------------------------------------------------------------------
# sharding-rules
# ---------------------------------------------------------------------------


class TestShardingRules:
    RULES = (
        "def param_shardings(cfg, mesh):\n"
        "    layers = {'attn_norm': 1, 'wq': 1}\n"
        "    layers.update({'w_gate': 1})\n"
        "    layers['wo'] = 1\n"
        "    return {'embed': 1, 'layers': layers}\n"
    )

    def _proj(self, model_src, rules_src=None):
        return Project.from_sources({
            "xllm_service_tpu/models/llama.py": model_src,
            "xllm_service_tpu/parallel/sharding.py": (
                rules_src if rules_src is not None else self.RULES
            ),
        })

    def test_unruled_leaf_trips(self):
        src = (
            "def init_params(cfg, key, dtype):\n"
            "    layers = {'attn_norm': 1, 'wq': 1}\n"
            "    layers['w_new_proj'] = 2\n"
            "    return {'embed': 1, 'layers': layers}\n"
        )
        fs = ShardingRulesPass().run(self._proj(src))
        assert len(fs) == 1 and "w_new_proj" in fs[0].message

    def test_ruled_tree_clean(self):
        src = (
            "def init_params(cfg, key, dtype):\n"
            "    layers = {'attn_norm': 1, 'wq': 1}\n"
            "    layers.update({'w_gate': 1, 'wo': 1})\n"
            "    return {'embed': 1, 'layers': layers}\n"
        )
        assert ShardingRulesPass().run(self._proj(src)) == []

    def test_runtime_lora_leaves_exempt(self):
        src = (
            "def init_params(cfg, key, dtype):\n"
            "    layers = {'wq': 1, 'lora_wq_a': 1}\n"
            "    return {'layers': layers}\n"
        )
        assert ShardingRulesPass().run(self._proj(src)) == []

    def test_missing_rules_file_trips(self):
        src = "def init_params(cfg, key, dtype):\n    return {'wq': 1}\n"
        fs = ShardingRulesPass().run(
            Project.from_sources(
                {"xllm_service_tpu/models/llama.py": src}
            )
        )
        assert len(fs) == 1 and "sharding.py" in fs[0].message

    def test_helper_created_leaf_trips(self):
        # deepseek builds its whole per-layer leaf dict (the MoE
        # expert/router leaves included) in _layer_stack — the pass must
        # walk init_params' local-call closure, or a new expert leaf
        # added out of line would silently replicate (ISSUE 15).
        src = (
            "def _layer_stack(cfg, key):\n"
            "    layers = {'wq': 1}\n"
            "    layers.update({'w_expert_bias': 1})\n"
            "    return layers\n"
            "def init_params(cfg, key, dtype):\n"
            "    return {'embed': 1, 'layers': _layer_stack(cfg, key)}\n"
        )
        fs = ShardingRulesPass().run(self._proj(src))
        assert len(fs) == 1 and "w_expert_bias" in fs[0].message

    def test_helper_created_ruled_leaf_clean(self):
        src = (
            "def _layer_stack(cfg, key):\n"
            "    return {'wq': 1, 'w_gate': 1, 'wo': 1}\n"
            "def init_params(cfg, key, dtype):\n"
            "    return {'embed': 1, 'layers': _layer_stack(cfg, key)}\n"
        )
        assert ShardingRulesPass().run(self._proj(src)) == []

    # -- ppermute axis-vocabulary rule (ISSUE 18) -----------------------

    def _ring_proj(self, ring_src):
        return Project.from_sources({
            "xllm_service_tpu/ops/collective_matmul.py": ring_src,
            "xllm_service_tpu/parallel/sharding.py": self.RULES,
        })

    def test_ppermute_literal_bad_axis_trips(self):
        src = (
            "import jax\n"
            "def ring(x, perm):\n"
            "    return jax.lax.ppermute(x, 'tp2', perm)\n"
        )
        fs = ShardingRulesPass().run(self._ring_proj(src))
        assert len(fs) == 1 and "'tp2'" in fs[0].message

    def test_ppermute_mesh_axes_clean(self):
        src = (
            "import jax\n"
            "def ring(x, perm):\n"
            "    x = jax.lax.ppermute(x, 'tp', perm)\n"
            "    x = jax.lax.ppermute(x, 'sp', perm)\n"
            "    return jax.lax.ppermute(x, axis_name='pp', perm=perm)\n"
        )
        assert ShardingRulesPass().run(self._ring_proj(src)) == []

    def test_ppermute_param_default_resolved(self):
        # The real call sites pass the axis through a parameter with a
        # string default (ring_attention's sp_axis="sp") — the pass must
        # see through that indirection.
        src = (
            "import jax\n"
            "def ring(x, perm, axis='tpp'):\n"
            "    return jax.lax.ppermute(x, axis, perm)\n"
        )
        fs = ShardingRulesPass().run(self._ring_proj(src))
        assert len(fs) == 1 and "'tpp'" in fs[0].message

    def test_ppermute_closure_default_resolved(self):
        # pipeline.py's shape: outer fn takes pp_axis="pp", the ppermute
        # sits in a nested local fn reading it from the closure.
        src = (
            "import jax\n"
            "def outer(x, perm, pp_axis='pp'):\n"
            "    def local(y):\n"
            "        return jax.lax.ppermute(y, pp_axis, perm)\n"
            "    return local(x)\n"
        )
        assert ShardingRulesPass().run(self._ring_proj(src)) == []

    def test_ppermute_dynamic_axis_skipped(self):
        # An axis the pass cannot resolve statically is skipped, never
        # guessed — no false positive on a plumbed-through variable.
        src = (
            "import jax\n"
            "def ring(x, perm, axis):\n"
            "    return jax.lax.ppermute(x, axis, perm)\n"
        )
        assert ShardingRulesPass().run(self._ring_proj(src)) == []

    def test_ppermute_local_assign_resolved(self):
        src = (
            "import jax\n"
            "def ring(x, perm):\n"
            "    ax = 'expert'\n"
            "    return jax.lax.ppermute(x, ax, perm)\n"
        )
        fs = ShardingRulesPass().run(self._ring_proj(src))
        assert len(fs) == 1 and "'expert'" in fs[0].message


# ---------------------------------------------------------------------------
# the real tree: repo-wide zero findings (tier-1 acceptance)
# ---------------------------------------------------------------------------


class TestRepoWide:
    def test_graftlint_all_exits_zero(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
             "--all"],
            capture_output=True, text=True, cwd=REPO, timeout=300,
        )
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert "OK" in r.stdout

    def test_graftlint_list_and_unknown_pass(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
             "--list"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r.returncode == 0 and "lock-discipline" in r.stdout
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
             "--pass", "nope"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r2.returncode == 2
