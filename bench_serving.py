#!/usr/bin/env python
"""Burst-trace serving benchmark (SURVEY.md §7 stage 8).

Replays a ShareGPT-class trace against an in-process cluster (master +
N instances over real sockets) and reports TTFT/TPOT/throughput
percentiles as ONE JSON line. Three trace sources:

  * --trace PATH: a REAL ShareGPT-format JSON (list of {"conversations":
    [{"from": "human", "value": ...}, {"from": "gpt", ...}, ...]});
    prompt text comes from the first human turn, the output budget from
    the first gpt reply's length.
  * default synthetic: lognormal token lengths FITTED to the published
    ShareGPT distribution (prompt median ~100 tokens / heavy tail,
    output median ~120 — the vLLM-paper trace shape), Poisson arrivals.
    Lengths clamp to the backend's max_seq_len.
  * --offline-frac F marks a fraction of requests `offline: true`,
    exercising hybrid scheduling (master parking + engine preemption)
    under the same burst.

Fault injection: --chaos-spec takes a seeded schedule (inline JSON or
@file) of events fired as the request stream passes index thresholds:

    {"seed": 7, "events": [
      {"at_frac": 0.3, "action": "kill", "instance": 1},
      {"at_frac": 0.2, "action": "flap", "instance": 0, "duration_s": 2},
      {"at_frac": 0.2, "action": "partition", "instance": 0,
       "duration_s": 2},
      {"at_frac": 0.1, "action": "slow", "instance": 0, "delay_ms": 50},
      {"at_frac": 0.5, "action": "master_kill"},
      {"at_frac": 0.5, "action": "master_partition", "duration_s": 3}]}

  * kill      — InstanceServer.crash(): heartbeats + HTTP drop, NO
                deregistration; live streams die mid-token and the
                master must resume them on survivors (token replay);
  * flap      — the instance's dispatch plane fails (common/faults.py
                drop rule on its address) while heartbeats continue: the
                health breaker must eject it without a retry storm;
  * partition — flap + dropped heartbeats (both directions of the link)
                for duration_s;
  * slow      — stretch the fake engine's per-token delay.
  * rolling_restart — the ops maneuver, fleet-wide: drain (graceful
                stop: deregister, live streams redispatch/resume onto
                survivors) -> grace_s dead -> rejoin a fresh instance
                under the same name, one instance at a time (step_s
                apart). Unlike `kill`, nothing is ungraceful, so the
                report's rolling_restart_guard demands ZERO dropped
                streams (exit 3 otherwise).

Control-plane chaos (docs/FAULT_TOLERANCE.md): any master_* event makes
the bench run a TWO-master replica set against one shared store, and the
driver resolves the current master from the store per attempt (retrying
a failed request against whichever replica holds the lease — the
client-retry contract the fenced front door redirects toward):

  * master_kill      — Master.kill() on the active replica: both HTTP
                       planes drop, the election keepalive stops WITHOUT
                       revoking the lease; the standby takes over at TTL
                       expiry, reconciles instance manifests, and serves;
  * master_partition — drop the active master's election.keepalive for
                       duration_s: it demotes + fences while alive (the
                       split-brain case); the standby takes over.

The report then carries takeover latency (lease-won -> reconciled, and
-> first dispatch), reconciled vs orphaned manifests, orphan reaps,
fenced-RPC rejections, and double_dispatches — completed streams whose
token count deviates from the trace's expectation, which MUST be 0.

The report carries redispatch/resume counts, resume-latency p99,
failed-after-retry, breaker ejections/probe recoveries, and the final
health states. --kill-at F remains as sugar for a one-kill spec. The
reference only PROMISES automatic rescheduling (README.md:46); here
recovery is measured, reproducibly.

Default backend is the fake engine (isolates the service tier);
--real-engine serves the actual JAX engine (llama3-tiny on CPU,
llama3-1b on TPU).

    python bench_serving.py --requests 512 --rate 64
    python bench_serving.py --requests 512 --rate 64 --kill-at 0.4
    python bench_serving.py --real-engine --requests 16 --rate 4
"""

from __future__ import annotations

import argparse
import json
import threading
import time

from xllm_service_tpu.runtime import compile_cache


def load_sharegpt(path: str, n: int, rng):
    """(prompt_text, out_tokens) pairs from a ShareGPT-format JSON."""
    with open(path) as f:
        data = json.load(f)
    pairs = []
    for conv in data:
        turns = conv.get("conversations") or []
        human = next((t["value"] for t in turns if t.get("from") == "human"), None)
        reply = next((t["value"] for t in turns if t.get("from") == "gpt"), None)
        if human and reply:
            pairs.append((human, max(len(reply) // 4, 4)))
    if not pairs:
        raise SystemExit(f"{path}: no usable conversations")
    idx = rng.integers(0, len(pairs), size=n)
    return [pairs[i] for i in idx]


def synthetic_sharegpt(n: int, rng, max_prompt: int, max_out: int,
                       word_mode: bool = False):
    """Lognormal fits to the public ShareGPT token statistics (heavy
    upper tail on both sides). word_mode (real tokenizers) emits n
    DISTINCT words — ~1+ BPE token each — instead of a repeated-char
    string a BPE tokenizer would collapse to a fraction of the intended
    length; the fake engine's byte tokenizer sees chars == tokens."""
    p_tok = rng.lognormal(mean=4.6, sigma=1.0, size=n)
    o_tok = rng.lognormal(mean=4.8, sigma=0.9, size=n)
    prompts = []
    for p in p_tok:
        ln = int(min(max(p, 4), max_prompt))
        if word_mode:
            # short numeric words tokenize to ~2 BPE tokens each; halve
            # the word count so the prompt lands near `ln` tokens. Salt
            # per request: identical prefixes would hand CAR routing a
            # near-100% shared-prefix artifact.
            salt = int(rng.integers(0, 100000))
            prompts.append(
                " ".join(
                    str((salt + i) % 9973)
                    for i in range(max(ln // 2, 2))
                )
            )
        else:
            prompts.append("w" * ln)
    outs = [int(min(max(o, 4), max_out)) for o in o_tok]
    return list(zip(prompts, outs))


def run_pd_bench(args) -> None:
    """PD handoff microbench (--pd): monolithic vs pipelined (streamed)
    KV handoff on one prefill+decode pair of REAL engines.

    Each phase replays the same multi-chunk prompt shape (distinct salts —
    the prefix cache must not collapse later requests to one chunk) and
    measures the handoff stall two ways:

      * server side: the prefill instance's `xllm_kv_handoff_stall_ms`
        samples (prefill-done -> decode-peer admission: master first-token
        ack + residual KV delivery), split by mode;
      * client side: the gap between the 1st streamed token (pushed at
        prefill-done) and the 2nd (the decode peer's first step) — the
        user-visible "prefill-done -> first decode step on the peer".

    Exits 3 when the streamed stall p50 is not <= the monolithic p50
    (the pipelined path must never lose to the one it replaces).
    """
    import http.client
    import os
    import sys

    try:
        mesh_sizes = [int(x) for x in args.mesh.split(",")]
        assert len(mesh_sizes) == 3 and all(s >= 1 for s in mesh_sizes)
    except (ValueError, AssertionError):
        raise SystemExit(
            f"--mesh must be dp,tp,ep integers, got {args.mesh!r}"
        )
    if mesh_sizes[0] * mesh_sizes[1] * mesh_sizes[2] > 1:
        # CPU mesh runs need that many virtual host devices, pinned
        # BEFORE the jax backend initializes (same trick as the tier-1
        # conftest / bench.py --mesh).
        from __graft_entry__ import _force_cpu_platform

        _force_cpu_platform(
            mesh_sizes[0] * mesh_sizes[1] * mesh_sizes[2]
        )

    import jax

    from xllm_service_tpu.api import Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    import numpy as np

    store = MemoryStore()
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=1.0, master_lease_ttl_s=5.0,
        load_balance_policy="RR", block_size=16,
    )
    master = Master(cfg, store=store)
    master.start()

    dp, tp, ep = mesh_sizes
    # tp>1 pairs stream per-shard block sets (parallel/shard_wire.py);
    # llama3-tiny's Hkv=2 serves tp<=2 — larger tp needs the shard-tiny
    # geometry (8 KV heads divide every tp in {2,4,8}).
    model = "llama3-tiny" if tp <= 2 else "llama3-shard-tiny"

    def engine_cfg(name, itype):
        return EngineConfig(
            model=model, dtype="float32", block_size=16,
            num_blocks=256, max_running_requests=4, max_seq_len=1024,
            max_prefill_tokens=args.pd_chunk_tokens,
            prefill_buckets=[64, 128, 256, 512, 1024],
            instance_name=name, instance_type=itype,
            dp_size=dp, tp_size=tp, ep_size=ep,
            enable_local_kv_transfer=False,  # measure the wire path
        )

    prefill = InstanceServer(
        engine_cfg("pd-pre", "PREFILL"), master_rpc_addr=master.rpc_address,
        heartbeat_interval_s=1.0,
    )
    decode = InstanceServer(
        engine_cfg("pd-dec", "DECODE"), master_rpc_addr=master.rpc_address,
        heartbeat_interval_s=1.0,
    )
    prefill.start()
    decode.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(master.scheduler.instance_mgr.counts()) == 2:
            break
        time.sleep(0.05)

    n_tok = max(args.pd_prompt_tokens, 64)
    host, _, port = master.http_address.partition(":")

    def one_request(salt: str):
        """Stream one completion; returns (text, first->second token gap s)."""
        prompt = salt + "x" * (n_tok - len(salt))
        conn = http.client.HTTPConnection(host, int(port), timeout=300.0)
        conn.request(
            "POST", "/v1/completions",
            body=json.dumps({
                "model": "llama3-tiny", "prompt": prompt,
                "max_tokens": args.pd_max_tokens, "temperature": 0.0,
                "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        stamps, text = [], []
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            try:
                ev = json.loads(payload)
            except ValueError:
                continue
            if ev.get("choices"):
                # One delta event per generations push — stamp them all
                # (a delta's text can be EMPTY while the incremental
                # detokenizer holds back a split multi-byte char).
                stamps.append(time.monotonic())
                for ch in ev["choices"]:
                    text.append(ch.get("text") or "")
        conn.close()
        gap = stamps[1] - stamps[0] if len(stamps) >= 2 else None
        return "".join(text), gap

    # Warm the compile caches off-measurement, once per mode: the two
    # modes exercise different import shapes on the decode peer (bulk
    # monolithic landing vs per-chunk + tail landings).
    os.environ["XLLM_PD_STREAMING"] = "1"
    one_request("warm1 ")
    os.environ["XLLM_PD_STREAMING"] = "0"
    one_request("warm0 ")

    # INTERLEAVE the modes request-by-request: a mono-then-streamed phase
    # split measures the second phase against a decode peer whose block
    # pool the first phase already filled (every chunk landing then pays
    # LRU evictions the first phase never saw) plus whatever the machine
    # drifted — alternation gives both modes the same cache pressure and
    # the same noise.
    stats = {
        m: {"stalls": [], "gaps": [], "chunks": 0, "aborts": 0,
            "degraded": 0, "streamed_blocks": 0, "total_blocks": 0}
        for m in ("mono", "streamed")
    }
    # Per-request stall, indexed by request (None when the handoff failed
    # and produced no sample) — the paired guard below must pair request
    # 2k with 2k+1 exactly, never realign across a gap.
    per_req_stall = []
    for i in range(2 * args.pd_requests):
        mode = "streamed" if i % 2 else "mono"
        os.environ["XLLM_PD_STREAMING"] = "1" if mode == "streamed" else "0"
        s = stats[mode]
        streamed0 = prefill._kv_stream_blocks_streamed
        total0 = prefill._kv_mig_blocks_total
        chunks0 = prefill._m_kv_stream_chunks.get()
        aborts0 = prefill._m_kv_stream_aborts.get()
        prefill._kv_stall_samples.clear()
        _, gap = one_request(f"{mode[0]}{i:05d} ")
        if gap is not None:
            s["gaps"].append(gap * 1000.0)
        # EVERY handoff counts — an aborted streaming session degrades to
        # a monolithic-tagged sample, and excluding it would hide exactly
        # the regressions the exit-3 guard exists to catch.
        samples = list(prefill._kv_stall_samples)
        per_req_stall.append(samples[0][1] if samples else None)
        s["stalls"].extend(ms for _, ms in samples)
        s["degraded"] += sum(1 for m, _ in samples if m != mode)
        s["chunks"] += int(prefill._m_kv_stream_chunks.get() - chunks0)
        s["aborts"] += int(prefill._m_kv_stream_aborts.get() - aborts0)
        s["streamed_blocks"] += (
            prefill._kv_stream_blocks_streamed - streamed0
        )
        s["total_blocks"] += prefill._kv_mig_blocks_total - total0
    os.environ.pop("XLLM_PD_STREAMING", None)

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 3) if xs else None

    def report(mode):
        s = stats[mode]
        return {
            "requests": args.pd_requests,
            "handoff_stall_p50_ms": pct(s["stalls"], 50),
            "handoff_stall_p99_ms": pct(s["stalls"], 99),
            "client_first_decode_gap_p50_ms": pct(s["gaps"], 50),
            "client_first_decode_gap_p99_ms": pct(s["gaps"], 99),
            "chunks": s["chunks"],
            "aborted_sessions": s["aborts"],
            "degraded_handoffs": s["degraded"],
            "overlap_frac": (
                round(s["streamed_blocks"] / s["total_blocks"], 4)
                if s["total_blocks"] else None
            ),
        }

    mono, streamed = report("mono"), report("streamed")

    # Guard: the pipelined path must not lose to the one it replaces, and
    # a multi-chunk prompt must actually overlap most of its migration.
    # The stall comparison is PAIRED — each alternated (mono, streamed)
    # request pair ran back-to-back under the same machine conditions, so
    # the median of per-pair differences cancels the load drift that
    # dwarfs a tiny-model payload's absolute win. (Byte-identity across
    # modes is pinned by tests/test_kv_stream.py; prompts here carry
    # distinct salts, so texts differ by design.)
    diffs = [
        s - m
        for m, s in zip(per_req_stall[0::2], per_req_stall[1::2])
        if m is not None and s is not None
    ]
    stall_delta = (
        round(float(np.percentile(diffs, 50)), 3) if diffs else None
    )
    guard_ok = True
    reasons = []
    if stall_delta is None or stall_delta > 0:
        guard_ok = False
        reasons.append(
            "paired streamed-minus-monolithic handoff stall median above 0"
        )
    if streamed["overlap_frac"] is None or streamed["overlap_frac"] <= 0.5:
        # None means streamed-mode handoffs recorded NO migration at all —
        # the pipeline being inert is the worst regression, not a pass.
        guard_ok = False
        reasons.append(
            "overlap fraction missing or <= 0.5 on a multi-chunk prompt"
        )

    kernel_dispatch = {}
    kv_wire_shards = 1
    for label, srv in (("prefill", prefill), ("decode", decode)):
        ex = getattr(srv.engine, "executor", None)
        if ex is None:
            continue
        if hasattr(ex, "kernel_report"):
            kernel_dispatch[label] = ex.kernel_report()
        if not ex.cfg.is_mla:
            kv_wire_shards = max(
                kv_wire_shards, ex.mesh.shape.get("tp", 1)
            )

    for srv in (prefill, decode):
        try:
            srv.stop()
        except Exception:
            pass
    master.stop()
    store.close()

    print(json.dumps({
        "metric": "pd_handoff",
        "backend": (
            "tpu" if jax.default_backend() == "tpu" else "cpu-real"
        ),
        "prompt_tokens": n_tok,
        "chunk_tokens": args.pd_chunk_tokens,
        # Shard-aware columns (docs/SHARDING.md): the per-instance mesh,
        # the RESOLVED per-shard kernel dispatch of the pair, and how
        # many per-shard block sets each handoff frame carried — rounds
        # compare across mesh shapes on these.
        "mesh": {"dp": dp, "tp": tp, "ep": ep},
        "kernel_dispatch": kernel_dispatch,
        "kv_wire_shards": kv_wire_shards,
        "monolithic": mono,
        "streamed": streamed,
        "paired_stall_delta_p50_ms": stall_delta,
        "pd_stream_guard": "ok" if guard_ok else "; ".join(reasons),
    }))
    if not guard_ok:
        sys.exit(3)


def _pd_adapt_guard(line: str) -> "tuple[str, int]":
    """Exit-3 guard for the --pd-adapt goodput A/B/C row (ISSUE 16).

    Adaptive placement exists to beat BOTH static deployments on a mixed
    trace — losing to either means the controller routed against its own
    goodput model. FAILs (rc 3) when adaptive goodput lands below
    XLLM_BENCH_PD_ADAPT_MIN_RATIO (default 1.0) of the best static
    baseline, or when the adaptive phase never produced an actionable
    decision (an inert controller stamping "ok" would be vacuous — the
    run_pd_bench inert-pipeline precedent). Abstains LOUDLY when no mode
    met its SLO at all (the host is too noisy for the --adapt-slo-*
    constants to mean anything) or when the goodput numbers are
    unparseable; passes through non-JSON lines and rows without all
    three modes untouched. XLLM_BENCH_NO_REGRESSION_GUARD disarms it.
    """
    import os

    if os.environ.get("XLLM_BENCH_NO_REGRESSION_GUARD"):
        return line, 0
    try:
        res = json.loads(line)
    except ValueError:
        return line, 0
    g = res.get("goodput") or {}
    if not isinstance(g, dict) or not all(
        k in g for k in ("adaptive", "static_pd", "all_mix")
    ):
        return line, 0
    try:
        a = float(g["adaptive"]["goodput_tok_s"])
        s = float(g["static_pd"]["goodput_tok_s"])
        m = float(g["all_mix"]["goodput_tok_s"])
    except (KeyError, TypeError, ValueError):
        # Still loud: a harness refactor that loses goodput_tok_s must
        # not make the guard silently vanish (the _moe_guard precedent).
        res["pd_adapt_guard"] = "abstained: unparseable goodput_tok_s"
        return json.dumps(res), 0
    if int(g["adaptive"].get("acted") or 0) <= 0:
        res["pd_adapt_guard"] = (
            "FAIL: the adaptive phase produced 0 actionable decisions — "
            "controller off (XLLM_GOODPUT_CONTROLLER=0?) or its inputs "
            "never warmed; an inert controller must not pass its own A/B"
        )
        return json.dumps(res), 3
    if a <= 0.0 and s <= 0.0 and m <= 0.0:
        res["pd_adapt_guard"] = (
            "abstained: no mode met its SLO at all — host too noisy for "
            "the --adapt-slo-* constants (rerun or raise them)"
        )
        return json.dumps(res), 0
    try:
        ratio = float(
            os.environ.get("XLLM_BENCH_PD_ADAPT_MIN_RATIO", "") or 1.0
        )
    except ValueError:
        ratio = 1.0
    best = max(s, m)
    if a >= ratio * best:
        res["pd_adapt_guard"] = "ok"
        return json.dumps(res), 0
    res["pd_adapt_guard"] = (
        f"FAIL: adaptive goodput {a:.1f} tok/s is below "
        f"{100.0 * ratio:.0f}% of the best static baseline {best:.1f} "
        f"(static_pd={s:.1f}, all_mix={m:.1f}) — per-request placement "
        f"lost to a static deployment on the swing trace"
    )
    return json.dumps(res), 3


def run_pd_adapt_bench(args) -> None:
    """Goodput-controller A/B/C (--pd-adapt): adaptive per-request
    colocate-vs-disaggregate placement vs BOTH static deployments, on
    one swing trace against one fleet in one process (ISSUE 16,
    docs/PD_DISAGGREGATION.md "Goodput controller").

    The trace interleaves two tenants with OPPOSITE optimal placements:

      * bench-batch — long prompt (256 tok), 2-token decode: the KV
        handoff stall (--adapt-stall-ms) buys almost no interference-free
        decode time, so colocation wins;
      * bench-chat  — short prompt (48 tok), 48-token decode: every
        colocated decode step overlapping a batch prefill pays the
        interference factor, so disaggregation wins.

    The fleet is --instances (>= 4) declared-MIX fakes with the
    colocation physics the stock FakeEngine lacks: per-token decode
    delay inflates by --adapt-interference per concurrent prefill on
    the same engine, a prefill occupies the engine for 1 ms/token, and
    a disaggregated import pays --adapt-stall-ms of simulated KV wire
    time INSIDE the real handoff path — so the prefill side's stall
    clock times it and `kv_stall_ms_ewma` heartbeats carry it to the
    controller. The engine also reports its prefill duty cycle as queue
    pressure (waiting_requests_num) — the signal a real engine's
    admission queue shows while prefills own the hot loop, which the
    fake's thread-per-request generation otherwise hides.

    One warmup pass trains the per-tenant decode-length EWMAs and the
    stall estimate (cold-EWMA decisions degrade to static = the PD
    pair), then three measured phases replay the same paced trace:
    static_pd (XLLM_GOODPUT_FORCE=disaggregate — the classic PD split),
    all_mix (=colocate — monolithic MIX serving), and adaptive (the
    controller decides per request). Each measured phase opens with an
    unmeasured batch-only lead-in that re-arms steady-state prefill
    duty (and lets heartbeats carry it) before the first measured
    decision — the A/B/C compares steady-state placement policies, not
    cold-start transients. Goodput = SLO-met tokens/s: prompt
    + generated tokens of requests finishing under their tenant's
    --adapt-slo-*-ms end-to-end budget, over the phase's wall time.
    Fleet reshaping is pinned off for the whole run
    (XLLM_GOODPUT_MIN_FLIP_INTERVAL_S=1e9): the A/B/C isolates the
    per-request half of the controller; the flip plane is tier-1's
    tests/test_goodput.py. Exits 3 via _pd_adapt_guard when adaptive
    loses to either static baseline or never acts.
    """
    import collections
    import http.client
    import os
    import sys

    from xllm_service_tpu.api import FakeEngine, Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    class InterferingFakeEngine(FakeEngine):
        """FakeEngine + the three colocation-physics terms the goodput
        model trades off (see run_pd_adapt_bench docstring)."""

        PREFILL_MS_PER_TOK = 1.0
        # waiting_requests_num = weight x prefill duty cycle: the queue
        # pressure a real engine reports while prefills own the hot loop.
        WAITING_WEIGHT = 30.0
        DUTY_WINDOW_S = 1.0

        def __init__(self, *, interference, handoff_stall_ms, **kw):
            # Set before super().__init__: its token_delay_s assignment
            # goes through the property setter below.
            self._base_delay = 0.0
            self._prefilling = 0
            self._pf_active = {}
            self._pf_done = collections.deque(maxlen=128)
            self._imu = threading.Lock()
            self.interference = interference
            self.handoff_stall_ms = handoff_stall_ms
            super().__init__(**kw)

        # Read once per emitted token: interference applies to exactly
        # the decode steps that overlap a prefill on this engine.
        @property
        def token_delay_s(self):
            return self._base_delay * (
                1.0 + self.interference * self._prefilling
            )

        @token_delay_s.setter
        def token_delay_s(self, v):
            self._base_delay = v

        def _prefill_sleep(self, n_tokens):
            key = object()
            t0 = time.monotonic()
            with self._imu:
                self._prefilling += 1
                self._pf_active[key] = t0
            try:
                time.sleep(self.PREFILL_MS_PER_TOK * n_tokens / 1000.0)
            finally:
                with self._imu:
                    self._prefilling -= 1
                    del self._pf_active[key]
                    self._pf_done.append((t0, time.monotonic()))

        def _prefill_duty(self):
            now = time.monotonic()
            lo = now - self.DUTY_WINDOW_S
            with self._imu:
                busy = sum(
                    min(t1, now) - max(t0, lo)
                    for t0, t1 in self._pf_done
                    if t1 > lo
                )
                busy += sum(
                    now - max(t0, lo) for t0 in self._pf_active.values()
                )
            return busy / self.DUTY_WINDOW_S

        def _run(self, req, skip_first=False):
            if not skip_first:
                # Colocated/monolithic: the prompt's prefill occupies
                # this engine before its own decode starts. A handed-off
                # import (skip_first) already paid prefill on the peer.
                self._prefill_sleep(len(req.prompt_token_ids))
            super()._run(req, skip_first=skip_first)

        def _run_prefill_only(self, req):
            self._prefill_sleep(len(req.prompt_token_ids))
            super()._run_prefill_only(req)

        def import_sequence(self, req, handoff):
            # Simulated KV wire time, paid BEFORE admission so the
            # sender's real stall clock (instance_kv commit path) times
            # it and heartbeats carry it to the controller.
            time.sleep(self.handoff_stall_ms / 1000.0)
            super().import_sequence(req, handoff)

        def get_load_metrics(self):
            lm = super().get_load_metrics()
            lm.waiting_requests_num = int(
                round(self.WAITING_WEIGHT * self._prefill_duty())
            )
            return lm

        def profiling_data(self):
            # Publish the UNCONTENDED curves: the controller models load
            # through the waiting/stall signals; a TPOT point sampled
            # mid-prefill would double-count interference.
            ttft = [
                (n, self.ttft_ms + self.PREFILL_MS_PER_TOK * n)
                for n in (64, 256, 1024, 4096)
            ]
            tpot = [
                (b, t, self._base_delay * 1000.0 + 0.1 * b)
                for b in (1, 8, 32)
                for t in (256, 4096)
            ]
            return ttft, tpot

    saved_env = {
        k: os.environ.get(k)
        for k in ("XLLM_GOODPUT_FORCE", "XLLM_GOODPUT_MIN_FLIP_INTERVAL_S")
    }
    # Pin reshaping off: mid-phase census changes would give the three
    # modes different fleets (the flip plane has its own tier-1 proof).
    os.environ["XLLM_GOODPUT_MIN_FLIP_INTERVAL_S"] = "1e9"

    store = MemoryStore()
    # 0.5s heartbeats: the duty/stall signals must reach the controller
    # well inside a phase (measured phases last ~2-3s).
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.5, master_lease_ttl_s=5.0,
        load_balance_policy="RR", block_size=16,
    )
    master = Master(cfg, store=store)
    master.start()

    n_inst = max(args.instances, 4)
    names = [f"adapt{i}" for i in range(n_inst)]
    servers = []
    for name in names:
        ecfg = EngineConfig(
            model="fake-echo", instance_name=name,
            instance_type="MIX", block_size=16,
        )
        srv = InstanceServer(
            ecfg, master_rpc_addr=master.rpc_address,
            heartbeat_interval_s=0.5,
            engine=InterferingFakeEngine(
                interference=args.adapt_interference,
                handoff_stall_ms=args.adapt_stall_ms,
                token_delay_s=args.adapt_token_delay_ms / 1000.0,
                ttft_ms=1.0,
            ),
        )
        srv.start()
        servers.append(srv)

    mgr = master.scheduler.instance_mgr
    ctrl = master.scheduler.goodput
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        preds = [mgr.get_time_predictor(n) for n in names]
        if sum(mgr.counts()) == n_inst and all(
            p is not None and p.has_ttft_model and p.has_tpot_model
            for p in preds
        ):
            break
        time.sleep(0.05)

    host, _, port = master.http_address.partition(":")
    slo_ms = {
        "bench-batch": args.adapt_slo_batch_ms,
        "bench-chat": args.adapt_slo_chat_ms,
    }
    tenants = {
        "bench-batch": {"prompt_tokens": 256, "max_tokens": 2},
        # prompt_tokens >= max_tokens: the fake echoes the reversed
        # prompt, so the decode length is capped by the prompt length.
        "bench-chat": {"prompt_tokens": 48, "max_tokens": 48},
    }

    def build_trace(tag, n):
        """n paced requests, 3:2 batch:chat, interleaved (the swing is
        request-to-request, so every phase sees the same mix). Distinct
        salts: the byte tokenizer makes chars == tokens."""
        out = []
        for i in range(n):
            tenant = "bench-batch" if i % 5 in (0, 2, 4) else "bench-chat"
            shape = tenants[tenant]
            salt = f"{tag}{i:04d} "
            prompt = salt + "x" * max(shape["prompt_tokens"] - len(salt), 1)
            out.append((tenant, prompt, shape["max_tokens"]))
        return out

    def run_phase(label, force, n, lead=12):
        if force:
            os.environ["XLLM_GOODPUT_FORCE"] = force
        else:
            os.environ.pop("XLLM_GOODPUT_FORCE", None)
        results = []
        res_mu = threading.Lock()

        def one(tenant, prompt, max_toks, record=True):
            t0 = time.monotonic()
            toks, ok = 0, False
            try:
                conn = http.client.HTTPConnection(
                    host, int(port), timeout=60.0
                )
                conn.request(
                    "POST", "/v1/completions",
                    body=json.dumps({
                        "model": tenant, "prompt": prompt,
                        "max_tokens": max_toks, "temperature": 0.0,
                        "stream": True,
                    }).encode(),
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                if resp.status == 200:
                    for raw in resp:
                        ln = raw.decode().strip()
                        if not ln.startswith("data: "):
                            continue
                        payload = ln[len("data: "):]
                        if payload == "[DONE]":
                            ok = True
                            break
                        try:
                            ev = json.loads(payload)
                        except ValueError:
                            continue
                        if ev.get("choices"):
                            toks += 1
                conn.close()
            except Exception:
                ok = False
            e2e_ms = (time.monotonic() - t0) * 1000.0
            if record:
                with res_mu:
                    results.append((tenant, len(prompt), toks, e2e_ms, ok))

        threads = []
        # Unmeasured batch-only lead-in: saturates the duty window and
        # gives heartbeats (0.5s) time to carry it, so the first
        # measured decision already sees steady-state prefill pressure.
        bshape = tenants["bench-batch"]
        for i in range(lead):
            salt = f"ld{label[:2]}{i:04d} "
            prompt = salt + "x" * max(bshape["prompt_tokens"] - len(salt), 1)
            th = threading.Thread(
                target=one,
                args=("bench-batch", prompt, bshape["max_tokens"], False),
                daemon=True,
            )
            th.start()
            threads.append(th)
            time.sleep(0.1)
        d0 = dict(ctrl.decisions)
        t_start = time.monotonic()
        for i, (tenant, prompt, max_toks) in enumerate(
            build_trace(label[:2], n)
        ):
            target = t_start + i * args.adapt_gap_ms / 1000.0
            now = time.monotonic()
            if target > now:
                time.sleep(target - now)
            th = threading.Thread(
                target=one, args=(tenant, prompt, max_toks), daemon=True
            )
            th.start()
            threads.append(th)
        # All measured requests are scheduled (decisions happen on HTTP
        # receipt); snapshot the delta BEFORE the drain pump below adds
        # its own unmeasured decisions.
        time.sleep(0.05)
        dd = {
            k: ctrl.decisions.get(k, 0) - d0.get(k, 0)
            for k in ("colocate", "disaggregate", "static")
        }

        # Drain pump: arrivals stopped but long decodes are still in
        # flight — keep the steady-state prefill pressure up (same
        # unmeasured batch load as the lead-in) so a phase's tail isn't
        # an artificially interference-free free ride.
        stop = threading.Event()
        bg_threads = []

        def drain_pump():
            i = 0
            while not stop.is_set():
                salt = f"dp{label[:2]}{i:04d} "
                prompt = salt + "x" * max(
                    bshape["prompt_tokens"] - len(salt), 1
                )
                th = threading.Thread(
                    target=one,
                    args=(
                        "bench-batch", prompt, bshape["max_tokens"], False
                    ),
                    daemon=True,
                )
                th.start()
                bg_threads.append(th)
                i += 1
                stop.wait(0.1)

        pump_th = None
        if lead:
            pump_th = threading.Thread(target=drain_pump, daemon=True)
            pump_th.start()
        for th in threads:
            th.join(timeout=120)
        dur = time.monotonic() - t_start
        stop.set()
        if pump_th is not None:
            pump_th.join(timeout=5)
        for th in bg_threads:
            th.join(timeout=30)
        met_tokens = total_tokens = met_n = failed = 0
        per_tenant = {
            t: {"requests": 0, "slo_met": 0, "e2e_ms": []} for t in slo_ms
        }
        for tenant, ptoks, toks, e2e_ms, ok in results:
            pt = per_tenant[tenant]
            pt["requests"] += 1
            pt["e2e_ms"].append(e2e_ms)
            total_tokens += ptoks + toks
            if not ok or toks <= 0:
                failed += 1
                continue
            if e2e_ms <= slo_ms[tenant]:
                pt["slo_met"] += 1
                met_n += 1
                met_tokens += ptoks + toks
        for pt in per_tenant.values():
            xs = sorted(pt.pop("e2e_ms"))
            pt["e2e_p50_ms"] = (
                round(xs[len(xs) // 2], 1) if xs else None
            )
        return {
            "duration_s": round(dur, 3),
            "requests": len(results),
            "failed": failed,
            "slo_met": met_n,
            "met_tokens": met_tokens,
            "total_tokens": total_tokens,
            "goodput_tok_s": (
                round(met_tokens / dur, 1) if dur > 0 else 0.0
            ),
            "throughput_tok_s": (
                round(total_tokens / dur, 1) if dur > 0 else 0.0
            ),
            "decisions": dd,
            "acted": dd["colocate"] + dd["disaggregate"],
            "per_tenant": per_tenant,
        }

    # Warmup: trains the tenant EWMAs (cold decisions degrade to static
    # = the PD pair, which also seeds the stall samples + prefill duty)
    # and the predictors' first heartbeat upload. Unmeasured.
    run_phase("warmup", None, 12, lead=0)
    reports = {}
    for label, force in (
        ("static_pd", "disaggregate"),
        ("all_mix", "colocate"),
        ("adaptive", None),
    ):
        time.sleep(0.25)  # settle: heartbeats carry the last phase's tail
        reports[label] = run_phase(label, force, args.adapt_requests)
    os.environ.pop("XLLM_GOODPUT_FORCE", None)

    row = {
        "metric": "pd_adapt",
        "backend": "fake",
        "instances": n_inst,
        "requests_per_phase": args.adapt_requests,
        "gap_ms": args.adapt_gap_ms,
        "stall_ms": args.adapt_stall_ms,
        "interference": args.adapt_interference,
        "token_delay_ms": args.adapt_token_delay_ms,
        "slo_ms": slo_ms,
        "tenants": tenants,
        "role_census": mgr.role_census(),
        "wanted_census": ctrl.wanted_census(),
        "reshape_flips": ctrl.reshape_flips,
        "goodput": reports,
    }

    for srv in servers:
        try:
            srv.stop()
        except Exception:
            pass
    master.stop()
    store.close()
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    line, rc = _pd_adapt_guard(json.dumps(row))
    print(line)
    if rc:
        sys.exit(rc)


def _trace_tails_guard(line: str) -> "tuple[str, int]":
    """Exit-3 guard for the --trace-tails blame-attribution row (ISSUE
    17). The bench injects a known bottleneck (a KV wire stall on every
    --tails-stall-every'th handoff) and then asks the tracing plane to
    find it: the per-stage blame summed across the pulled p99-tail
    traces must be dominated by the injected stage, and every tail's
    assembled timeline must span master + prefill + decode (>= 3
    processes) — a collector that lost a participant would still print
    plausible numbers. Abstains LOUDLY when the row is unparseable;
    passes through non-JSON lines untouched.
    XLLM_BENCH_NO_REGRESSION_GUARD disarms it.
    """
    import os

    if os.environ.get("XLLM_BENCH_NO_REGRESSION_GUARD"):
        return line, 0
    try:
        res = json.loads(line)
    except ValueError:
        return line, 0
    if res.get("metric") != "trace_tails":
        return line, 0
    tails = res.get("tails")
    injected = res.get("injected")
    if not tails or not injected:
        res["trace_tails_guard"] = (
            "FAIL: no tail traces were assembled — the collector or the "
            "participant index lost the p99 requests"
        )
        return json.dumps(res), 3
    reasons = []
    sums = {}
    for t in tails:
        blame = t.get("blame_ms") or {}
        for k, v in blame.items():
            if k != "total":
                sums[k] = sums.get(k, 0.0) + float(v)
        if len(t.get("processes") or []) < 3:
            reasons.append(
                f"tail {t.get('srid')} spans "
                f"{len(t.get('processes') or [])} processes (< 3): a "
                f"participant's spans dropped out of the assembly"
            )
    if not sums:
        reasons.append("tail traces carry no blame_ms edges")
    else:
        dominant = max(sums, key=lambda k: sums[k])
        res["dominant"] = dominant
        if dominant != injected:
            reasons.append(
                f"dominant blamed stage is {dominant!r} "
                f"({round(sums[dominant], 1)} ms summed) but the bench "
                f"injected the bottleneck into {injected!r} "
                f"({round(sums.get(injected, 0.0), 1)} ms) — blame "
                f"attribution points at the wrong stage"
            )
    if reasons:
        res["trace_tails_guard"] = "FAIL: " + "; ".join(reasons)
        return json.dumps(res), 3
    res["trace_tails_guard"] = "ok"
    return json.dumps(res), 0


def run_trace_tails_bench(args) -> None:
    """p99 blame attribution (--trace-tails): stream a burst against a
    PD pair, auto-pull the master's assembled distributed traces for the
    p99-tail requests, and print a per-stage blame table — queue vs
    prefill vs handoff vs decode vs host_gap (ISSUE 17,
    docs/OBSERVABILITY.md "Distributed tracing").

    The stack is one master + one PREFILL + one DECODE fake instance in
    one process (three distinct span rings, so an assembled trace spans
    three processes exactly like a real fleet). The decode side pays
    --tails-stall-ms of simulated KV wire time on every
    --tails-stall-every'th admission, INSIDE the real import path —
    after the prefill side's handoff_send span, before the decode side's
    decode_admit span — so the stall lands in the blame table's handoff
    edge and in the sender's commit stall clock, not in a bench-side
    fudge factor. Every request streams its completion over SSE; the
    service_request_id is captured from the events' "id" field (the
    same id a production client would quote in a latency report).

    The tail set is the slowest ~5% by end-to-end latency. For each
    tail the bench GETs /trace/<srid> from the master — the collector
    pulls each participant's ring, shifts spans by the heartbeat-derived
    clock offsets, and returns blame_stages() over the merged timeline.
    The guard (exit 3 via _trace_tails_guard) checks the tracing plane
    actually FOUND the planted bottleneck: the dominant blamed stage
    summed across tails must be "handoff", and every tail's timeline
    must span >= 3 processes. A median request's blame row is printed
    alongside for contrast (its handoff edge should be wire-thin).
    """
    import http.client
    import os
    import sys

    from xllm_service_tpu.api import FakeEngine, Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    class StallingDecodeServer(InstanceServer):
        """Decode InstanceServer that pays the simulated KV wire stall
        inside the real admission path (see run_trace_tails_bench
        docstring): the InterferingFakeEngine precedent moved one layer
        up, because import_sequence runs AFTER the decode_admit span and
        a sleep there would be blamed to decode, not handoff."""

        def __init__(self, *a, stall_ms=0.0, stall_every=1, **kw):
            self._tails_stall_ms = float(stall_ms)
            self._tails_stall_every = max(int(stall_every), 1)
            self._tails_imports = 0
            self._tails_mu = threading.Lock()
            super().__init__(*a, **kw)

        def _admit_import(self, handoff, header):
            with self._tails_mu:
                self._tails_imports += 1
                n = self._tails_imports
            if n % self._tails_stall_every == 0:
                time.sleep(self._tails_stall_ms / 1000.0)
            return super()._admit_import(handoff, header)

    saved_trace = os.environ.get("XLLM_TRACE")
    os.environ["XLLM_TRACE"] = "1"  # the bench IS the tracing plane

    store = MemoryStore()
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=0.5, master_lease_ttl_s=5.0,
        load_balance_policy="RR", block_size=16,
    )
    master = Master(cfg, store=store)
    master.start()

    token_delay_s = args.tails_token_delay_ms / 1000.0
    pf = InstanceServer(
        EngineConfig(
            model="fake-echo", instance_name="tails-prefill",
            instance_type="PREFILL", block_size=16,
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.5,
        engine=FakeEngine(token_delay_s=token_delay_s, ttft_ms=1.0),
    )
    dec = StallingDecodeServer(
        EngineConfig(
            model="fake-echo", instance_name="tails-decode",
            instance_type="DECODE", block_size=16,
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.5,
        engine=FakeEngine(token_delay_s=token_delay_s, ttft_ms=1.0),
        stall_ms=args.tails_stall_ms, stall_every=args.tails_stall_every,
    )
    pf.start()
    dec.start()

    mgr = master.scheduler.instance_mgr
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and sum(mgr.counts()) < 2:
        time.sleep(0.05)

    host, _, port = master.http_address.partition(":")
    results = []  # (srid, e2e_ms, tokens, ok)
    for i in range(args.tails_requests):
        salt = f"tt{i:04d} "
        prompt = salt + "x" * max(48 - len(salt), 1)
        srid, toks, ok = "", 0, False
        t0 = time.monotonic()
        try:
            conn = http.client.HTTPConnection(host, int(port), timeout=60.0)
            conn.request(
                "POST", "/v1/completions",
                body=json.dumps({
                    "model": "fake-echo", "prompt": prompt,
                    "max_tokens": args.tails_max_tokens,
                    "temperature": 0.0, "stream": True,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            if resp.status == 200:
                for raw in resp:
                    ln = raw.decode().strip()
                    if not ln.startswith("data: "):
                        continue
                    payload = ln[len("data: "):]
                    if payload == "[DONE]":
                        ok = True
                        break
                    try:
                        ev = json.loads(payload)
                    except ValueError:
                        continue
                    # The event id IS the service_request_id — the same
                    # handle /trace/<srid> keys the assembled timeline on.
                    srid = srid or str(ev.get("id") or "")
                    if ev.get("choices"):
                        toks += 1
            conn.close()
        except Exception:
            ok = False
        results.append((srid, (time.monotonic() - t0) * 1000.0, toks, ok))

    def pull_trace(srid):
        try:
            conn = http.client.HTTPConnection(host, int(port), timeout=10.0)
            conn.request("GET", f"/trace/{srid}")
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            if resp.status != 200:
                return None
            return json.loads(body)
        except Exception:
            return None

    done = sorted(
        (r for r in results if r[3] and r[0]),
        key=lambda r: r[1], reverse=True,
    )
    n_tails = max(1, int(round(len(done) * 0.05)))
    stages = ("queue", "prefill", "handoff", "decode", "host_gap")
    tails = []
    for srid, e2e_ms, _toks, _ok in done[:n_tails]:
        tr = pull_trace(srid)
        if tr is None:
            continue
        blame = tr.get("blame_ms") or {}
        edge = {k: blame.get(k) for k in stages if blame.get(k) is not None}
        tails.append({
            "srid": srid,
            "e2e_ms": round(e2e_ms, 1),
            "processes": tr.get("processes") or [],
            "blame_ms": blame,
            "top_stage": max(edge, key=lambda k: edge[k]) if edge else None,
        })
    median_blame = None
    if done:
        med = done[len(done) // 2]
        med_tr = pull_trace(med[0])
        if med_tr is not None:
            median_blame = med_tr.get("blame_ms")

    hdr = f"{'srid':<22}{'e2e_ms':>9}" + "".join(
        f"{s:>10}" for s in stages + ("total",)
    )
    print(hdr)
    print("-" * len(hdr))
    for t in tails:
        b = t["blame_ms"]
        print(
            f"{t['srid'][:21]:<22}{t['e2e_ms']:>9.1f}" + "".join(
                f"{float(b.get(s) or 0.0):>10.1f}"
                for s in stages + ("total",)
            )
        )
    if median_blame:
        print(
            f"{'(median)':<22}{done[len(done) // 2][1]:>9.1f}" + "".join(
                f"{float(median_blame.get(s) or 0.0):>10.1f}"
                for s in stages + ("total",)
            )
        )

    row = {
        "metric": "trace_tails",
        "backend": "fake",
        "requests": len(results),
        "failed": sum(1 for r in results if not r[3]),
        "stall_ms": args.tails_stall_ms,
        "stall_every": args.tails_stall_every,
        "token_delay_ms": args.tails_token_delay_ms,
        "injected": "handoff",
        "tails": tails,
        "median_blame_ms": median_blame,
    }

    for srv in (pf, dec):
        try:
            srv.stop()
        except Exception:
            pass
    master.stop()
    store.close()
    if saved_trace is None:
        os.environ.pop("XLLM_TRACE", None)
    else:
        os.environ["XLLM_TRACE"] = saved_trace

    line, rc = _trace_tails_guard(json.dumps(row))
    print(line)
    if rc:
        sys.exit(rc)


def run_prefix_trace_bench(args) -> None:
    """Fleet prefix-fabric bench (--prefix-trace): a Zipf-ish shared-
    system-prompt workload replayed at high stream concurrency against
    REAL engines, fabric-on vs fabric-off on the SAME trace with a fresh
    stack per phase (docs/KV_CACHE.md).

    Each request draws one of --prefix-sessions session prompts (Zipf
    popularity, exponent --prefix-zipf) of --prefix-blocks full blocks,
    plus a distinct tail — the millions-of-users shape where most traffic
    shares system prompts. All --prefix-streams requests run CONCURRENTLY
    (streaming, client-side TTFT). Reported per phase: fleet prefix hit
    rate (engine counters), fabric fetch/adopt/abort/dedup counters,
    fetched-vs-recomputed block fractions, and TTFT p50/p99.

    Exits 3 when fabric-on is worse than fabric-off on the paired trace:
    a lower fleet hit rate, a materially worse p99 TTFT, or an inert
    fetch plane (0 blocks fetched on a workload built to need it).
    """
    import http.client
    import os
    import sys

    import numpy as np

    from xllm_service_tpu.api import Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    import jax

    on_tpu = jax.default_backend() == "tpu"
    model = "llama3-1b" if on_tpu else "llama3-tiny"
    bs = 128 if on_tpu else 16
    n_sessions = max(args.prefix_sessions, 1)
    n_streams = max(args.prefix_streams, 1)

    # The trace, built ONCE and replayed in both phases: session draw by
    # Zipf rank probability, session prefix of --prefix-blocks full
    # blocks, distinct ~1.5-block tail per request.
    rng = np.random.default_rng(args.seed)
    ranks = np.arange(1, n_sessions + 1, dtype=np.float64)
    pzipf = ranks ** (-float(args.prefix_zipf))
    pzipf /= pzipf.sum()
    sess_of = rng.choice(n_sessions, size=n_streams, p=pzipf)
    prefix_tok = args.prefix_blocks * bs

    def build_prompt(i: int) -> str:
        s = int(sess_of[i])
        # Distinct leading char per session makes block 0 diverge, so
        # sessions never share blocks with each other — only within.
        head = chr(65 + s % 26) + ("%02d" % s)
        prefix = (head + "x" * prefix_tok)[:prefix_tok]
        tail = f"|{i:05d}|" + "y" * (bs + bs // 2 - 8)
        return prefix + tail

    prompts = [build_prompt(i) for i in range(n_streams)]
    max_new = max(args.prefix_max_tokens, 1)

    def build_stack():
        store = MemoryStore()
        cfg = ServiceConfig(
            host="127.0.0.1", http_port=0, rpc_port=0,
            heartbeat_interval_s=0.25, master_lease_ttl_s=5.0,
            load_balance_policy="CAR", block_size=bs,
        )
        master = Master(cfg, store=store)
        master.start()
        instances = []
        for i in range(args.instances):
            ecfg = EngineConfig(
                model=model, dtype="float32" if not on_tpu else "bfloat16",
                block_size=bs,
                num_blocks=2048 if on_tpu else 512,
                max_running_requests=32 if on_tpu else 8,
                max_seq_len=2048 if on_tpu else 512,
                max_prefill_tokens=4 * bs,  # multi-chunk: fetch overlaps
                prefill_buckets=(
                    [256, 512, 1024, 2048] if on_tpu
                    else [64, 128, 256, 512]
                ),
                instance_name=f"pfx{i}", instance_type="DEFAULT",
                enable_local_kv_transfer=False,  # measure the wire path
                compilation_cache_dir=compile_cache.DEFAULT_DIR,
            )
            srv = InstanceServer(
                ecfg, master_rpc_addr=master.rpc_address,
                heartbeat_interval_s=0.25,
            )
            srv.start()
            instances.append(srv)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if sum(master.scheduler.instance_mgr.counts()) == args.instances:
                break
            time.sleep(0.05)
        return master, instances, store

    def teardown(master, instances, store):
        for srv in instances:
            try:
                srv.stop()
            except Exception:
                pass
        master.stop()
        store.close()

    def one_stream(addr: str, prompt: str, out: dict):
        t0 = time.monotonic()
        try:
            host, _, port = addr.partition(":")
            conn = http.client.HTTPConnection(host, int(port), timeout=600.0)
            conn.request(
                "POST", "/v1/completions",
                body=json.dumps({
                    "model": model, "prompt": prompt,
                    "max_tokens": max_new, "temperature": 0.0,
                    "stream": True,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                out["err"] = f"HTTP {resp.status}"
                return
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    out["done"] = True
                    break
                if "ttft" not in out and '"text"' in payload:
                    out["ttft"] = time.monotonic() - t0
            conn.close()
        except Exception as e:  # noqa: BLE001
            out["err"] = repr(e)

    def inst_counter(instances, name):
        total = 0
        for srv in instances:
            m = srv.metrics.get(name)
            if m is not None:
                total += int(m.get())
        return total

    def run_phase(fabric_on: bool):
        os.environ["XLLM_PREFIX_FABRIC"] = "1" if fabric_on else "0"
        master, instances, store = build_stack()
        try:
            # Warm the per-shape compiles off-measurement, driven DIRECTLY
            # at each instance's own address — through the master, CAR
            # affinity/tie-breaking would funnel every warm request onto
            # one instance and leave the others to compile mid-phase.
            for srv in instances:
                w = {}
                one_stream(srv.address, "warm" + "w" * (2 * bs), w)
            # Seed wave: one request per session, sequential, then two
            # heartbeats — the steady-state shape where session prefixes
            # already live SOMEWHERE in the fleet and the master's index
            # knows it. Without this, a cold all-at-once burst gives the
            # fabric nothing to route or fetch against (and gives
            # fabric-off the identical cold start, hiding nothing).
            for s in range(n_sessions):
                w = {}
                one_stream(
                    master.http_address, build_prompt(
                        int(np.argmax(sess_of == s))
                        if (sess_of == s).any() else 0
                    ), w,
                )
            time.sleep(0.6)
            results = [dict() for _ in range(n_streams)]
            threads = [
                threading.Thread(
                    target=one_stream,
                    args=(master.http_address, prompts[i], results[i]),
                )
                for i in range(n_streams)
            ]
            # Paced arrivals (args.rate mean arrivals/s, exponential
            # gaps): service time far exceeds the arrival span, so
            # concurrency still climbs to ~all streams while the master's
            # heartbeat-lagged index/load view gets the temporal
            # structure live traffic has.
            arr_rng = np.random.default_rng(args.seed + 1)
            gaps = arr_rng.exponential(1.0 / max(args.rate, 1e-3),
                                       size=n_streams)
            t0 = time.monotonic()
            for t, g in zip(threads, gaps):
                time.sleep(float(g))
                t.start()
            for t in threads:
                t.join(timeout=900.0)
            wall = time.monotonic() - t0
            ttfts = [r["ttft"] for r in results if "ttft" in r]
            errors = [r["err"] for r in results if "err" in r]
            failed = sum(1 for r in results if not r.get("done"))
            cached = sum(
                srv.engine.prefix_cached_tokens for srv in instances
            )
            prompted = sum(
                srv.engine.prefix_prompt_tokens for srv in instances
            )
            total_blocks = prompted // bs
            fetched = inst_counter(
                instances, "xllm_fabric_fetch_blocks_total"
            )
            import numpy as _np

            def pct(q):
                return (
                    round(float(_np.percentile(ttfts, q)) * 1000, 2)
                    if ttfts else None
                )

            return {
                "fabric": "on" if fabric_on else "off",
                "streams": n_streams,
                "errors": len(errors),
                "failed_requests": failed,
                "wall_s": round(wall, 2),
                "fleet_prefix_hit_rate": (
                    round(cached / prompted, 4) if prompted else None
                ),
                "fetched_block_frac": (
                    round(fetched / total_blocks, 4) if total_blocks else None
                ),
                "recomputed_block_frac": (
                    round((prompted - cached) / bs / total_blocks, 4)
                    if total_blocks else None
                ),
                "fabric_fetches": inst_counter(
                    instances, "xllm_fabric_fetches_total"
                ),
                "fabric_fetch_blocks": fetched,
                "fabric_fetch_aborts": inst_counter(
                    instances, "xllm_fabric_fetch_aborts_total"
                ),
                "fabric_dedup_waits": inst_counter(
                    instances, "xllm_fabric_dedup_waits_total"
                ),
                "midprefill_adopted_blocks": sum(
                    getattr(srv.engine, "midprefill_adopted_blocks", 0)
                    for srv in instances
                ),
                "ttft_p50_ms": pct(50),
                "ttft_p99_ms": pct(99),
                "error_sample": errors[0][:160] if errors else None,
            }
        finally:
            teardown(master, instances, store)
            os.environ.pop("XLLM_PREFIX_FABRIC", None)

    # Mirrored ABBA phase order (off,on,on,off), aggregated per mode: a
    # single off-vs-on shot is dominated by run-to-run drift (512 client
    # threads + engines share one GIL), and ordering bias favors whoever
    # runs second on a warm machine. Min-of-rounds for latency (standard
    # noise rejection), mean for hit rate, sums for counters.
    rounds = {False: [], True: []}
    for fab in (False, True, True, False):
        rounds[fab].append(run_phase(fab))

    def agg(rs):
        out = dict(rs[0])
        out["rounds"] = len(rs)
        for k in ("errors", "failed_requests", "fabric_fetches",
                  "fabric_fetch_blocks", "fabric_fetch_aborts",
                  "fabric_dedup_waits", "midprefill_adopted_blocks"):
            out[k] = sum(r[k] for r in rs)
        for k in ("fleet_prefix_hit_rate", "fetched_block_frac",
                  "recomputed_block_frac"):
            vals = [r[k] for r in rs if r[k] is not None]
            out[k] = round(sum(vals) / len(vals), 4) if vals else None
        for k in ("ttft_p50_ms", "ttft_p99_ms", "wall_s"):
            vals = [r[k] for r in rs if r[k] is not None]
            out[k] = min(vals) if vals else None
        out["ttft_p99_ms_per_round"] = [r["ttft_p99_ms"] for r in rs]
        return out

    off, on = agg(rounds[False]), agg(rounds[True])

    guard_ok = True
    reasons = []
    if on["failed_requests"] or off["failed_requests"]:
        guard_ok = False
        reasons.append("failed requests under the prefix trace")
    hit_on, hit_off = on["fleet_prefix_hit_rate"], off["fleet_prefix_hit_rate"]
    if hit_on is None or hit_off is None or hit_on < hit_off - 0.01:
        guard_ok = False
        reasons.append("fabric-on fleet prefix hit rate below fabric-off")
    if not on["fabric_fetch_blocks"]:
        # An inert fetch plane on a workload built to need it is the
        # regression this guard exists to catch.
        guard_ok = False
        reasons.append("fabric-on fetched 0 blocks (fetch plane inert)")
    if (
        on["ttft_p99_ms"] is not None
        and off["ttft_p99_ms"] is not None
        and on["ttft_p99_ms"] > off["ttft_p99_ms"] * 1.5
    ):
        # Backstop against pathological regressions (e.g. a fetch that
        # blocks admission), NOT a perf bar: at CPU-toy scale the fetch's
        # fixed overheads (engine-thread export on the holder, landing on
        # the requester) rival the near-free recompute they replace, and
        # single-GIL-process phase noise runs tens of percent. The
        # structural signals are the hit-rate / inert-fetch / failed-
        # request guards above; real-model KV makes recompute 3-4 orders
        # costlier per block while the fetch overhead barely grows.
        guard_ok = False
        reasons.append("fabric-on TTFT p99 pathologically above fabric-off")

    print(json.dumps({
        "metric": "prefix_fabric_trace",
        "backend": "tpu" if on_tpu else "cpu-real",
        "sessions": n_sessions,
        "zipf": args.prefix_zipf,
        "prefix_blocks": args.prefix_blocks,
        "instances": args.instances,
        "fabric_off": off,
        "fabric_on": on,
        "prefix_fabric_guard": "ok" if guard_ok else "; ".join(reasons),
    }))
    if not guard_ok:
        sys.exit(3)


def run_mm_trace_bench(args) -> None:
    """Encoder-fabric bench (--mm-trace): a multi-turn re-sent-media
    chat trace against REAL towers + a real LM engine (docs/EPD.md).

    --mm-sessions concurrent conversations each carry ONE image; every
    conversation re-sends its image on each of --mm-turns turns (the
    multi-turn chat shape where the same attachment rides every request).
    Turn 1 is a cold burst — same-kind items from different requests
    coalesce in the encoder micro-batcher; later turns are embedding-
    cache hits that skip the towers entirely.

    Reported: embedding cache hit rate, mean encoder batch occupancy,
    stage-E-overlap fraction (share of the embedding wait hidden behind
    an already-admitted text prefill), per-turn wall times, failed
    requests. Exit 3 when the fabric is inert on a workload built for
    it: 0 cache hits on the re-sent turns, mean occupancy <= 1 on the
    burst, any failed request, or no streamed sessions at all.
    """
    import sys

    import numpy as np

    from xllm_service_tpu.api import Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    n_sessions = max(args.mm_sessions, 2)
    n_turns = max(args.mm_turns, 2)
    n_encoders = max(args.mm_encoders, 1)

    store = MemoryStore(clock=lambda: 0.0)
    master = Master(
        ServiceConfig(
            host="127.0.0.1", http_port=0, rpc_port=0,
            heartbeat_interval_s=0.25, master_lease_ttl_s=5.0,
            load_balance_policy="RR", block_size=16,
            mm_tokens_per_media=4,  # == vit-tiny out_tokens
        ),
        store=store,
    )
    master.start()
    lm = InstanceServer(
        EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=16,
            num_blocks=256, max_running_requests=16, max_seq_len=256,
            prefill_buckets=[64, 128], instance_name="mm-lm",
            instance_type="MIX",
            compilation_cache_dir=compile_cache.DEFAULT_DIR,
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.25,
    )
    lm.start()
    encoders = []
    for i in range(n_encoders):
        enc = InstanceServer(
            EngineConfig(
                model="vit-tiny", instance_name=f"mm-enc{i}",
                instance_type="ENCODE",
                # A wider admission window makes burst coalescing
                # deterministic at bench scale.
                encoder_batch_window_ms=25.0,
            ),
            master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.25,
        )
        enc.start()
        encoders.append(enc)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        c = master.scheduler.instance_mgr.counts()
        if c[2] == n_encoders and sum(c) == 1 + n_encoders:
            break
        time.sleep(0.05)

    rng = np.random.default_rng(args.seed)
    imgs = [
        rng.random((32, 32, 3)).astype(np.float32)
        for _ in range(n_sessions)
    ]

    import base64 as _b64
    import http.client

    def one_request(img, out: dict):
        t0 = time.monotonic()
        url = (
            "data:application/x-raw-f32;shape=32x32x3;base64,"
            + _b64.b64encode(np.ascontiguousarray(img).tobytes()).decode()
        )
        try:
            host, _, port = master.http_address.partition(":")
            conn = http.client.HTTPConnection(host, int(port), timeout=300.0)
            conn.request(
                "POST", "/v1/chat/completions",
                body=json.dumps({
                    "model": "llama3-tiny",
                    "messages": [{
                        "role": "user",
                        "content": [
                            {"type": "text", "text": "describe "},
                            {"type": "image_url", "image_url": {"url": url}},
                        ],
                    }],
                    "max_tokens": 4,
                    "temperature": 0.0,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                out["err"] = f"HTTP {resp.status}: {body[:120]!r}"
            else:
                out["latency_s"] = time.monotonic() - t0
                out["text"] = json.loads(body)["choices"][0]["message"][
                    "content"
                ]
            conn.close()
        except Exception as e:  # noqa: BLE001
            out["err"] = repr(e)

    # Warm the compiles off-measurement (one request pays the LM + tower
    # jit; the trace then measures serving, not compilation).
    warm = {}
    one_request(imgs[0], warm)
    for e in encoders:
        e.engine.emb_cache.hits = 0
        e.engine.emb_cache.misses = 0

    def enc_counter(name):
        # Batcher/cache series live on the ENGINE registry, session
        # series on the instance front-door registry — check both.
        total = 0
        for e in encoders:
            m = e.engine.metrics.get(name) or e.metrics.get(name)
            if m is not None:
                total += int(m.get())
        return total

    occ0_items = enc_counter("xllm_encoder_batched_items_total")
    occ0_batches = enc_counter("xllm_encoder_batches_total")

    turns = []
    results_all = []
    texts_by_session = [[] for _ in range(n_sessions)]
    for turn in range(n_turns):
        results = [dict() for _ in range(n_sessions)]
        threads = [
            threading.Thread(target=one_request, args=(imgs[i], results[i]))
            for i in range(n_sessions)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        wall = time.monotonic() - t0
        for i, r in enumerate(results):
            if "text" in r:
                texts_by_session[i].append(r["text"])
        lat = [r["latency_s"] for r in results if "latency_s" in r]
        turns.append({
            "turn": turn,
            "wall_s": round(wall, 3),
            "mean_latency_ms": (
                round(1000 * sum(lat) / len(lat), 1) if lat else None
            ),
        })
        results_all.extend(results)

    failed = sum(1 for r in results_all if "text" not in r)
    errors = [r["err"] for r in results_all if "err" in r]
    # A conversation's re-sent image must never change its answer.
    divergent = sum(
        1 for ts in texts_by_session if len(set(ts)) > 1
    )
    hits = sum(e.engine.emb_cache.hits for e in encoders)
    misses = sum(e.engine.emb_cache.misses for e in encoders)
    batches = enc_counter("xllm_encoder_batches_total") - occ0_batches
    batched_items = (
        enc_counter("xllm_encoder_batched_items_total") - occ0_items
    )
    occupancy = batched_items / batches if batches else 0.0
    sessions_streamed = enc_counter("xllm_mm_stream_sessions_total")
    aborts = enc_counter("xllm_mm_stream_aborts_total")
    overlap = float(
        lm.metrics.get("xllm_mm_stream_overlap_frac").get()
    )
    fleet_hit_rate = (
        master.scheduler.encoder_fabric.fleet_hit_items
        / max(master.scheduler.encoder_fabric.fleet_total_items, 1)
    )

    for e in encoders:
        e.stop()
    lm.stop()
    master.stop()
    store.close()

    guard_ok = True
    reasons = []
    if failed or divergent:
        guard_ok = False
        reasons.append(
            f"{failed} failed / {divergent} divergent requests on the "
            "mm trace"
        )
    if hits <= 0:
        guard_ok = False
        reasons.append("0 embedding-cache hits on a re-sent-media trace")
    if occupancy <= 1.0:
        guard_ok = False
        reasons.append(
            f"mean encoder batch occupancy {occupancy:.2f} <= 1 "
            "(cross-request batching inert)"
        )
    if sessions_streamed <= 0:
        guard_ok = False
        reasons.append("no streamed encoder->prefill sessions opened")

    print(json.dumps({
        "metric": "encoder_fabric_mm_trace",
        "sessions": n_sessions,
        "turns": n_turns,
        "encoders": n_encoders,
        "failed_requests": failed,
        "divergent_conversations": divergent,
        "embed_cache_hits": int(hits),
        "embed_cache_misses": int(misses),
        "embed_cache_hit_rate": round(hits / max(hits + misses, 1), 4),
        "router_fleet_embed_hit_rate": round(fleet_hit_rate, 4),
        "encoder_batches": int(batches),
        "encoder_batched_items": int(batched_items),
        "mean_batch_occupancy": round(occupancy, 2),
        "streamed_sessions": int(sessions_streamed),
        "stream_aborts": int(aborts),
        "stage_e_overlap_frac": round(overlap, 4),
        "per_turn": turns,
        "error_sample": errors[0][:160] if errors else None,
        "mm_trace_guard": "ok" if guard_ok else "; ".join(reasons),
    }))
    if not guard_ok:
        sys.exit(3)


def main() -> None:
    p = argparse.ArgumentParser("xllm-service-tpu burst bench")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--rate", type=float, default=32.0, help="mean arrivals/s")
    p.add_argument("--instances", type=int, default=2)
    p.add_argument("--real-engine", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--policy", default="RR", choices=["RR", "CAR", "SLO_AWARE"])
    p.add_argument("--trace", default="", help="ShareGPT-format JSON path")
    p.add_argument("--offline-frac", type=float, default=0.0)
    p.add_argument(
        "--kill-at", type=float, default=0.0,
        help="crash one instance after this fraction of requests "
        "dispatched (sugar for a one-kill --chaos-spec)",
    )
    p.add_argument(
        "--chaos-spec", default="",
        help="seeded fault schedule, inline JSON or @file (see module "
        "docstring): kill / flap / partition / slow events at request-"
        "fraction thresholds",
    )
    p.add_argument(
        "--shared-prefix", type=int, default=0,
        help="prefix-heavy trace: every prompt starts with a shared "
        "~N-token system prompt (the CAR-vs-RR workload, VERDICT r4 #5); "
        "real-engine runs report the fleet prefix-cache hit rate",
    )
    p.add_argument(
        "--prefix-sessions", type=int, default=1,
        help="number of DISTINCT shared prefixes (request i uses prefix "
        "i %% N). One session converges to all-hits under any policy "
        "(every instance caches the single prefix after one miss); many "
        "sessions discriminate: RR re-prefills each prefix once PER "
        "INSTANCE, cache-aware routing follows the blocks",
    )
    p.add_argument(
        "--token-delay-ms", type=float, default=2.0,
        help="fake-engine per-token delay; above target_tpot_ms (50) it "
        "drives SLO_AWARE decode-pressure flips",
    )
    p.add_argument(
        "--heartbeat-s", type=float, default=1.0,
        help="instance heartbeat interval: load metrics AND the global "
        "KV index are exactly this stale at the master — cache-aware "
        "routing follows blocks it can only see after a heartbeat",
    )
    p.add_argument(
        "--prefix-trace", action="store_true",
        help="prefix-fabric bench: Zipf shared-system-prompt trace at "
        "--prefix-streams concurrent streams on real engines, fabric-on "
        "vs fabric-off with a fresh stack per phase; reports fleet prefix "
        "hit rate, fetched-vs-recomputed block fractions, and TTFT "
        "p50/p99; exits 3 when fabric-on is worse (docs/KV_CACHE.md)",
    )
    p.add_argument(
        "--prefix-streams", type=int, default=512,
        help="--prefix-trace: concurrent client streams per phase",
    )
    p.add_argument(
        "--prefix-zipf", type=float, default=1.1,
        help="--prefix-trace: Zipf exponent of the session draw",
    )
    p.add_argument(
        "--prefix-blocks", type=int, default=8,
        help="--prefix-trace: shared session prefix length in KV blocks",
    )
    p.add_argument(
        "--prefix-max-tokens", type=int, default=2,
        help="--prefix-trace: generated tokens per request",
    )
    p.add_argument(
        "--mm-trace", action="store_true",
        help="encoder-fabric bench: multi-turn re-sent-media chat trace "
        "reporting encoder batch occupancy, embedding cache hit rate, "
        "and stage-E-overlap fraction (exit 3 when the fabric is inert)",
    )
    p.add_argument(
        "--mm-sessions", type=int, default=8,
        help="--mm-trace: concurrent conversations (one image each)",
    )
    p.add_argument(
        "--mm-turns", type=int, default=3,
        help="--mm-trace: turns per conversation (each re-sends its image)",
    )
    p.add_argument(
        "--mm-encoders", type=int, default=2,
        help="--mm-trace: ENCODE instances in the stack",
    )
    p.add_argument(
        "--pd", action="store_true",
        help="PD handoff microbench: monolithic vs pipelined (streamed) "
        "KV handoff on a real-engine prefill+decode pair; reports "
        "handoff-stall p50/p99 and overlap fraction per mode; exits 3 "
        "when the streamed stall is not <= monolithic "
        "(docs/PD_DISAGGREGATION.md)",
    )
    p.add_argument(
        "--pd-requests", type=int, default=6,
        help="--pd: measured requests per phase",
    )
    p.add_argument(
        "--pd-adapt", action="store_true",
        help="goodput-controller A/B/C: adaptive per-request colocate-"
        "vs-disaggregate placement vs static-PD (force=disaggregate) "
        "and all-MIX (force=colocate) on one two-tenant swing trace "
        "over a declared-MIX fake fleet with colocation physics; "
        "reports SLO-met tokens/s per mode; exits 3 when adaptive "
        "loses to either static baseline (docs/PD_DISAGGREGATION.md)",
    )
    p.add_argument(
        "--adapt-requests", type=int, default=40,
        help="--pd-adapt: requests per measured phase (3:2 batch:chat)",
    )
    p.add_argument(
        "--adapt-gap-ms", type=float, default=50.0,
        help="--pd-adapt: open-loop arrival gap between requests",
    )
    p.add_argument(
        "--adapt-stall-ms", type=float, default=400.0,
        help="--pd-adapt: simulated KV wire time per disaggregated "
        "handoff (paid inside the real handoff path, so the stall "
        "telemetry the controller consumes measures it)",
    )
    p.add_argument(
        "--adapt-interference", type=float, default=6.0,
        help="--pd-adapt: per-concurrent-prefill decode slowdown factor "
        "on a colocated engine",
    )
    p.add_argument(
        "--adapt-token-delay-ms", type=float, default=10.0,
        help="--pd-adapt: uncontended per-token decode delay",
    )
    p.add_argument(
        "--adapt-slo-batch-ms", type=float, default=550.0,
        help="--pd-adapt: e2e SLO for the long-prompt/short-decode "
        "tenant (misses under static-PD: the stall buys nothing)",
    )
    p.add_argument(
        "--adapt-slo-chat-ms", type=float, default=1300.0,
        help="--pd-adapt: e2e SLO for the short-prompt/long-decode "
        "tenant (misses under all-MIX: prefill interference)",
    )
    p.add_argument(
        "--trace-tails", action="store_true",
        help="p99 blame attribution: stream a burst against a PD fake "
        "pair with a KV wire stall injected on every Nth handoff, "
        "auto-pull the master's assembled distributed traces for the "
        "p99-tail requests, and print a per-stage blame table (queue / "
        "prefill / handoff / decode / host_gap); exits 3 when the "
        "dominant blamed stage is not the injected bottleneck "
        "(docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--tails-requests", type=int, default=40,
        help="--trace-tails: sequential streamed requests",
    )
    p.add_argument(
        "--tails-stall-ms", type=float, default=250.0,
        help="--trace-tails: simulated KV wire stall paid inside the "
        "decode admission path (between handoff_send and decode_admit, "
        "so the blame table's handoff edge times it)",
    )
    p.add_argument(
        "--tails-stall-every", type=int, default=8,
        help="--trace-tails: stall every Nth handoff — the stalled "
        "requests ARE the p99 tail the bench must find",
    )
    p.add_argument(
        "--tails-max-tokens", type=int, default=8,
        help="--trace-tails: generated tokens per request",
    )
    p.add_argument(
        "--tails-token-delay-ms", type=float, default=2.0,
        help="--trace-tails: fake-engine per-token decode delay",
    )
    p.add_argument(
        "--pd-prompt-tokens", type=int, default=960,
        help="--pd: prompt length (tokens == chars on the test tokenizer)",
    )
    p.add_argument(
        "--pd-chunk-tokens", type=int, default=64,
        help="--pd: engine max_prefill_tokens (chunks per prompt = "
        "prompt/chunk)",
    )
    p.add_argument(
        "--pd-max-tokens", type=int, default=4,
        help="--pd: generated tokens per request",
    )
    p.add_argument(
        "--mesh", default="1,1,1", metavar="DP,TP,EP",
        help="--pd: engine mesh per instance (docs/SHARDING.md) — a "
        "tp>1 pair streams PER-SHARD KV block sets over the handoff "
        "wire and the rows gain mesh + resolved kernel-dispatch "
        "columns; the CPU harness runs it on the virtual host mesh",
    )
    p.add_argument(
        "--instance-type", default="MIX",
        choices=["MIX", "DEFAULT", "PREFILL", "DECODE"],
        help="MIX fleets split one decode + rest prefill (the reference "
        "placement rule, instance_mgr.cpp:110-127), leaving a SINGLE "
        "prefill candidate at --instances 2 — every policy then routes "
        "identically. Use DEFAULT (colocated, all prefill candidates) "
        "for RR-vs-CAR comparisons",
    )
    args = p.parse_args()

    import os

    if (
        not args.real_engine and not args.pd and not args.prefix_trace
        and not args.mm_trace
    ):
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.trace_tails:
        run_trace_tails_bench(args)
        return
    if args.pd_adapt:
        run_pd_adapt_bench(args)
        return
    if args.pd:
        run_pd_bench(args)
        return
    if args.prefix_trace:
        run_prefix_trace_bench(args)
        return
    if args.mm_trace:
        run_mm_trace_bench(args)
        return

    import numpy as np

    from xllm_service_tpu.api import FakeEngine, Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import EngineConfig, ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    rng = np.random.default_rng(args.seed)

    # Chaos schedule (common/faults.py) — parsed ONCE, up front: the
    # master topology below depends on whether control-plane events are
    # scheduled.
    chaos = {"seed": args.seed, "events": []}
    if args.chaos_spec:
        raw = args.chaos_spec
        if raw.startswith("@"):
            with open(raw[1:]) as f:
                chaos = json.load(f)
        else:
            chaos = json.loads(raw)
    if args.kill_at > 0:
        chaos.setdefault("events", []).append(
            {"at_frac": args.kill_at, "action": "kill", "instance": -1}
        )
    chaos_events = list(chaos.get("events", []))
    master_chaos = any(
        str(e.get("action", "")).startswith("master_")
        for e in chaos_events
    )

    store = MemoryStore()
    cfg = ServiceConfig(
        host="127.0.0.1", http_port=0, rpc_port=0,
        heartbeat_interval_s=args.heartbeat_s, master_lease_ttl_s=3.0,
        load_balance_policy=args.policy, block_size=16,
        detect_disconnected_instance_interval_s=2.0,
        reconcile_orphan_ttl_s=5.0,
    )
    master = Master(cfg, store=store)
    master.start()
    masters = [master]
    if master_chaos:
        # Control-plane chaos needs a standby to take over; spin it up
        # front (same store, own ephemeral ports) so the takeover is a
        # pure election + reconcile, not a process boot.
        standby = Master(cfg, store=store)
        standby.start()
        masters.append(standby)

    on_tpu = False
    if args.real_engine:
        import jax

        on_tpu = jax.default_backend() == "tpu"
    model = "llama3-1b" if on_tpu else "llama3-tiny"

    def make_instance(i):
        """Build (NOT start) instance i — also the rolling-restart rebuild
        path, which re-creates a drained instance under the same name."""
        if args.real_engine:
            ecfg = EngineConfig(
                model=model, block_size=128 if on_tpu else 16,
                num_blocks=512 if on_tpu else 128,
                max_running_requests=32 if on_tpu else 8,
                max_seq_len=2048 if on_tpu else 256,
                prefill_buckets=(
                    [256, 512, 1024, 2048] if on_tpu else [64, 128, 256]
                ),
                instance_name=f"bench{i}",
                instance_type=args.instance_type,
                # persistent jit cache: repeat runs skip the compiles
                compilation_cache_dir=compile_cache.DEFAULT_DIR,
            )
            return InstanceServer(
                ecfg, master_rpc_addr=master.rpc_address,
                heartbeat_interval_s=args.heartbeat_s,
            )
        ecfg = EngineConfig(
            model="fake-echo", instance_name=f"bench{i}",
            instance_type=args.instance_type, block_size=16,
        )
        return InstanceServer(
            ecfg, master_rpc_addr=master.rpc_address,
            heartbeat_interval_s=args.heartbeat_s,
            engine=FakeEngine(
                token_delay_s=args.token_delay_ms / 1000.0,
                ttft_ms=10.0,
            ),
        )

    instances = []
    for i in range(args.instances):
        srv = make_instance(i)
        srv.start()
        instances.append(srv)

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(master.scheduler.instance_mgr.counts()) == args.instances:
            break
        time.sleep(0.05)

    # Trace.
    if args.real_engine and not on_tpu:
        max_prompt, max_out = 180, 48  # tiny-model max_seq_len budget
    elif args.real_engine:
        max_prompt, max_out = 1500, 256
    else:
        max_prompt, max_out = 1024, 512
    if args.trace:
        pairs = load_sharegpt(args.trace, args.requests, rng)
        pairs = [
            (t[:max_prompt], min(o, max_out)) for t, o in pairs
        ]
    else:
        pairs = synthetic_sharegpt(
            args.requests, rng, max_prompt, max_out,
            word_mode=args.real_engine,
        )
    if args.shared_prefix > 0:
        # Prefix-heavy rewrite: each request draws one of N session
        # system prompts (~N tokens of numeric words) + a short distinct
        # tail. CacheAwareRouting should route a session's repeats onto
        # the instance already holding its prefix blocks; RR alternates
        # and re-prefills every prefix on every instance.
        n_sess = max(args.prefix_sessions, 1)
        sys_prompts = [
            " ".join(
                str(7000 + 101 * s + i)
                for i in range(max(args.shared_prefix // 2, 2))
            )
            for s in range(n_sess)
        ]
        tail_budget = max(max_prompt - args.shared_prefix, 16)
        # Random session draw — a deterministic i % N assignment would
        # CORRELATE with round-robin dispatch (session i%N always lands
        # on instance i%2), silently pinning sessions under RR too.
        sess_of = rng.integers(0, n_sess, size=len(pairs))
        pairs = [
            (
                sys_prompts[int(sess_of[i])] + " " + " ".join(
                    str((911 * i + j) % 9973)
                    for j in range(max(min(tail_budget, 32) // 2, 2))
                ),
                o,
            )
            for i, (_, o) in enumerate(pairs)
        ]
    offline_mask = rng.random(args.requests) < args.offline_frac
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)

    # ---- chaos plan installation (events parsed above) ---------------- #
    from xllm_service_tpu.common import faults

    if chaos_events:
        if any(
            e.get("action") in ("kill", "rolling_restart")
            for e in chaos_events
        ) and len(instances) < 2:
            raise SystemExit(
                "kill/rolling_restart events need --instances >= 2 "
                "(someone must survive)"
            )
        plan = faults.install_plan(
            faults.FaultPlan(seed=int(chaos.get("seed", args.seed)))
        )
    killed_at = []

    def _expiring_rules(rules, duration_s):
        for r in rules:
            plan.add_rule(r)
        if duration_s and duration_s > 0:
            t = threading.Timer(
                duration_s, lambda: [plan.remove_rule(r) for r in rules]
            )
            t.daemon = True
            t.start()

    def _active_master():
        for m in masters:
            if not m._killed and m.scheduler.is_master:
                return m
        for m in masters:
            if not m._killed:
                return m
        return masters[0]

    master_kills = []
    rolling_log = []
    rolling_threads = []

    def _rolling_restart(ev, t_start):
        """Fleet-wide rolling restart: DRAIN (graceful stop: deregister ->
        the master redispatches pre-token / token-replay-resumes
        mid-stream work onto survivors), wait a grace period (the process
        is dead), then REJOIN a fresh InstanceServer under the same name
        — for every instance in sequence. The ops-maneuver counterpart of
        `kill`: nothing here is ungraceful, so the guard is ZERO dropped
        streams, not merely recovered ones."""
        grace_s = float(ev.get("grace_s", 0.5))
        step_s = float(ev.get("step_s", grace_s + 1.0))
        for i in range(len(instances)):
            old = instances[i]
            t_drain = time.monotonic() - t_start
            try:
                old.stop()
            except Exception:
                pass
            time.sleep(grace_s)
            srv = make_instance(i)
            srv.start()
            instances[i] = srv
            rolling_log.append({
                "instance": srv.name,
                "drained_at_s": round(t_drain, 3),
                "rejoined_at_s": round(time.monotonic() - t_start, 3),
            })
            # Let the rejoin register before the next drain so capacity
            # never dips by more than one instance.
            deadline = time.monotonic() + 10.0
            mgr = _active_master().scheduler.instance_mgr
            while time.monotonic() < deadline:
                if any(
                    m.name == srv.name for m in mgr.list_instances()
                ):
                    break
                time.sleep(0.05)
            rest = step_s - grace_s
            if rest > 0:
                time.sleep(rest)

    def fire_chaos(ev, t_start):
        action = ev.get("action")
        if action == "rolling_restart":
            th = threading.Thread(
                target=_rolling_restart, args=(ev, t_start), daemon=True,
            )
            th.start()
            rolling_threads.append(th)
            return
        if action == "master_kill":
            # Ungraceful: planes drop, keepalive stops, lease LINGERS
            # until TTL — the standby takes over only when the store's
            # liveness fires, then reconciles instance manifests.
            m = _active_master()
            m.kill()
            master_kills.append(
                {"master": m.http_address,
                 "at_s": round(time.monotonic() - t_start, 3)}
            )
            return
        if action == "master_partition":
            # The split-brain case: the active master's keepalive HANGS
            # (a partitioned etcd link times out, it doesn't fail fast),
            # so its lease expires and the standby is elected WHILE this
            # replica still believes it is master and keeps dispatching.
            # Those stale-epoch dispatches are exactly what instances
            # must fence (412) once the successor's reconcile raises
            # their epoch — the run's fenced_rpcs counter proves it.
            m = _active_master()
            _expiring_rules(
                [faults.FaultRule(
                    point="election.keepalive",
                    match=m.scheduler.election_identity,
                    action="delay",
                    delay_ms=float(ev.get("delay_ms", 6000.0)),
                )],
                ev.get("duration_s"),
            )
            return
        idx = ev.get("instance", -1) % len(instances)
        srv = instances[idx]
        if action == "kill":
            srv.crash()
            killed_at.append(
                {"instance": srv.name,
                 "at_s": round(time.monotonic() - t_start, 3)}
            )
        elif action == "flap":
            # dispatch plane dark, heartbeats alive: the breaker's job
            _expiring_rules(
                [faults.FaultRule(
                    point="post_json.send", match=srv.address,
                    action="drop",
                )],
                ev.get("duration_s"),
            )
        elif action == "partition":
            # both directions of the master<->instance link
            _expiring_rules(
                [
                    faults.FaultRule(
                        point="post_json.send", match=srv.address,
                        action="drop",
                    ),
                    faults.FaultRule(
                        point="heartbeat.send", match=srv.name,
                        action="partition",
                    ),
                ],
                ev.get("duration_s"),
            )
        elif action == "slow":
            if hasattr(srv.engine, "token_delay_s"):
                srv.engine.token_delay_s = ev.get("delay_ms", 50) / 1000.0
        else:
            raise SystemExit(f"unknown chaos action {action!r}")

    pending_events = sorted(
        (
            (min(int(float(e.get("at_frac", 0.0)) * args.requests),
                 args.requests - 1), e)
            for e in chaos_events
        ),
        key=lambda p: p[0],
    )

    ttfts, tpots, lats, errors = [], [], [], []
    off_ttfts, on_ttfts = [], []
    first_tokens = [0]
    retried_to_new_master = [0]
    double_dispatches = [0]
    unrecovered = [0]
    mu = threading.Lock()

    from xllm_service_tpu.coordination import MASTER_KEY

    def _master_addr() -> str:
        """The client-retry contract: resolve whichever replica holds the
        master lease NOW (the election identity IS its client address);
        the fenced front door 307s toward the same value."""
        try:
            cur = store.get(MASTER_KEY)
        except Exception:
            cur = None
        return cur or _active_master().http_address

    def drive(i: int):
        import http.client

        t0 = time.monotonic()
        # Fake-echo expectation: one delta event per token, reversal
        # capped by max_tokens — the double-dispatch detector below.
        expect_tok = min(len(pairs[i][0]), int(pairs[i][1]))
        # Retries must outlive the takeover window: lease TTL (3 s) +
        # election + reconcile before the standby serves.
        max_attempts = 6 if master_chaos else 1
        for attempt in range(max_attempts):
            if attempt:
                with mu:
                    retried_to_new_master[0] += 1
                time.sleep(1.0)  # takeover window; addr re-resolves below
            addr = _master_addr() if master_chaos else master.http_address
            n_tok = 0
            t_first = t_last = None
            deltas = []
            stream_err = ""
            done = False
            try:
                host, _, port = addr.partition(":")
                conn = http.client.HTTPConnection(
                    host, int(port), timeout=300.0
                )
                body = {
                    "model": model if args.real_engine else "fake-echo",
                    "prompt": pairs[i][0],
                    "max_tokens": int(pairs[i][1]),
                    "temperature": 0.0,
                    "stream": True,
                }
                if offline_mask[i]:
                    body["offline"] = True
                conn.request(
                    "POST", "/v1/completions",
                    body=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                if resp.status != 200:
                    # 307 = standby's redirect, 503 = no master yet —
                    # both retry against the re-resolved address.
                    raise RuntimeError(
                        f"HTTP {resp.status}: {resp.read()[:120]!r}"
                    )
                for raw in resp:
                    line = raw.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    payload = line[len("data: "):]
                    if payload == "[DONE]":
                        done = True
                        break
                    try:
                        ev = json.loads(payload)
                    except ValueError:
                        ev = {}
                    if isinstance(ev, dict) and "error" in ev:
                        # mid-stream error event (e.g. instance died after
                        # tokens reached us — not replayable, or the
                        # master demoted mid-exchange): fault-visible
                        stream_err = payload[:200]
                        break
                    now = time.monotonic()
                    if t_first is None:
                        t_first = now
                    elif t_last is not None:
                        deltas.append(now - t_last)
                    t_last = now
                    n_tok += 1
                conn.close()
            except Exception as e:  # noqa: BLE001
                stream_err = stream_err or repr(e)
            if not done and attempt + 1 < max_attempts:
                continue  # retry-to-current-master
            with mu:
                if t_first is not None:
                    ttfts.append(t_first - t0)
                    (off_ttfts if offline_mask[i] else on_ttfts).append(
                        t_first - t0
                    )
                tpots.extend(deltas)
                lats.append(time.monotonic() - t0)
                first_tokens[0] += n_tok
                if (
                    master_chaos
                    and done
                    and not args.real_engine
                    and n_tok != expect_tok
                ):
                    # A COMPLETED stream whose token count deviates from
                    # the trace expectation means duplicated (two masters
                    # fed it) or lost tokens — the split-brain symptom
                    # epoch fencing exists to make impossible.
                    double_dispatches[0] += 1
                if not done:
                    if master_chaos:
                        unrecovered[0] += 1
                        errors.append(
                            stream_err or "stream ended without [DONE]"
                        )
                    elif stream_err:
                        errors.append(stream_err)
                elif stream_err:
                    errors.append(stream_err)
            return

    threads = []
    t_start = time.monotonic()
    for i in range(args.requests):
        time.sleep(float(gaps[i]))
        while pending_events and pending_events[0][0] <= i:
            _, ev = pending_events.pop(0)
            fire_chaos(ev, t_start)
        t = threading.Thread(target=drive, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=600.0)
    for t in rolling_threads:
        t.join(timeout=600.0)
    wall = time.monotonic() - t_start
    # Read terminal stats from the replica that ended the run as master —
    # under master chaos the original one may be dead.
    active = _active_master()
    sched = active.scheduler
    redispatches = sched.total_redispatches
    resumes = sched.total_resumes
    redispatch_attempts = sched.total_redispatch_attempts
    mgr = sched.instance_mgr
    pd_flips = mgr.total_flips
    failed_after_retry = int(
        sched.metrics.get("xllm_service_finished_total")
        .labels(outcome="error").get()
    )
    resume_hist = sched.metrics.get("xllm_service_resume_latency_ms")
    resume_p99 = resume_hist.percentile(99) if resume_hist else None
    health_states = dict(mgr.health_states())
    ejections = mgr.total_ejections
    probe_recoveries = mgr.total_probe_recoveries
    budget_exhausted = active._retry_budget.exhausted_total
    master_report = None
    if master_chaos:
        # Give the instance-side orphan TTL a chance to fire so the reap
        # counters below reflect the steady state, not a race.
        time.sleep(cfg.reconcile_orphan_ttl_s + 1.0)

        def _inst_counter(name):
            total = 0
            for srv in instances:
                m = srv.metrics.get(name)
                if m is not None:
                    total += int(m.get())
            return total

        master_report = {
            "master_kills": master_kills or None,
            "final_master": sched.election_identity,
            "final_epoch": sched.master_epoch,
            "takeover_ms": (
                round(sched.last_takeover_ms, 3)
                if sched.last_takeover_ms is not None else None
            ),
            "takeover_to_first_dispatch_ms": (
                round(sched.takeover_first_dispatch_ms, 3)
                if sched.takeover_first_dispatch_ms is not None else None
            ),
            "reconciled_requests": sched.total_reconciled,
            "orphaned_requests": sched.total_orphaned,
            "orphans_reaped": _inst_counter(
                "xllm_service_orphan_reaped_total"
            ),
            "fenced_rpcs": _inst_counter(
                "xllm_instance_fenced_rpcs_total"
            ),
            "retried_to_new_master": retried_to_new_master[0],
            "double_dispatches": double_dispatches[0],
            "unrecovered_reconcilable_streams": unrecovered[0],
        }
    faults.clear()

    # Service-tier latency distributions from the obs histograms (the
    # same series the master's /metrics exports): bucket-interpolated
    # percentiles, cross-checkable against the client-side measurements
    # above.
    def hist_pcts(name):
        h = sched.metrics.get(name)
        if h is None:
            return None
        return {
            f"p{q}": (
                round(v, 3) if (v := h.percentile(q)) is not None else None
            )
            for q in (50, 90, 99)
        }

    service_hists = {
        "ttft_ms": hist_pcts("xllm_service_ttft_ms"),
        "tpot_ms": hist_pcts("xllm_service_tpot_ms"),
        "e2e_ms": hist_pcts("xllm_service_e2e_ms"),
        "queue_delay_ms": hist_pcts("xllm_service_queue_delay_ms"),
    }
    cached = sum(
        getattr(srv.engine, "prefix_cached_tokens", 0) for srv in instances
    )
    prompted = sum(
        getattr(srv.engine, "prefix_prompt_tokens", 0) for srv in instances
    )
    prefix_hit_rate = round(cached / prompted, 4) if prompted else None
    prefix_by_instance = {
        srv.name: [
            int(getattr(srv.engine, "prefix_cached_tokens", 0)),
            int(getattr(srv.engine, "prefix_prompt_tokens", 0)),
        ]
        for srv in instances
    }

    for srv in instances:
        try:
            srv.stop()
        except Exception:
            pass
    for m in masters:
        try:
            m.stop()
        except Exception:
            pass
    store.close()

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 4) if xs else None

    print(
        json.dumps(
            {
                "metric": "serving_burst",
                "backend": (
                    ("tpu" if on_tpu else "cpu-real")
                    if args.real_engine
                    else "fake"
                ),
                "policy": args.policy,
                "trace": args.trace or "synthetic-sharegpt",
                "requests": args.requests,
                "offline_frac": args.offline_frac,
                "errors": len(errors),
                "rate_req_s": args.rate,
                "wall_s": round(wall, 3),
                "total_tokens": first_tokens[0],
                "throughput_tok_s": round(first_tokens[0] / wall, 1),
                "ttft_p50_s": pct(ttfts, 50),
                "ttft_p99_s": pct(ttfts, 99),
                "online_ttft_p99_s": pct(on_ttfts, 99),
                "offline_ttft_p99_s": pct(off_ttfts, 99),
                "tpot_p50_ms": (
                    round(1000 * float(np.percentile(tpots, 50)), 2)
                    if tpots else None
                ),
                "tpot_p99_ms": (
                    round(1000 * float(np.percentile(tpots, 99)), 2)
                    if tpots else None
                ),
                "req_p99_s": pct(lats, 99),
                "chaos_events": chaos_events or None,
                "killed_instances": killed_at or None,
                "redispatches": redispatches,
                "redispatch_attempts": redispatch_attempts,
                "recovered_streams": resumes,
                "resume_latency_p99_ms": (
                    round(resume_p99, 3) if resume_p99 is not None else None
                ),
                "failed_after_retry": failed_after_retry,
                "breaker_ejections": ejections,
                "breaker_probe_recoveries": probe_recoveries,
                "retry_budget_exhausted": budget_exhausted,
                "health_states": health_states or None,
                "service_histograms": service_hists,
                "error_sample": errors[0][:200] if errors else None,
                "shared_prefix_tokens": args.shared_prefix or None,
                "prefix_cache_hit_rate": prefix_hit_rate,
                "prefix_by_instance": (
                    prefix_by_instance if args.shared_prefix else None
                ),
                "pd_flips": pd_flips,
                "rolling_restarts": rolling_log or None,
                "rolling_restart_guard": (
                    ("ok" if not errors else f"{len(errors)} dropped streams")
                    if rolling_log else None
                ),
                "master_failover": master_report,
            }
        )
    )
    if rolling_log and errors:
        # The maneuver is graceful end to end; ANY client-visible stream
        # error during it is a recovery bug, not acceptable collateral.
        import sys

        sys.exit(3)


if __name__ == "__main__":
    main()
