#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py              # one TPU v5e chip (what the driver runs)
    python chip_smoke.py --chips 4    # the tp-sharded engine on a 2x2 host

One process. Without arguments: a master and one MIX instance, in-process
over real sockets, serve **llama3-3b** at full published width (28 layers,
hidden 3072, 24/8 heads of 128, vocab 128256, bf16; weights random-init
from --seed, ByteTokenizer) and answer a few greedy `/v1/completions`
requests — one streamed, three concurrent (two of them identical), prompts
long enough to cross a prefill chunk. Every served token is then checked
against the repo's plain dense forward (`models/llama.hidden_dense`: no
paged cache, no Pallas) run teacher-forced over prompt + served tokens on
the same weights.

With `--chips 4` it runs ONLY the sharded path and what it is compared
with: **llama3-8b** bf16 at tp_size=4 through the same master + instance
path, once with the per-shard Pallas kernels and once with them forced off
(plain GSPMD), on the same prompts.

Any phase that fails ends the run with a non-zero exit code. Finding no
TPU is a failure, not a CPU run; `--rehearse-cpu` (a cut-down model on the
CPU backend, for finding wrong paths before spending chip time) is the
only way to run without one and can never report `"platform": "tpu"`.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import http.client
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # hard stop inside the driver's 1200 s limit

# bf16 carries 8 mantissa bits: one ulp at |logit| ~ 4-8 is 0.016-0.03, and
# the served path and the dense reference round differently in every one
# of the layers. A served token whose reference logit is within LOGIT_TOL
# of the reference maximum is a tie the two paths may break differently; a
# token from a wrong path (stale KV, wrong block, wrong mask) sits several
# units below the maximum (random-init logits: sigma ~ 1, max over 128256
# entries ~ 4.5 sigma). Each limit is about twice the worst reading on the
# chip (PERF.md, PR 26: deficit 0.0260 on one chip and 0.0407 on four,
# logprob error 0.0391 and 0.0494, 77/80 and 73/80 tokens exact).
LOGIT_TOL = 0.1
MIN_EXACT = 0.85  # share of served tokens that must be the reference argmax
LOGPROB_TOL = 0.1  # chosen-token logprob: served vs reference
# Two greedy engines can only be compared up to their first bf16 tie (it can
# be the first token); after it the streams are unrelated. Each is held to
# the dense reference on its own; between them, logprobs must agree up to
# and at the divergence, and this share of all tokens must lie before any
# divergence (read on four chips: 47/80; chance agreement of one token:
# 1/vocab).
MIN_AGREED = 0.3

MAX_TOKENS = 16
BLOCK = 128


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------- HTTP helpers


def post_json(addr: str, path: str, body: dict, timeout: float = 900.0):
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def post_sse(addr: str, path: str, body: dict, timeout: float = 900.0):
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request(
            "POST", path, body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"stream HTTP {resp.status}: {resp.read()!r}")
        events = []
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                events.append("[DONE]")
                break
            events.append(json.loads(payload))
        return events
    finally:
        conn.close()


def wait_until(pred, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return pred()


# ------------------------------------------------------------------ phases


def rebuild_native() -> None:
    """`*.so` is ignored by git, so a checkout has none and a copied work
    tree may carry stale ones (the loaders rebuild on mtime only): remove
    them and let each loader build from its .cpp source, then say which
    core serves — the C++ one or its Python twin. Never changes which."""
    native = os.path.join(REPO, "xllm_service_tpu", "native")
    stale = sorted(glob.glob(os.path.join(native, "*.so")))
    for path in stale:
        os.remove(path)
    from xllm_service_tpu.common import hashing
    from xllm_service_tpu.runtime import native_blocks
    from xllm_service_tpu.tokenizer import (
        native_bpe, native_sp, native_tiktoken,
    )

    t0 = time.time()
    cores = {
        "murmur3": hashing._load_native() is not None,
        "block_store": native_blocks.native_available(),
        "bpe": native_bpe._load_lib() is not None,
        "sentencepiece": native_sp._load_lib() is not None,
        "tiktoken": native_tiktoken._load_lib() is not None,
    }
    built = sorted(
        os.path.basename(p)
        for p in glob.glob(os.path.join(native, "*.so"))
    )
    log(
        f"native: removed {len(stale)} built libraries, rebuilt "
        f"{len(built)} from .cpp in {time.time() - t0:.1f}s: "
        + ", ".join(
            f"{k}={'c++' if v else 'python-twin'}" for k, v in cores.items()
        )
    )
    if not native_blocks.native_available():
        log(f"native: block store build error: {native_blocks._lib_error}")


class CompileMeter:
    """Sums jax's own compile events: backend compile seconds (cache
    retrieval included on a hit), persistent-cache hits and misses."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.retrieval_s = 0.0
        self.hits = 0
        self.misses = 0
        self.programs = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.programs += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "programs": self.programs,
            "persistent_cache_hits": self.hits,
            "persistent_cache_misses": self.misses,
            "compile_s": round(self.compile_s, 2),
            "of_which_cache_retrieval_s": round(self.retrieval_s, 2),
        }


def make_prompt(rng, n_bytes: int) -> str:
    words = (
        "tensor shard block cache prefill decode ragged paged kernel "
        "router master instance lease token stream chunk batch slot "
        "mesh chip host queue"
    ).split()
    out = []
    size = 0
    while size < n_bytes:
        w = words[int(rng.integers(len(words)))]
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def nbytes(tree) -> int:
    import jax

    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree))


class Stack:
    """A master and one MIX instance over real sockets, in this process."""

    def __init__(self, engine_cfg, model_cfg, seed: int, executor=None):
        # The master tier imports no JAX; the instance tier does.
        from xllm_service_tpu.api import Master
        from xllm_service_tpu.api.instance import InstanceServer
        from xllm_service_tpu.common.config import ServiceConfig
        from xllm_service_tpu.coordination import MemoryStore
        from xllm_service_tpu.runtime.engine import InferenceEngine
        from xllm_service_tpu.runtime.executor import ModelExecutor

        self.model = engine_cfg.model
        self.store = MemoryStore()
        self.master = Master(
            ServiceConfig(
                host="127.0.0.1", http_port=0, rpc_port=0,
                heartbeat_interval_s=0.5, block_size=engine_cfg.block_size,
            ),
            store=self.store,
        )
        self.inst = None
        self.master.start()
        t0 = time.time()
        self.executor = executor or ModelExecutor(
            engine_cfg, model_cfg=model_cfg, init_seed=seed
        )
        self.build_s = time.time() - t0
        engine = InferenceEngine(engine_cfg, executor=self.executor)
        # Tap (read-only) the token ids each request is served: the API
        # returns text, and ByteTokenizer folds ids onto bytes.
        self.served: dict = {}
        add = engine.add_request

        def tapped(req):
            rec = {"prompt": list(req.prompt_token_ids), "out": []}
            self.served[req.request_id] = rec
            cb = req.callback

            def on_output(out):
                for s in out.outputs:
                    rec["out"].extend(int(t) for t in s.token_ids)
                return cb(out)

            req.callback = on_output
            return add(req)

        engine.add_request = tapped
        self.engine = engine
        self.inst = InstanceServer(
            engine_cfg,
            master_rpc_addr=self.master.rpc_address,
            heartbeat_interval_s=0.5,
            engine=engine,
        )
        self.inst.start()
        check(
            wait_until(
                lambda: sum(self.master.scheduler.instance_mgr.counts()) == 1,
                30.0,
            ),
            "instance did not register with the master",
        )

    def stop(self) -> None:
        if self.inst is not None:
            self.inst.stop()
        self.master.stop()
        self.store.close()

    def complete(self, prompt: str, stream: bool = False) -> dict:
        body = {
            "model": self.model, "prompt": prompt, "max_tokens": MAX_TOKENS,
            "temperature": 0.0, "logprobs": 1, "ignore_eos": True,
            "stream": stream,
        }
        addr = self.master.http_address
        if stream:
            events = post_sse(addr, "/v1/completions", body)
            check(events and events[-1] == "[DONE]", "stream did not end [DONE]")
            chunks = [e["choices"][0] for e in events[:-1] if e.get("choices")]
            check(len(chunks) >= 2, f"stream came in {len(chunks)} chunk(s)")
            text = "".join(c.get("text", "") for c in chunks)
            lps = [
                lp for c in chunks if c.get("logprobs")
                for lp in c["logprobs"]["token_logprobs"]
            ]
            return {"text": text, "logprobs": lps, "prompt": prompt,
                    "chunks": len(chunks)}
        code, resp = post_json(addr, "/v1/completions", body)
        check(code == 200, f"HTTP {code}: {resp}")
        choice = resp["choices"][0]
        check(
            resp["usage"]["completion_tokens"] == MAX_TOKENS,
            f"completion_tokens {resp['usage']} != {MAX_TOKENS}",
        )
        return {
            "text": choice["text"], "prompt": prompt,
            "logprobs": (choice.get("logprobs") or {}).get(
                "token_logprobs", []
            ),
        }

    def ids_for(self, result: dict, tokenizer) -> dict:
        """The tapped token ids of the request that produced `result`
        (matched on prompt ids and on the decoded text), consumed once."""
        want = tokenizer.encode(result["prompt"])
        for rid, rec in self.served.items():
            if rec.get("taken") or rec["prompt"][-len(want):] != want:
                continue
            if tokenizer.decode(rec["out"]) == result["text"]:
                rec["taken"] = True
                return rec
        raise SmokeFailure(
            f"no served request matches the response text {result['text']!r}"
        )


def serve_traffic(stack: Stack, prompts: dict) -> dict:
    """One streamed request, three concurrent (two identical), then the
    streamed prompt again unstreamed (prefix-cache hit path)."""
    from xllm_service_tpu.tokenizer.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    results = {}
    t0 = time.time()
    results["stream_a"] = stack.complete(prompts["a"], stream=True)
    log(f"serve: streamed request done in {time.time() - t0:.1f}s "
        f"({results['stream_a']['chunks']} chunks)")

    t0 = time.time()
    out: dict = {}

    def worker(name: str, prompt: str) -> None:
        try:
            out[name] = stack.complete(prompt)
        except BaseException as e:  # re-raised on the main thread below
            out[name] = e

    threads = [
        threading.Thread(target=worker, args=(n, prompts[p]))
        for n, p in (("pair_b1", "b"), ("pair_b2", "b"), ("conc_c", "c"))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
        check(not t.is_alive(), "a concurrent request never returned")
    for name, res in out.items():
        if isinstance(res, BaseException):
            raise res
        results[name] = res
    log(f"serve: 3 concurrent requests done in {time.time() - t0:.1f}s")

    t0 = time.time()
    results["again_a"] = stack.complete(prompts["a"])
    log(f"serve: repeat of the streamed prompt done in {time.time() - t0:.1f}s")

    for name, res in results.items():
        rec = stack.ids_for(res, tok)
        check(
            len(rec["out"]) == MAX_TOKENS,
            f"{name}: served {len(rec['out'])} tokens, asked {MAX_TOKENS}",
        )
        check(
            len(res["logprobs"]) == MAX_TOKENS,
            f"{name}: {len(res['logprobs'])} logprobs for {MAX_TOKENS} tokens",
        )
        res["ids"] = rec["out"]
        res["prompt_ids"] = rec["prompt"]
    return results


def dense_reference_check(executor, results: dict) -> None:
    """Teacher-forced plain dense forward over prompt + served tokens, on
    the weights that served them: every served token must be the reference
    argmax or tie with it within LOGIT_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, mod = executor.cfg, executor.model_mod
    longest = max(len(r["prompt_ids"]) + MAX_TOKENS for r in results.values())
    L = 128 * ((longest + 127) // 128)

    @jax.jit
    def ref_logits(params, toks, idx):
        h = mod.hidden_dense(params, cfg, toks)[0]  # [L, E] final-normed
        return mod._project(params, cfg, h[idx])  # [n, V] f32

    exact = total = 0
    worst = 0.0
    t0 = time.time()
    with executor.mesh:
        for name, r in results.items():
            n_prompt = len(r["prompt_ids"])
            toks = np.zeros((1, L), np.int32)
            seq = r["prompt_ids"] + r["ids"]
            toks[0, : len(seq)] = seq
            idx = np.arange(n_prompt - 1, n_prompt - 1 + MAX_TOKENS)
            logits = np.asarray(
                ref_logits(
                    executor.params, jnp.asarray(toks),
                    jnp.asarray(idx, jnp.int32),
                )
            )
            check(
                logits.shape == (MAX_TOKENS, cfg.vocab_size)
                and bool(np.isfinite(logits).all()),
                f"{name}: reference logits not finite / wrong shape",
            )
            served = np.asarray(r["ids"])
            deficit = logits.max(-1) - logits[np.arange(MAX_TOKENS), served]
            n_exact = int((logits.argmax(-1) == served).sum())
            # The served logprob of the chosen token against the
            # reference's log-softmax at the same token.
            ref_lp = logits - np.logaddexp.reduce(logits, axis=-1, keepdims=True)
            lp_err = float(
                np.abs(
                    ref_lp[np.arange(MAX_TOKENS), served]
                    - np.asarray(r["logprobs"], np.float64)
                ).max()
            )
            log(
                f"reference: {name}: prompt {n_prompt} tok, "
                f"{n_exact}/{MAX_TOKENS} argmax-exact, worst logit deficit "
                f"{float(deficit.max()):.4f}, worst |logprob err| {lp_err:.4f}"
            )
            check(
                float(deficit.max()) <= LOGIT_TOL,
                f"{name}: served token {float(deficit.max()):.3f} below the "
                f"dense reference maximum (tolerance {LOGIT_TOL})",
            )
            check(
                lp_err <= LOGPROB_TOL,
                f"{name}: served logprob off the reference by {lp_err:.3f}",
            )
            exact += n_exact
            total += MAX_TOKENS
            worst = max(worst, float(deficit.max()))
    log(
        f"reference: {exact}/{total} served tokens are the dense-forward "
        f"argmax, worst deficit {worst:.4f} (tolerance {LOGIT_TOL}), "
        f"{time.time() - t0:.1f}s"
    )
    check(
        exact >= MIN_EXACT * total,
        f"only {exact}/{total} served tokens match the dense argmax",
    )


def device_report(executor) -> None:
    import jax

    gib = 2.0 ** 30
    log(
        f"memory: num_blocks={executor.num_blocks} "
        f"params={nbytes(executor.params) / gib:.3f}GiB "
        f"kv_pool={(nbytes(executor.k_cache) + nbytes(executor.v_cache)) / gib:.3f}GiB"
    )
    for d in executor.mesh.devices.flat:
        stats = d.memory_stats()
        if stats is None:
            log(f"memory: device {d.id}: backend reports no memory_stats")
            continue
        log(
            f"memory: device {d.id}: bytes_limit={stats['bytes_limit']} "
            f"({stats['bytes_limit'] / gib:.2f}GiB) "
            f"bytes_in_use={stats['bytes_in_use']} "
            f"peak_bytes_in_use={stats['peak_bytes_in_use']} "
            f"({stats['peak_bytes_in_use'] / gib:.2f}GiB)"
        )


def cache_hit_replay(stack: Stack, meter: CompileMeter, prompt: str,
                     expect_ids: list) -> None:
    """The cold-vs-hit cost of whole-model programs, through the server:
    with the engine idle, drop the in-process jit caches and send a
    request the run has served before. Its step programs re-trace on the
    engine thread (a Pallas kernel's serialized module carries its Python
    call stack, so only the same stack gives the same cache key) and must
    come back from the persistent cache, and the tokens must not change."""
    import jax

    from xllm_service_tpu.tokenizer.tokenizer import ByteTokenizer

    hits, misses, secs, retr = (
        meter.hits, meter.misses, meter.compile_s, meter.retrieval_s
    )
    jax.clear_caches()
    t0 = time.time()
    res = stack.complete(prompt)
    rec = stack.ids_for(res, ByteTokenizer())
    log(
        f"compile: after jax.clear_caches() the repeat request took "
        f"{time.time() - t0:.1f}s: persistent-cache hits "
        f"+{meter.hits - hits}, misses +{meter.misses - misses}, compile "
        f"{meter.compile_s - secs:.1f}s of which cache retrieval "
        f"{meter.retrieval_s - retr:.1f}s"
    )
    check(rec["out"] == expect_ids, "tokens changed after the cache replay")
    check(
        meter.hits > hits,
        "no re-traced step program was found in the persistent compile cache",
    )


# ---------------------------------------------------------------- one chip


def engine_config(model: str, cache_dir: str, tp: int, rehearse: bool):
    from xllm_service_tpu.common.config import EngineConfig

    return EngineConfig(
        model=model,
        dtype="float32" if rehearse else "bfloat16",
        block_size=BLOCK,
        num_blocks=64 if rehearse else 0,  # 0: sized from the chip's HBM
        max_running_requests=32,
        max_seq_len=2048,
        max_prefill_tokens=512,  # prompts below cross at least one chunk
        prefill_buckets=[128, 256, 512, 1024, 2048],
        tp_size=tp,
        compilation_cache_dir=cache_dir,
        instance_name="smoke0",
        instance_type="MIX",
    )


def model_config(model: str, rehearse: bool):
    from xllm_service_tpu.models.configs import get_model_config

    cfg = get_model_config(model)
    if rehearse:  # CPU: keep the head geometry, cut everything else
        cfg = dataclasses.replace(
            cfg, num_layers=2, hidden_size=256, intermediate_size=512,
            num_heads=8, num_kv_heads=4, vocab_size=2048,
        )
    return cfg


def prompts_for(seed: int) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    return {
        "a": make_prompt(rng, 700),
        "b": make_prompt(rng, 900),
        "c": make_prompt(rng, 650),
    }


def check_kernels(executor, on_chip: bool, shards: int) -> None:
    # ops.attention.attention_routes for this executor's cache, head_dim
    # and shard fan-out: the decision the dispatchers take their branch from.
    rep = executor.kernel_report()
    log(f"kernels: {json.dumps(rep)}")
    if on_chip:
        check(
            (rep["decode"], rep["prefill"], rep["mixed"])
            == ("paged", "flash", "paged+flash"),
            f"the chip is not served by the Pallas kernels: {rep}",
        )
    else:
        log("kernels: CPU rehearsal — the Pallas kernels are not selected")
    check(rep["shards"] == shards, f"shards {rep['shards']} != {shards}")


def run_one_chip(args, meter: CompileMeter, cache_dir: str) -> None:
    ecfg = engine_config("llama3-3b", cache_dir, 1, args.rehearse_cpu)
    mcfg = model_config("llama3-3b", args.rehearse_cpu)
    log(
        f"building llama3-3b: layers={mcfg.num_layers} hidden={mcfg.hidden_size} "
        f"heads={mcfg.num_heads}/{mcfg.num_kv_heads}x{mcfg.head_dim} "
        f"vocab={mcfg.vocab_size} dtype={ecfg.dtype} seed={args.seed}"
    )
    stack = Stack(ecfg, mcfg, args.seed)
    try:
        ex = stack.executor
        log(f"built in {stack.build_s:.1f}s; master http "
            f"{stack.master.http_address}, instance registered")
        device_report(ex)
        check_kernels(ex, not args.rehearse_cpu, 1)
        results = serve_traffic(stack, prompts_for(args.seed))
        log(f"compile: after serving {json.dumps(meter.snapshot())}; "
            f"{ex.lowering_count()} whole-model step programs")
        check(
            results["stream_a"]["ids"] == results["again_a"]["ids"],
            "the streamed request and its unstreamed repeat disagree",
        )
        log(
            "identical concurrent greedy pair agrees: "
            + str(results["pair_b1"]["ids"] == results["pair_b2"]["ids"]).lower()
        )
        cache_hit_replay(
            stack, meter, prompts_for(args.seed)["a"], results["again_a"]["ids"]
        )
    finally:
        stack.stop()
    dense_reference_check(ex, results)
    device_report(ex)


# -------------------------------------------------------------- four chips


def run_four_chips(args, meter: CompileMeter, cache_dir: str) -> None:
    import jax
    import numpy as np

    tp = 4
    ecfg = engine_config("llama3-8b", cache_dir, tp, args.rehearse_cpu)
    mcfg = model_config("llama3-8b", args.rehearse_cpu)
    log(
        f"building llama3-8b tp={tp}: layers={mcfg.num_layers} "
        f"hidden={mcfg.hidden_size} heads={mcfg.num_heads}/{mcfg.num_kv_heads}"
        f"x{mcfg.head_dim} vocab={mcfg.vocab_size} dtype={ecfg.dtype}"
    )
    prompts = prompts_for(args.seed)
    stack = Stack(ecfg, mcfg, args.seed)
    try:
        ex = stack.executor
        log(f"built in {stack.build_s:.1f}s")
        devs = list(ex.mesh.devices.flat)
        check(len({d.id for d in devs}) == tp, f"mesh holds {devs}")
        device_report(ex)
        # Each device holds its quarter of every sharded leaf and of the pool.
        per_dev = {d.id: 0 for d in devs}
        for leaf in jax.tree.leaves((ex.params, ex.k_cache, ex.v_cache)):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] += sh.data.nbytes
        total = nbytes((ex.params, ex.k_cache, ex.v_cache))
        log("placement: bytes per device "
            + json.dumps({str(k): v for k, v in per_dev.items()})
            + f" of {total} (params+pool, replicated leaves count on each)")
        lo, hi = min(per_dev.values()), max(per_dev.values())
        check(lo == hi, f"devices hold unequal shares: {per_dev}")
        check(
            0.25 * total <= lo <= 0.30 * total,
            f"each device should hold about a quarter, holds {lo / total:.3f}",
        )
        for leaf in jax.tree.leaves((ex.k_cache, ex.v_cache)):
            check(
                all(
                    s.data.nbytes * tp == leaf.nbytes
                    for s in leaf.addressable_shards
                ),
                "a KV pool leaf is not split four ways",
            )
        if not args.rehearse_cpu:
            in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
            check(
                max(in_use) <= 1.15 * min(in_use) and min(in_use) >= lo,
                f"memory_stats in_use differs across devices: {in_use}",
            )
        check_kernels(ex, not args.rehearse_cpu, tp)
        on = serve_traffic(stack, prompts)
        log(f"compile: kernels-on serving {json.dumps(meter.snapshot())}")
    finally:
        stack.stop()

    check_allreduce(ex)
    dense_reference_check(ex, on)

    # What it is compared with: the same weights and pool, Pallas kernels
    # forced off (plain GSPMD gather/blockwise path), same prompts.
    for var in ("XLLM_PAGED_ATTENTION_KERNEL", "XLLM_PREFILL_ATTENTION_KERNEL",
                "XLLM_MQ_ATTENTION_KERNEL"):
        os.environ[var] = "0"
    jax.clear_caches()
    ref_stack = Stack(ecfg, mcfg, args.seed, executor=ex)
    try:
        rep = ex.kernel_report()
        log(f"kernels (reference engine): {json.dumps(rep)}")
        check(
            rep["decode"].startswith("gather")
            and rep["prefill"].startswith("blockwise")
            and rep["mq"].startswith("blockwise"),
            f"the reference engine still runs kernels: {rep}",
        )
        off = serve_traffic(ref_stack, prompts)
    finally:
        ref_stack.stop()

    # The reference engine is held to the dense forward too, then the two
    # greedy streams are compared up to their first divergence, which must
    # be a tie: the two engines' chosen-token logprobs agree there and at
    # every position before it.
    dense_reference_check(ex, off)
    agreed = total_tok = 0
    for name in on:
        a, b = on[name], off[name]
        n = 0
        while n < MAX_TOKENS and a["ids"][n] == b["ids"][n]:
            n += 1
        upto = min(n + 1, MAX_TOKENS)
        err = float(
            np.abs(
                np.asarray(a["logprobs"][:upto]) - np.asarray(b["logprobs"][:upto])
            ).max()
        )
        log(f"compare: {name}: {n}/{MAX_TOKENS} tokens agree with the "
            f"kernels-off engine, |logprob diff| <= {err:.4f} up to and at "
            f"the divergence")
        check(err <= LOGPROB_TOL, f"{name}: logprobs differ by {err:.3f}")
        agreed += n
        total_tok += MAX_TOKENS
    log(f"compare: {agreed}/{total_tok} tokens agree before any divergence")
    check(agreed >= MIN_AGREED * total_tok, "kernels-on and -off engines diverge")
    device_report(ex)


def check_allreduce(executor) -> None:
    """The sharded decode step, as compiled for this mesh, all-reduces."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.runtime.executor import DEC_FIELDS

    R = executor.R
    executor._set_shard_ctx()
    lowered = executor._decode_jit.lower(
        executor.k_cache, executor.v_cache, executor.token_counts,
        executor.params, jnp.zeros((R, len(DEC_FIELDS) + 8), jnp.int32),
        jnp.zeros((R,), jnp.int32), use_kernel=None,
    )
    text = lowered.compile().as_text()
    n_ar = text.count("all-reduce(") + text.count("all-reduce-start(")
    n_kernel = text.count("tpu_custom_call")
    log(f"compiled decode step: {n_ar} all-reduce ops, "
        f"{n_kernel} tpu_custom_call sites")
    check(n_ar > 0, "the sharded decode step contains no all-reduce")


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="cut-down model on the CPU backend (never reports a TPU)",
    )
    args = ap.parse_args()

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            )
    threading.Thread(
        target=lambda: (time.sleep(DEADLINE_S), os._exit(124)), daemon=True
    ).start()

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: {str(e).splitlines()[0]}", file=sys.stderr)
        return 2
    dev = devices[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU: jax reports platform={dev.platform!r} "
            f"({len(devices)} device(s)); this is a chip check, not a CPU run",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but jax reports "
            f"{len(devices)} device(s)", file=sys.stderr,
        )
        return 2
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    from xllm_service_tpu.runtime import compile_cache

    cache_dir = compile_cache.resolve_cache_dir(compile_cache.DEFAULT_DIR)
    log(
        f"compile cache: {cache_dir} "
        f"({'placed by' if os.environ.get(compile_cache.ENV_DIR) else 'no'} "
        f"{compile_cache.ENV_DIR}; {compile_cache.cache_entries(cache_dir)} "
        f"entries at start)"
    )
    rebuild_native()
    meter = CompileMeter()

    t0 = time.time()
    if args.chips == 4:
        run_four_chips(args, meter, cache_dir)
    else:
        run_one_chip(args, meter, cache_dir)
    log(f"compile: end of run {json.dumps(meter.snapshot())}; "
        f"{compile_cache.cache_entries(cache_dir)} entries in {cache_dir}")
    log(f"all phases passed in {time.time() - t0:.1f}s")
    result = {
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
