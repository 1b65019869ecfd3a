"""The system under test, in this process: a Master and one MIX
InstanceServer over real sockets around an InferenceEngine (after
chip_smoke.Stack; copied, not imported, because later PRs may change the
smoke).

The benchmark depends on the program's internals here and in the family
files (benchmarks/families/: each maps its configuration's keys to
ModelConfig, its one import from the program):
  * `install_weights` puts the benchmark's own weights (the family's
    make_weights) in the place of the executor's random-init ones, leaf
    for leaf: the guard between a family file and the program's tree;
  * the tap wraps `engine.add_request` and each request's callback,
    read-only, for engine-side timestamps and the served token ids.
Everything else goes through HTTP: /v1/completions on the master and
/metrics on the instance."""

from __future__ import annotations

import dataclasses
import http.client
import json
import time
from typing import Dict, List, Mapping, Optional

from benchmarks.harness.family import seed_key
from benchmarks.harness.loadgen import request_key  # imports no JAX

class StackError(RuntimeError):
    pass


def engine_config(name: str, engine: Mapping, cache_dir: str):
    from xllm_service_tpu.common.config import EngineConfig

    known = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(set(engine) - known)
    if unknown:
        raise StackError(f"configuration names unknown EngineConfig fields {unknown}")
    kw = dict(engine)
    kw.update(
        model=name, compilation_cache_dir=cache_dir, instance_name="bench0",
        instance_type="MIX",
    )
    return EngineConfig(**kw)


def place_weights(executor, family, m: Mapping, seed: int, shardings) -> float:
    """The benchmark's weights (the family's make_weights), made on the
    device in one jitted call with the given shardings, put in the
    executor's place for parameters."""
    import jax

    t0 = time.monotonic()
    make = jax.jit(
        lambda k: family.make_weights(m, k, executor.dtype),
        out_shardings=shardings,
    )
    with executor.mesh:
        executor.params = make(seed_key(seed))
    jax.block_until_ready(executor.params)
    return time.monotonic() - t0


def install_weights(executor, family, m: Mapping, seed: int) -> float:
    """Replace the executor's random-init parameters by the benchmark's,
    leaf for leaf, with the executor's own shardings. The old leaves are
    freed first: two copies do not fit beside the pool."""
    import jax

    old = executor.params
    shardings = jax.tree.map(lambda a: a.sharding, old)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), old)
    made = jax.eval_shape(
        lambda k: family.make_weights(m, k, executor.dtype), seed_key(seed)
    )
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), made)
    if have != want:
        raise StackError(
            f"the executor's parameter tree is not {family.__name__}'s: "
            f"executor {want} family {have}"
        )
    for leaf in jax.tree.leaves(old):
        leaf.delete()
    return place_weights(executor, family, m, seed, shardings)


def parse_metrics(text: str) -> Dict[str, float]:
    """Prometheus text -> {name: value}; labelled series are summed under
    the bare name and kept under their full name too."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        try:
            v = float(val)
        except ValueError:
            continue
        out[head] = out.get(head, 0.0) + v
        bare = head.split("{", 1)[0]
        if bare != head:
            out[bare] = out.get(bare, 0.0) + v
    return out


def http_get(addr: str, path: str, timeout: float = 10.0) -> str:
    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise StackError(f"GET {path}: HTTP {resp.status}")
        return body
    finally:
        conn.close()


def http_post(addr: str, path: str, body: dict, timeout: float = 600.0):
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


class Stack:
    def __init__(self, name: str, family, config: Mapping, seed: int, cache_dir: str,
                 engine: Optional[Mapping] = None):
        """`config` is the configuration file as parsed; `engine` replaces
        its "engine" group (a control that switches a lower precision on)."""
        from xllm_service_tpu.api import Master
        from xllm_service_tpu.api.instance import InstanceServer
        from xllm_service_tpu.common.config import ServiceConfig
        from xllm_service_tpu.coordination import MemoryStore
        from xllm_service_tpu.runtime.engine import InferenceEngine
        from xllm_service_tpu.runtime.executor import ModelExecutor

        self.name, self.family, self.config = name, family, config
        ecfg = engine_config(name, config["engine"] if engine is None else engine, cache_dir)
        self.engine_cfg = ecfg
        self.store = MemoryStore()
        self.master = Master(
            ServiceConfig(
                host="127.0.0.1", http_port=0, rpc_port=0,
                heartbeat_interval_s=0.5, block_size=ecfg.block_size,
            ),
            store=self.store,
        )
        self.inst = None
        self.master.start()
        t0 = time.monotonic()
        self.executor = ModelExecutor(
            ecfg, model_cfg=family.model_config(name, config), init_seed=0
        )
        self.build_s = time.monotonic() - t0
        self.weights_s = install_weights(self.executor, family, config, seed)
        engine_obj = InferenceEngine(ecfg, executor=self.executor)
        self.taps: Dict[str, dict] = {}
        add = engine_obj.add_request

        def tapped(req):
            rec = {
                "prompt_len": len(req.prompt_token_ids),
                "prompt": None, "out": [], "times": [], "counts": [],
                "t_add": time.monotonic(), "finished": False,
            }
            if self.keep_prompts:
                rec["prompt"] = [int(t) for t in req.prompt_token_ids]
            self.taps[request_key(req.prompt_token_ids)] = rec
            cb = req.callback

            def on_output(out):
                now = time.monotonic()
                n = 0
                for s in out.outputs:
                    rec["out"].extend(int(t) for t in s.token_ids)
                    n += len(s.token_ids)
                if n:
                    rec["times"].append(now)
                    rec["counts"].append(n)
                if getattr(out, "finished", False):
                    rec["finished"] = True
                return cb(out)

            req.callback = on_output
            return add(req)

        self.keep_prompts = False
        engine_obj.add_request = tapped
        self.engine = engine_obj
        self.inst = InstanceServer(
            ecfg, master_rpc_addr=self.master.rpc_address,
            heartbeat_interval_s=0.5, engine=engine_obj,
        )
        self.inst.start()
        deadline = time.monotonic() + 30.0
        while sum(self.master.scheduler.instance_mgr.counts()) != 1:
            if time.monotonic() > deadline:
                raise StackError("instance did not register with the master")
            time.sleep(0.05)

    @property
    def master_addr(self) -> str:
        return self.master.http_address

    @property
    def instance_addr(self) -> str:
        return self.inst.meta.http_address

    def counters(self) -> Dict[str, float]:
        return parse_metrics(http_get(self.instance_addr, "/metrics"))

    def kernel_report(self) -> Dict[str, str]:
        return dict(self.executor.kernel_report())

    def lowerings(self) -> int:
        return int(self.executor.lowering_count())

    def weights(self):
        """The benchmark's weights as installed (the reference's input)."""
        return self.executor.params

    def stop(self) -> None:
        if self.inst is not None:
            self.inst.stop()
        self.master.stop()
        self.store.close()


def greedy_sample(stack: Stack, prompts: List[List[int]], max_tokens: int) -> List[dict]:
    """Serve `prompts` (token ids) greedily with logprobs through the
    master, concurrently; return served ids (from the tap) and logprobs
    (from the API) per prompt."""
    import threading

    stack.keep_prompts = True
    results: List[Optional[dict]] = [None] * len(prompts)

    def one(i: int) -> None:
        body = {
            "model": stack.name, "prompt": prompts[i], "max_tokens": max_tokens,
            "temperature": 0.0, "logprobs": 1, "ignore_eos": True,
            "stream": False,
        }
        try:
            code, resp = http_post(stack.master_addr, "/v1/completions", body)
            results[i] = {"code": code, "resp": resp}
        except Exception as e:  # reported by the caller as a failed check
            results[i] = {"code": -1, "resp": {"error": repr(e)}}

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
    stack.keep_prompts = False
    out = []
    for i, prompt in enumerate(prompts):
        r = results[i]
        if r is None or r["code"] != 200:
            raise StackError(f"correctness request {i} failed: {r}")
        rec = stack.taps.get(request_key(prompt))
        if rec is None or rec["prompt"] != list(prompt):
            raise StackError(f"correctness request {i} never reached the engine as sent")
        lps = (r["resp"]["choices"][0].get("logprobs") or {}).get("token_logprobs") or []
        out.append({
            "prompt": list(prompt), "served_ids": list(rec["out"]),
            "served_logprobs": [float(x) for x in lps],
        })
    return out


def warm_shapes(stack: Stack, spec: Mapping, vocab: int, seed: int) -> list:
    """Walk the engine through the step programs a cell's traffic can
    reach, through the API alone. The engine compiles one program per
    (prefill rows, padded chunk, prefill context bucket, decode context
    bucket); a mix whose prompts are whole chunks reaches a grid of the
    two context buckets. For each background prompt length (which pins
    the decode bucket while it decodes) one probe prompt, chunk by chunk,
    crosses every prefill bucket. The next background starts while the
    last still decodes. Returns the threads still serving."""
    import numpy as np

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    threads = []
    for n in spec["background_prompts"]:
        ids = rng.integers(0, vocab, size=int(n)).tolist()
        threads.append(_serve_in_background(stack, ids, spec["background_output"]))
        ids = rng.integers(0, vocab, size=int(spec["probe_prompt"])).tolist()
        _post(stack, ids, 1)
    return threads


def start_bridge(stack: Stack, spec: Mapping, vocab: int, seed: int) -> list:
    """One request (the shortest background's prompt length,
    `bridge_output` answers) started as the last act of set-up
    and still decoding when the load generator's first requests arrive.
    A step dispatched by an idle engine, and the step after it, are
    program variants of their own (their inputs come from the host, not
    from the step before), 20 s of compile each in a cold checkout: the
    bridge meets them here, in set-up, whatever the check and the
    reference took, and the traffic never does. Returns its thread."""
    import numpy as np

    if not spec.get("bridge_output"):
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 13]))
    n = int(min(spec["background_prompts"]))
    # three tokens: the step from idle, the step after it, and one more
    return [_serve_in_background(stack, rng.integers(0, vocab, size=n).tolist(),
                                 spec["bridge_output"], tokens=3)]


def _post(stack: Stack, ids: List[int], max_tokens: int) -> None:
    body = {"model": stack.name, "prompt": ids, "max_tokens": int(max_tokens),
            "temperature": 0.0, "ignore_eos": True, "stream": False}
    code, resp = http_post(stack.master_addr, "/v1/completions", body)
    if code != 200:
        raise StackError(f"warm-up request failed: HTTP {code} {resp}")


def _serve_in_background(stack: Stack, ids: List[int], max_tokens: int, tokens: int = 1):
    """Post a request from a thread; return once `tokens` of its tokens are out."""
    import threading

    t = threading.Thread(target=_post, args=(stack, ids, max_tokens), daemon=True)
    t.start()
    key, deadline = request_key(ids), time.monotonic() + 1100.0
    while len((stack.taps.get(key) or {}).get("out", ())) < tokens:
        if time.monotonic() > deadline or not t.is_alive():
            raise StackError("a warm-up request produced no token")
        time.sleep(0.01)
    return t
