"""Encoder fabric (docs/EPD.md): seeded differential + chaos suite.

Proves the fourth cluster plane changes WHERE/WHEN embeddings are
computed, never WHAT the client sees:

  * cached ≡ fresh-encode ≡ legacy-sync byte-identical outputs (greedy
    and seeded sampling), including under `mm_handoff.*` / `encode.dispatch`
    chaos and an encoder crash — 0 failed requests;
  * cross-request micro-batched embeddings ≡ per-item encodes;
  * streamed chunk-boundary adoption in the engine ≡ up-front embedding
    injection (engine-level differential, no HTTP);
  * the legacy path's interleaved-kind ordering regression (outputs must
    map back to their original item positions across flush boundaries);
  * the `XLLM_ENCODER_FABRIC=0` escape hatch serves the legacy path;
  * `_pop_mm_import` reap/wait instruments (satellite).
"""

from __future__ import annotations

import base64
import threading
import time

import numpy as np
import pytest

from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.service import image_processor as ip


# ----------------------------------------------------------- content hash


def test_media_content_hash_keys_on_kind_shape_payload():
    a = ip.media_content_hash("img", [32, 32, 3], "payload")
    assert a == ip.media_content_hash("img", [32, 32, 3], "payload")
    assert len(bytes.fromhex(a)) == 16  # KV-block-hash width
    assert a != ip.media_content_hash("audio", [32, 32, 3], "payload")
    assert a != ip.media_content_hash("img", [32, 16, 3], "payload")
    assert a != ip.media_content_hash("img", [32, 32, 3], "payload2")


def test_scheduler_media_parts_carry_hashes():
    """_expand_media stamps every part with its content key, and a
    re-sent identical payload keys identically (the multi-turn cache-hit
    property)."""
    from types import SimpleNamespace

    from xllm_service_tpu.common.config import ServiceConfig
    from xllm_service_tpu.service.scheduler import Scheduler
    from xllm_service_tpu.tokenizer.chat_template import (
        Message,
        MMContentPart,
    )

    arr = np.random.default_rng(0).random((32, 32, 3)).astype(np.float32)
    url = (
        "data:application/x-raw-f32;shape=32x32x3;base64,"
        + base64.b64encode(arr.tobytes()).decode()
    )

    class _Tok:
        def encode(self, s):
            return [ord(c) % 250 for c in s]

    ns = SimpleNamespace(
        _config=ServiceConfig(mm_tokens_per_media=4),
        _MM_DATA_RE=Scheduler._MM_DATA_RE,
        _MM_DATA2_RE=Scheduler._MM_DATA2_RE,
        _MM_DATA4_RE=Scheduler._MM_DATA4_RE,
        _MM_MARKERS=Scheduler._MM_MARKERS,
        _tokenizer=_Tok(),
        _decode_media_part=lambda p: Scheduler._decode_media_part(ns, p),
    )
    req = SimpleNamespace(
        messages=[Message(
            role="user",
            content=[
                MMContentPart(type="text", text="hi "),
                MMContentPart(type="image", url=url),
            ],
        )],
        prompt="hi <|image|>",
        token_ids=[], mm_positions=[], media_parts=[], mm_grids=[],
    )
    assert Scheduler._expand_media(ns, req) is None
    (p,) = req.media_parts
    assert p["hash"] == ip.media_content_hash("img", [32, 32, 3], p["data"])
    req2 = SimpleNamespace(
        messages=req.messages, prompt="hi <|image|>",
        token_ids=[], mm_positions=[], media_parts=[], mm_grids=[],
    )
    assert Scheduler._expand_media(ns, req2) is None
    assert req2.media_parts[0]["hash"] == p["hash"]


# ------------------------------------------------- embedding LRU + deltas


def test_embedding_lru_events_and_eviction():
    from xllm_service_tpu.runtime.vision_executor import _EmbeddingLRU

    lru = _EmbeddingLRU(2)
    k = [bytes([i]) * 16 for i in range(3)]
    assert lru.get(k[0]) is None and lru.misses == 1
    lru.put(k[0], np.zeros((4, 8), np.float32))
    lru.put(k[1], np.ones((4, 8), np.float32))
    assert lru.get(k[0]) is not None and lru.hits == 1
    lru.put(k[2], np.full((4, 8), 2.0, np.float32))  # evicts k[1] (LRU)
    assert lru.evictions == 1 and lru.get(k[1]) is None
    ev = lru.take_event()
    assert ev.stored_cache == {k[0], k[2]}
    assert ev.removed_cache == {k[1]}
    assert lru.take_event().empty()  # drained
    snap = lru.snapshot_event()
    assert snap.stored_cache == {k[0], k[2]} and not snap.removed_cache


# ------------------------------------------- micro-batcher differentials


@pytest.fixture(scope="module")
def vit_engine():
    from xllm_service_tpu.runtime.vision_executor import EncoderEngine

    eng = EncoderEngine(
        model="vit-tiny", dtype="float32",
        cfg=EngineConfig(
            model="vit-tiny", instance_type="ENCODE",
            encoder_batch_window_ms=25.0,
        ),
    )
    eng.start()
    yield eng
    eng.stop()


def test_micro_batcher_coalesces_cross_request(vit_engine):
    """Concurrent same-kind items from different threads land in ONE
    tower dispatch whose rows are byte-identical to per-item encodes."""
    eng = vit_engine
    rng = np.random.default_rng(1)
    imgs = [rng.random((32, 32, 3), dtype=np.float32) for _ in range(4)]
    ref = [eng.encode(im[None])[0] for im in imgs]
    b0 = eng.metrics.get("xllm_encoder_batches_total").get()
    outs = [None] * 4

    def go(i):
        outs[i] = eng.encode_media("img", imgs[i])

    ts = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for i in range(4):
        np.testing.assert_array_equal(outs[i], ref[i])
    dispatched = eng.metrics.get("xllm_encoder_batches_total").get() - b0
    assert dispatched < 4  # some coalescing happened
    assert eng.metrics.get("xllm_encoder_batched_items_total").get() >= 4


def test_cache_hit_skips_tower_and_feeds_deltas(vit_engine):
    eng = vit_engine
    img = np.random.default_rng(2).random((32, 32, 3), dtype=np.float32)
    key = bytes(range(16))
    eng.take_cache_event()  # drain
    first = eng.encode_media("img", img, key=key)
    h0 = eng.emb_cache.hits
    b0 = eng.metrics.get("xllm_encoder_batches_total").get()
    again = eng.encode_media("img", img, key=key)
    np.testing.assert_array_equal(again, first)  # cached ≡ fresh, bitwise
    assert eng.emb_cache.hits == h0 + 1
    assert eng.metrics.get("xllm_encoder_batches_total").get() == b0
    ev = eng.take_cache_event()
    assert key in ev.stored_cache  # heartbeat delta feeds the fleet index
    snap = eng.cache_snapshot_event()
    assert key in snap.stored_cache  # resync contract


def test_batcher_dedups_identical_keys(vit_engine):
    """Two requests racing the SAME media item share one tower row."""
    eng = vit_engine
    img = np.random.default_rng(3).random((32, 32, 3), dtype=np.float32)
    key = bytes([9]) * 16
    outs = [None, None]

    def go(i):
        outs[i] = eng.encode_media("img", img, key=key)

    i0 = eng.metrics.get("xllm_encoder_batched_items_total").get()
    ts = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    np.testing.assert_array_equal(outs[0], outs[1])
    # Served 2 items (or 1 + a cache hit if the threads missed the same
    # window); never 2 separate tower rows for the same key in one batch.
    assert eng.metrics.get("xllm_encoder_batched_items_total").get() - i0 <= 2


# -------------------------------------------------- master embedding index


def test_encoder_fabric_index_match_prune_resync():
    from xllm_service_tpu.cluster.encoder_fabric import EncoderFabric
    from xllm_service_tpu.common.types import KvCacheEvent

    class _Mgr:
        def get_instance(self, name):
            return None

    fab = EncoderFabric(None, _Mgr())
    h1, h2 = b"a" * 16, b"b" * 16
    fab.record_event("enc0", KvCacheEvent(stored_cache={h1, h2}))
    fab.record_event("enc1", KvCacheEvent(stored_cache={h1}))
    assert fab.match([h1, h2]) == {"enc0": 2, "enc1": 1}
    assert fab.fleet_hit_items == 2 and fab.fleet_total_items == 2
    fab.record_event("enc0", KvCacheEvent(removed_cache={h2}))
    assert fab.match([h2]) == {}
    fab.remove_instance("enc1")
    assert fab.match([h1]) == {"enc0": 1}
    fab.remove_instance("enc0")
    assert fab.match([h1]) == {}
    assert len(fab) == 0
    # hashes_of tolerates legacy parts without hashes
    assert EncoderFabric.hashes_of(
        [{"hash": h1.hex()}, {"shape": [1, 2]}, {"hash": "zz"}]
    ) == [h1]


def test_next_encode_instance_hit_and_queue_scoring():
    from xllm_service_tpu.cluster.instance_mgr import InstanceMgr
    from xllm_service_tpu.common.types import (
        InstanceMetaInfo,
        InstanceType,
        LoadMetrics,
    )
    from xllm_service_tpu.coordination import MemoryStore

    store = MemoryStore(clock=lambda: 0.0)
    mgr = InstanceMgr(store, is_master=lambda: True)
    for i in range(3):
        mgr._register(InstanceMetaInfo(
            name=f"enc{i}", type=InstanceType.ENCODE,
            modalities=["image"],
        ))
    mgr.record_load_metrics_update("enc0", LoadMetrics(0, 0.0))
    mgr.record_load_metrics_update("enc1", LoadMetrics(0, 0.0))
    mgr.record_load_metrics_update("enc2", LoadMetrics(0, 0.0))
    # Cache affinity: the holder wins over idle peers.
    assert mgr.next_encode_instance(
        {"image"}, hit_scores={"enc1": 2}
    ) == "enc1"
    # Queue depth overrides a small hit bonus (HIT_WEIGHT=2: 1 hit = 2
    # queue slots; enc1 at depth 5 loses to an idle peer).
    mgr.record_load_metrics_update("enc1", LoadMetrics(5, 0.0))
    assert mgr.next_encode_instance(
        {"image"}, hit_scores={"enc1": 1}
    ) != "enc1"
    # exclude supports the encode-dispatch re-route.
    got = mgr.next_encode_instance({"image"}, exclude={"enc0", "enc1"})
    assert got == "enc2"
    # Modality filter still applies under scoring.
    assert mgr.next_encode_instance(
        {"audio"}, hit_scores={"enc1": 5}
    ) == ""
    # Fabric off (no scores): round-robin rotation unchanged.
    seen = {mgr.next_encode_instance({"image"}) for _ in range(6)}
    assert seen == {"enc0", "enc1", "enc2"}
    store.close()


# ------------------------------------------------ stream handle semantics


def test_mm_stream_handle_out_of_order_and_idempotent():
    from xllm_service_tpu.api.instance_mm import MMStreamHandle

    h = MMStreamHandle("s", [2, 3, 7, 8], deadline_s=60.0)
    assert h.ready_upto(2)  # no placeholder below 2
    assert not h.ready_upto(4)
    h.land([7, 8], np.ones((2, 4), np.float32))  # item 2 first
    assert not h.ready_upto(4) and not h.complete()
    h.land([2, 3], np.zeros((2, 4), np.float32))
    assert h.complete() and h.ready_upto(100)
    emb, pos = h.assembled()
    assert list(pos) == [2, 3, 7, 8]
    np.testing.assert_array_equal(emb[:2], np.zeros((2, 4)))
    np.testing.assert_array_equal(emb[2:], np.ones((2, 4)))
    h.land([2, 3], np.full((2, 4), 9.0, np.float32))  # idempotent re-land
    emb2, _ = h.assembled()
    np.testing.assert_array_equal(emb, emb2)


def test_mm_stream_handle_desync_and_expiry():
    from xllm_service_tpu.api.instance_mm import MMStreamHandle

    h = MMStreamHandle("s", [0, 1], deadline_s=60.0)
    h.land([5], np.zeros((1, 4), np.float32))  # outside placeholders
    assert h.failed()
    h2 = MMStreamHandle("s2", [0, 1], deadline_s=0.0)
    time.sleep(1.1)
    assert h2.expired() and not h2.complete()


# ------------------------- engine differential: streamed ≡ up-front inject


def test_engine_streamed_adoption_matches_upfront():
    """Chunk-boundary adoption differential: the same prompt served (a)
    with embeddings injected up-front and (b) through an MMStreamHandle
    whose items land WHILE text chunks prefill produces byte-identical
    tokens — and the streamed request is admitted before its embeddings
    finish (text/stage-E overlap actually happened)."""
    from tests.test_engine import Collector, make_engine
    from xllm_service_tpu.api.instance_mm import MMStreamHandle
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest

    eng, ex = make_engine(num_blocks=96, max_seq_len=512)
    # Tight chunk budget: the 96-token prompt prefills in 3 chunks.
    eng.cfg.max_prefill_tokens = 32
    eng.start()
    try:
        rng = np.random.default_rng(11)
        prompt = [int(t) for t in rng.integers(3, 200, size=96)]
        # Placeholders near the END: chunks 0-1 are pure text and must
        # prefill while the "encoder" is still streaming.
        positions = [80, 81, 82, 83, 90, 91, 92, 93]
        for p in positions:
            prompt[p] = 0
        E = ex.cfg.hidden_size
        emb_a = rng.standard_normal((4, E)).astype(np.float32)
        emb_b = rng.standard_normal((4, E)).astype(np.float32)
        upfront = np.concatenate([emb_a, emb_b])
        sp = SamplingParams(temperature=0.0, max_new_tokens=8)

        ref = Collector()
        eng.add_request(EngineRequest(
            request_id="up", prompt_token_ids=list(prompt), sampling=sp,
            callback=ref, mm_embeds=upfront, mm_positions=list(positions),
        ))
        assert ref.finished.wait(60)

        handle = MMStreamHandle("sv", positions, deadline_s=60.0,
                                on_update=eng.wake)
        got = Collector()
        admitted_before_complete = {}

        def feeder():
            # Item 2 (positions 90-93) lands first — out of order — then
            # item 1 after a delay that spans several engine steps.
            time.sleep(0.2)
            handle.land([90, 91, 92, 93], emb_b)
            time.sleep(0.4)
            admitted_before_complete["waiting"] = not bool(
                eng._waiting
            ) or any(
                getattr(x, "req", x).request_id == "st"
                for x in list(eng._waiting)
            )
            handle.land([80, 81, 82, 83], emb_a)

        t = threading.Thread(target=feeder)
        t.start()
        eng.add_request(EngineRequest(
            request_id="st", prompt_token_ids=list(prompt), sampling=sp,
            callback=got, mm_positions=list(positions), mm_stream=handle,
        ))
        assert got.finished.wait(60)
        t.join()
        assert got.tokens == ref.tokens  # streamed ≡ up-front, bitwise
        assert handle.complete()
    finally:
        eng.stop()


def test_engine_streamed_deadline_rejects():
    """A stream that never completes error-finishes the request at the
    deadline (the legacy 503 surface, moved off the HTTP thread) — and
    frees the engine to serve other work."""
    from tests.test_engine import Collector, make_engine
    from xllm_service_tpu.api.instance_mm import MMStreamHandle
    from xllm_service_tpu.common.types import StatusCode
    from xllm_service_tpu.ops.sampling import SamplingParams
    from xllm_service_tpu.runtime.engine import EngineRequest

    eng, _ex = make_engine()
    eng.start()
    try:
        handle = MMStreamHandle("dead", [2, 3], deadline_s=0.5,
                                on_update=eng.wake)
        got = Collector()
        eng.add_request(EngineRequest(
            request_id="dead", prompt_token_ids=[1, 2, 0, 0, 5],
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4),
            callback=got, mm_positions=[2, 3], mm_stream=handle,
        ))
        assert got.finished.wait(30)
        assert got.outputs[-1].status.code == StatusCode.UNAVAILABLE
    finally:
        eng.stop()


# ---------------------- legacy path: interleaved-kind ordering regression


def _dual_tower_engine():
    from xllm_service_tpu.runtime.vision_executor import (
        AudioExecutor,
        EncoderEngine,
        VisionExecutor,
    )

    return EncoderEngine(
        executor=VisionExecutor("vit-tiny", dtype="float32"),
        audio_executor=AudioExecutor("audio-tiny", dtype="float32"),
        cfg=EngineConfig(model="vit-tiny", instance_type="ENCODE"),
    )


class _HStub:
    def __init__(self):
        self.json = None
        self.err = None

    def send_json(self, obj, status=200):
        self.json = obj

    def send_error_json(self, code, msg, **kw):
        self.err = (code, msg)


def test_interleaved_kinds_keep_item_order(monkeypatch):
    """Regression (satellite): audio<->image interleave must map each
    output back to its ORIGINAL item position across flush boundaries —
    the flat embedding stream must equal per-item encodes concatenated
    in request order, for every interleaving."""
    from types import MethodType

    from xllm_service_tpu.api import instance_mm
    from xllm_service_tpu.models.audio import audio_out_tokens

    eng = _dual_tower_engine()
    rng = np.random.default_rng(5)
    imgs = [rng.random((32, 32, 3), dtype=np.float32) for _ in range(2)]
    mels = [
        rng.random(
            (eng.audio_executor.cfg.num_mel_bins,
             eng.audio_executor.cfg.mel_frames), dtype=np.float32
        )
        for _ in range(2)
    ]
    # Per-item reference rows, in request order.
    per_item = [
        eng.encode(imgs[0][None])[0],
        eng.encode_audio(mels[0][None])[0],
        eng.encode(imgs[1][None])[0],
        eng.encode_audio(mels[1][None])[0],
    ]
    want = np.concatenate([r.reshape(-1, r.shape[-1]) for r in per_item])

    def part(arr):
        return {
            "shape": list(arr.shape),
            "data": base64.b64encode(
                np.ascontiguousarray(arr).tobytes()
            ).decode(),
        }

    captured = {}

    def fake_post(addr, route, body, timeout=0):
        captured[route] = body
        return 200, {"ok": True}

    monkeypatch.setattr(instance_mm, "post_json", fake_post)
    monkeypatch.setenv("XLLM_ENCODER_FABRIC", "0")  # legacy path

    shim = instance_mm.MultimodalMixin.__new__(
        type("S", (instance_mm.MultimodalMixin,), {})
    )
    shim.engine = eng
    shim.cfg = eng.cfg
    shim.name = "enc-test"
    n_tok = (
        eng.executor.cfg.out_tokens * 2
        + audio_out_tokens(eng.audio_executor.cfg.mel_frames) * 2
    )
    h = _HStub()
    shim._handle_encode = MethodType(
        instance_mm.MultimodalMixin._handle_encode, shim
    )
    shim._handle_encode(h, {
        "service_request_id": "ord",
        "parts": [part(imgs[0]), part(mels[0]),
                  part(imgs[1]), part(mels[1])],
        "positions": list(range(n_tok)),
        "target": "127.0.0.1:1",
    })
    assert h.err is None, h.err
    body = captured["/mm/import"]
    got = np.frombuffer(
        base64.b64decode(body["embeds"]), np.float32
    ).reshape(body["count"], body["dim"])
    np.testing.assert_array_equal(got, want)


# --------------------------------------- mm import reap/wait instruments


def test_mm_import_reap_and_wait_instruments():
    from xllm_service_tpu.api import instance_mm
    from xllm_service_tpu.obs import MetricsRegistry

    shim = instance_mm.MultimodalMixin.__new__(
        type("S", (instance_mm.MultimodalMixin,), {})
    )
    shim.metrics = MetricsRegistry()
    shim.cfg = EngineConfig()
    shim.name = "reap-test"
    shim.engine = None
    shim._init_mm()
    # An orphaned import (its waiter died) ages past the TTL...
    emb = np.zeros((2, 4), np.float32)
    shim._mm_imports["orphan"] = (emb, [0, 1], time.monotonic() - 1e6)
    h = _HStub()
    shim._handle_mm_import(h, {
        "service_request_id": "fresh",
        "count": 2, "dim": 4,
        "embeds": base64.b64encode(emb.tobytes()).decode(),
        "positions": [0, 1],
    })
    assert h.json == {"ok": True}
    assert shim.metrics.get("xllm_mm_import_reaped_total").get() == 1
    assert "orphan" not in shim._mm_imports
    # ...and _pop_mm_import observes its wait either way.
    assert shim._pop_mm_import("fresh", timeout=1.0) is not None
    assert shim._pop_mm_import("never", timeout=0.05) is None
    hist = shim.metrics.get("xllm_mm_import_wait_ms")
    assert hist is not None
    _counts, _sum, n = hist._only().snapshot()
    assert n == 2


# ----------------------------------------------------- cluster e2e suites


def _build_stack(n_encoders=2, encoder_engines=None):
    from xllm_service_tpu.api import Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    from tests.test_api_e2e import wait_until

    store = MemoryStore(clock=lambda: 0.0)
    master = Master(
        ServiceConfig(
            host="127.0.0.1", http_port=0, rpc_port=0,
            heartbeat_interval_s=0.2, master_lease_ttl_s=1.0,
            load_balance_policy="RR", block_size=16,
            mm_tokens_per_media=4,  # == vit-tiny out_tokens
            mm_image_processor="siglip", mm_image_size=32,
        ),
        store=store,
    )
    master.start()
    lm = InstanceServer(
        EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=16,
            num_blocks=64, max_running_requests=4, max_seq_len=256,
            prefill_buckets=[64, 128], instance_name="fab-mix",
            instance_type="MIX",
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.2,
    )
    lm.start()
    encoders = []
    for i in range(n_encoders):
        eng = None
        if encoder_engines is not None:
            eng = encoder_engines[i]
        enc = InstanceServer(
            EngineConfig(
                model="vit-tiny", instance_name=f"fab-enc{i}",
                instance_type="ENCODE", encoder_batch_window_ms=5.0,
            ),
            master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.2,
            engine=eng,
        )
        enc.start()
        encoders.append(enc)
    assert wait_until(
        lambda: master.scheduler.instance_mgr.counts()[2] == n_encoders
        and sum(master.scheduler.instance_mgr.counts()) == 1 + n_encoders
    )
    return master, lm, encoders, store


def _teardown_stack(master, lm, encoders, store):
    for enc in encoders:
        try:
            enc.stop()
        except Exception:
            pass
    lm.stop()
    master.stop()
    store.close()


def _ask(master, img, seed=None, max_tokens=6):
    from tests.test_api_e2e import http_post

    url = (
        "data:application/x-raw-f32;shape=32x32x3;base64,"
        + base64.b64encode(np.ascontiguousarray(img).tobytes()).decode()
    )
    content = [
        {"type": "text", "text": "describe "},
        {"type": "image_url", "image_url": {"url": url}},
    ]
    body = {
        "model": "llama3-tiny",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0 if seed is None else 0.8,
    }
    if seed is not None:
        body["seed"] = seed
    code, resp = http_post(
        master.http_address, "/v1/chat/completions", body, timeout=180.0
    )
    assert code == 200, resp
    return resp["choices"][0]["message"]["content"]


def test_encoder_fabric_differential_e2e(monkeypatch):
    """One stack, many differentials (compiles amortized): fresh ≡
    cached ≡ chaos-fallback ≡ legacy-sync outputs byte-identical; cache
    hits > 0 on a re-sent image; encoder-kill re-route completes with 0
    failed requests; the escape hatch serves the legacy path."""
    from xllm_service_tpu.common import faults

    monkeypatch.delenv("XLLM_ENCODER_FABRIC", raising=False)
    img = np.random.default_rng(21).random((32, 32, 3)).astype(np.float32)
    master, lm, encoders, store = _build_stack(n_encoders=2)
    try:
        # --- fresh encode (fabric on, streamed session)
        out1 = _ask(master, img)
        sessions = sum(
            s.metrics.get("xllm_mm_stream_sessions_total").get()
            for s in encoders
        )
        assert sessions > 0  # the streamed path served, not a fallback
        assert lm.metrics.get("xllm_mm_stream_chunks_landed_total").get() > 0
        # --- re-sent media: embedding cache serves, output identical
        out2 = _ask(master, img)
        assert out2 == out1
        hits = sum(
            e.engine.emb_cache.hits for e in encoders
        )
        assert hits > 0  # the tower was skipped on the re-send
        # --- seeded sampling differential
        s1 = _ask(master, img, seed=7)
        s2 = _ask(master, img, seed=7)
        assert s1 == s2
        # --- chaos: dropped chunk send => abort => monolithic fallback
        faults.install_spec({"rules": [
            {"point": "mm_handoff.send", "action": "drop", "count": 1},
        ]})
        out3 = _ask(master, img)
        assert out3 == out1
        # --- chaos: receiver drop => chunk POST fails => same fallback
        faults.install_spec({"rules": [
            {"point": "mm_handoff.recv", "action": "drop", "count": 1},
        ]})
        out4 = _ask(master, img)
        assert out4 == out1
        faults.clear()
        aborts = sum(
            s.metrics.get("xllm_mm_stream_aborts_total").get()
            for s in encoders
        )
        assert aborts >= 2
        # --- chaos: encode dispatch to enc0 fails => re-route to enc1
        faults.install_spec({"rules": [
            {"point": "encode.dispatch", "action": "error",
             "match": "fab-enc0", "count": 4},
        ]})
        out5 = _ask(master, img)
        assert out5 == out1
        faults.clear()
        # --- encoder crash mid-fleet: request still completes via the
        # surviving encoder (third-role failover; 0 failed requests)
        encoders[0].crash()
        out6 = _ask(master, img)
        assert out6 == out1
        # --- escape hatch: legacy synchronous path, byte-identical. It
        # has no re-route: wait until the breaker has taken the crashed
        # encoder out of the rotation (stale heartbeats), or the
        # round-robin may still pick it (whether it did hung on how long
        # the requests above took).
        from tests.test_api_e2e import wait_until

        assert wait_until(
            lambda: master.scheduler.instance_mgr.health_state(
                encoders[0].name
            ) != "healthy",
            timeout=10.0,
        )
        monkeypatch.setenv("XLLM_ENCODER_FABRIC", "0")
        out7 = _ask(master, img)
        assert out7 == out1
        monkeypatch.delenv("XLLM_ENCODER_FABRIC")
        # --- fleet index saw the cached item (heartbeat deltas landed)
        assert wait_until(
            lambda: len(master.scheduler.encoder_fabric) > 0, timeout=5.0
        )
    finally:
        _teardown_stack(master, lm, encoders, store)


def test_mixed_hatch_streaming_encoder_legacy_prefill(monkeypatch):
    """Heterogeneous config hardening: a streaming encoder feeding a
    prefill whose OWN hatch is off (legacy blocking `_pop_mm_import`)
    still serves — the commit handler assembles the stashed per-item
    chunks into a monolithic import for the blocked waiter."""
    from xllm_service_tpu.api import Master
    from xllm_service_tpu.api.instance import InstanceServer
    from xllm_service_tpu.common.config import ServiceConfig
    from xllm_service_tpu.coordination import MemoryStore

    from tests.test_api_e2e import wait_until

    monkeypatch.delenv("XLLM_ENCODER_FABRIC", raising=False)
    store = MemoryStore(clock=lambda: 0.0)
    master = Master(
        ServiceConfig(
            host="127.0.0.1", http_port=0, rpc_port=0,
            heartbeat_interval_s=0.2, master_lease_ttl_s=1.0,
            load_balance_policy="RR", block_size=16,
            mm_tokens_per_media=4,
        ),
        store=store,
    )
    master.start()
    lm = InstanceServer(
        EngineConfig(
            model="llama3-tiny", dtype="float32", block_size=16,
            num_blocks=64, max_running_requests=4, max_seq_len=256,
            prefill_buckets=[64, 128], instance_name="mix-legacy",
            instance_type="MIX",
            enable_encoder_fabric=False,  # prefill side: legacy waiter
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.2,
    )
    enc = InstanceServer(
        EngineConfig(
            model="vit-tiny", instance_name="enc-streaming",
            instance_type="ENCODE",  # encoder side: streams
        ),
        master_rpc_addr=master.rpc_address, heartbeat_interval_s=0.2,
    )
    lm.start()
    enc.start()
    try:
        assert wait_until(
            lambda: sum(master.scheduler.instance_mgr.counts()) == 2
        )
        img = np.random.default_rng(33).random((32, 32, 3)).astype(
            np.float32
        )
        out1 = _ask(master, img)
        out2 = _ask(master, img)
        assert out1 == out2
    finally:
        enc.stop()
        lm.stop()
        master.stop()
        store.close()


def test_encoder_fabric_off_stack_matches(monkeypatch):
    """A whole stack running with the fabric disabled (config-level, no
    env hatch) produces the same bytes for the same media request."""
    monkeypatch.setenv("XLLM_ENCODER_FABRIC", "0")
    img = np.random.default_rng(21).random((32, 32, 3)).astype(np.float32)
    master, lm, encoders, store = _build_stack(n_encoders=1)
    try:
        off1 = _ask(master, img)
        off2 = _ask(master, img)
        assert off1 == off2
        # No sessions were opened with the hatch off.
        assert all(
            s.metrics.get("xllm_mm_stream_sessions_total").get() == 0
            for s in encoders
        )
    finally:
        _teardown_stack(master, lm, encoders, store)
    # Cross-check against a fabric-on stack on the SAME payload.
    monkeypatch.delenv("XLLM_ENCODER_FABRIC")
    master, lm, encoders, store = _build_stack(n_encoders=1)
    try:
        on1 = _ask(master, img)
        assert on1 == off1  # legacy-sync ≡ fabric, byte-identical
    finally:
        _teardown_stack(master, lm, encoders, store)
