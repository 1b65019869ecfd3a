"""KV-handoff plane of the instance server (PD disaggregation).

Split from api/instance.py (round-3 de-monolith): everything that moves
prefilled KV to a decode peer — the transfer worker loop, the handoff
sender (ack-ordered send with local-peer direct import, pull-plane offer,
bytes-plane fallback), the pipelined streaming session (per-prefill-chunk
KV export overlapped with the remaining prefill — docs/PD_DISAGGREGATION.md),
the /kv/import receiver, and decode-side admission. Mixed into
InstanceServer (api/instance.py); `self` is the server.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from xllm_service_tpu.api.http_utils import HttpJsonApi, post_bytes
from xllm_service_tpu.api.instance_registry import _LOCAL_INSTANCES, _LOCAL_MU
from xllm_service_tpu.api.protocol import (
    handoff_from_parts,
    handoff_header,
    kv_frame_array,
    kv_frame_split,
    kv_frame_to_bytes,
    resolve_kv_dtype,
    sampling_from_body,
)
from xllm_service_tpu.common import faults
from xllm_service_tpu.common.shortuuid import generate_uuid
from xllm_service_tpu.parallel.shard_wire import ShardedKV, to_host
from xllm_service_tpu.common.types import RequestOutput, Status, StatusCode
from xllm_service_tpu.tokenizer.tokenizer import IncrementalDetokenizer

logger = logging.getLogger("xllm_service_tpu.api.instance")

def _host_kv(kv):
    """Device payload → host wire form with NO cross-shard gather: a
    tp-sharded export becomes per-shard pieces (ShardedKV) that the
    bytes plane serializes shard-by-shard (docs/SHARDING.md); everything
    else is the flat np.asarray the old wire carried."""
    return to_host(kv)


def _device_resident(kv) -> bool:
    """True when `kv` still lives on device (pull-plane eligible); host
    np payloads AND per-shard host pieces (ShardedKV) ride the bytes
    plane."""
    return kv is not None and not isinstance(kv, (np.ndarray, ShardedKV))


# Receiver session table bounds: stale sessions (sender died mid-stream
# without an abort) are reaped past the TTL; the table itself is capped so
# a misbehaving sender cannot grow it without bound.
_KV_SESSION_TTL_S = 300.0
_KV_SESSION_CAP = 64


def _pd_streaming_enabled(cfg) -> bool:
    """Pipelined-handoff escape hatch: XLLM_PD_STREAMING=1|0 overrides
    EngineConfig.enable_pd_streaming either way. Read per request so the
    hatch can flip on a live instance."""
    env = os.environ.get("XLLM_PD_STREAMING", "")
    if env == "1":
        return True
    if env == "0":
        return False
    return bool(getattr(cfg, "enable_pd_streaming", True))


class _KVStreamSession:
    """Sender side of one pipelined PD handoff (docs/PD_DISAGGREGATION.md).

    The engine's chunked-prefill loop calls `send_chunk` (engine thread)
    after each partial chunk; the chunk's blocks are handed to the
    transfer worker pool and migrate — direct import for a colocated peer,
    pull-plane offer or bytes POST for a remote one — WHILE the next chunk
    is still prefilling. Chunk delivery is order-independent (the receiver
    commits content-addressed blocks into its prefix cache), so each
    chunk's offer completes asynchronously; the commit waits only for the
    session to drain. Any failure aborts the session: the engine then
    exports the FULL payload in the commit (monolithic retry — the blocks
    are still held at `_handoff` time), and blocks a failed chunk did
    deliver are merely unused cache entries on the peer.
    """

    def __init__(
        self, owner, srid: str, decode_name: str, epoch: int = 0,
        trace: Optional[Dict[str, Any]] = None,
    ):
        self.owner = owner
        self.srid = srid
        self.decode_name = decode_name
        # Fencing epoch of the master that routed this PD pair: the
        # session OPEN carries it so the decode peer's fence rejects KV
        # control traffic descending from a deposed master's dispatch.
        self.epoch = int(epoch or 0)
        # Trace context of the dispatching request: rides the session
        # OPEN so the decode peer's chunk-landing spans join the same
        # cross-process timeline.
        self.trace = trace if isinstance(trace, dict) else None
        self.session_id = generate_uuid(16)
        self.aborted = False
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._pending = 0
        self._next_idx = 0
        self.chunks_sent = 0
        self.chunks_delivered = 0
        self.blocks_delivered = 0
        # Admit-time routing: the master picked the decode peer before the
        # prefill was dispatched, so the peer address resolves HERE (HTTP
        # serving thread) and session-open can precede prefill-done without
        # a directory lookup on the engine thread. A colocated peer skips
        # the lookup entirely.
        self._addr = ""
        if owner._local_peer(decode_name) is None:
            try:
                self._addr = owner._resolve_instance_addr(decode_name)
            except Exception:
                self._addr = ""
        self._offer_session = None  # lazy: one per session, pull plane only
        # Set once chunk 0 (the session OPEN) is delivered: later chunks
        # wait on it so a worker racing chunk 1 ahead of the open can't
        # get refused by the receiver's session gate.
        self._opened = threading.Event()

    # ------------------------------------------------------ engine thread

    def send_chunk(self, chunk) -> bool:
        """Accept one KVStreamChunk for delivery (engine thread: must not
        block on the network — the actual send runs on the transfer pool).
        Returns False once the session is aborted; the engine then stops
        streaming and the final handoff goes monolithic."""
        if self.aborted:
            return False
        try:
            faults.point(
                "kv_stream.send",
                instance=self.owner.name, peer=self.decode_name,
                srid=self.srid, session=self.session_id,
                chunk=self._next_idx,
            )
        except faults.FaultInjected as fi:
            self._fail(str(fi))
            return False
        kv = chunk.kv
        # TOCTOU guard (same rule as the monolithic send): with no local
        # peer and no transfer server at all, the export would pin HBM
        # through the queue wait for no reason — copy to host now. A
        # bytes-plane-CACHED peer is deliberately NOT converted here:
        # that np.asarray is a blocking device sync on the engine thread,
        # and the worker converts at serialization anyway (queue pinning
        # stays bounded at the lane's maxsize).
        if (
            _device_resident(kv)
            and self.owner._local_peer(self.decode_name) is None
            and self.owner._kv_transfer is None
        ):
            kv = _host_kv(kv)
        idx = self._next_idx
        self._next_idx += 1
        with self._cv:
            self._pending += 1
        header_meta = {
            "idx": idx,
            "start_block": int(chunk.start_block),
            "expected_blocks": int(chunk.total_blocks_hint),
            "prompt_tokens": int(chunk.prompt_tokens),
        }
        hashes = list(chunk.block_hashes)
        try:
            # NON-blocking put on the DEDICATED stream lane (instance.py
            # _stream_q), unlike the monolithic path's backpressure:
            # send_chunk runs mid-prefill on the engine thread and a
            # streaming request multiplies queue traffic ~chunks-per-
            # prompt-fold, so one stuck decode peer can only saturate this
            # lane — the session then degrades to the monolithic fallback
            # (put_nowait -> abort) and neither the engine thread nor the
            # monolithic transfer pool ever stalls on a chunk's behalf.
            self.owner._stream_q.put_nowait(
                lambda: self._deliver(header_meta, hashes, kv)
            )
        except queue.Full:
            with self._cv:
                self._pending -= 1
                self._cv.notify_all()
            self._fail("transfer queue saturated")
            return False
        except BaseException:
            with self._cv:
                self._pending -= 1
                self._cv.notify_all()
            raise
        self.chunks_sent += 1
        return True

    # ---------------------------------------------------- transfer worker

    def _deliver(self, meta: Dict[str, Any], hashes: List[bytes], kv) -> None:
        try:
            if self.aborted:
                return
            peer = self.owner._local_peer(self.decode_name)
            if peer is not None:
                # Colocated peer: direct in-process landing, KV stays a
                # device array end-to-end (ICI-path analog).
                if not hasattr(peer.engine, "import_kv_blocks"):
                    self._fail("local peer engine has no streaming import")
                    return
                peer.engine.import_kv_blocks(hashes, kv)
                self._mark_delivered(len(hashes))
                self._opened.set()
                return
            # The receiver refuses chunks for a session it never opened —
            # a worker racing chunk N ahead of the open must wait for
            # chunk 0's ack (the event also sets on abort, so a failed
            # open releases the waiters immediately).
            if meta["idx"] > 0 and not self._opened.wait(30.0):
                self._fail("session open never completed")
                return
            if self.aborted:
                return
            # Mid-session TOCTOU: the colocated peer this chunk was
            # enqueued for may have deregistered since. With no pull plane
            # the payload must ride host bytes per-chunk — copy NOW, don't
            # strand the session.
            if _device_resident(kv) and self.owner._kv_transfer is None:
                kv = _host_kv(kv)
            addr = self._addr or self.owner._resolve_instance_addr(
                self.decode_name
            )
            if not addr:
                self._fail(f"decode instance {self.decode_name} unknown")
                return
            self._addr = addr
            err = self._post_chunk(addr, meta, hashes, kv)
            if err:
                self._fail(err)
            else:
                self._mark_delivered(len(hashes))
                self._opened.set()
        except Exception as e:  # noqa: BLE001 — session must fail closed
            self._fail(f"chunk delivery failed: {e}")
        finally:
            with self._cv:
                self._pending -= 1
                self._cv.notify_all()

    def _post_chunk(
        self, addr: str, meta: Dict[str, Any], hashes: List[bytes], kv
    ) -> str:
        """POST one chunk to the remote peer; '' on success. Chunk 0 is the
        session OPEN (carries the reservation hint). Delivery rides the
        shared _post_kv_frame protocol, with the session's offer registry
        (bulk-retract on abort) and a 409 session refusal treated as
        final — a bytes retry cannot fix a refused reservation."""
        header: Dict[str, Any] = {
            "kv_stream": {
                "id": self.session_id,
                "op": "open" if meta["idx"] == 0 else "chunk",
                **meta,
            },
            "service_request_id": self.srid,
            "block_hashes": [b.hex() for b in hashes],
        }
        if meta["idx"] == 0 and self.epoch:
            # Epoch fence on the /kv/import control plane: the session
            # OPEN is the admission decision (reservation), so it is the
            # message the receiver must be able to reject as stale.
            header["master_epoch"] = self.epoch
        if meta["idx"] == 0 and self.trace:
            header["trace"] = self.trace
        if self._offer_session is None and self.owner._kv_transfer is not None:
            self._offer_session = self.owner._kv_transfer.open_offer_session()
        return self.owner._post_kv_frame(
            addr, header, kv,
            offer_session=self._offer_session, final_codes=(409,),
        )

    def _mark_delivered(self, n_blocks: int) -> None:
        with self._mu:  # concurrent _deliver workers of one session
            self.chunks_delivered += 1
            self.blocks_delivered += n_blocks
        m = getattr(self.owner, "_m_kv_stream_chunks", None)
        if m is not None:
            m.inc()
        _span = getattr(self.owner, "_span", None)
        if _span is not None:
            _span(
                self.srid, "kv_chunk_sent",
                blocks=n_blocks, session=self.session_id,
                peer=self.decode_name,
            )

    def _fail(self, reason: str) -> None:
        with self._mu:
            if self.aborted:
                return
            self.aborted = True
        logger.warning(
            "KV stream session %s (%s -> %s) aborted: %s — commit falls "
            "back to the monolithic payload",
            self.session_id, self.owner.name, self.decode_name, reason,
        )
        m = getattr(self.owner, "_m_kv_stream_aborts", None)
        if m is not None:
            m.inc()
        self._opened.set()  # release any worker waiting on the open
        if self._offer_session is not None:
            # Outstanding offers may still be mid-pull: grace-retract.
            self._offer_session.retract_all_later()
        self._notify_peer_abort()

    def _notify_peer_abort(self) -> None:
        """Best-effort peer notification so its session entry (and its
        soft block reservation) clears before the TTL reap. On a
        dedicated short-lived thread: the stream lane may be SATURATED —
        that's a common abort cause — and a dropped notify would let
        dead sessions pile toward the receiver's cap, 409ing fresh
        sessions for up to the whole TTL."""
        if not self._addr:
            return
        payload = kv_frame_to_bytes(
            {
                "kv_stream": {"id": self.session_id, "op": "abort"},
                "service_request_id": self.srid,
            }
        )
        addr = self._addr

        def _notify():
            try:
                post_bytes(addr, "/kv/import", payload, timeout=5.0)
            except Exception:
                pass

        threading.Thread(
            target=_notify,
            name=f"kv-stream-abort-{self.session_id[:8]}",
            daemon=True,
        ).start()

    def dispose(self) -> None:
        """The request ended WITHOUT a handoff (cancel / reject / EOS on
        the very first token): stop further sends, drop offer keepalives,
        and clear the peer's session entry ahead of the TTL reap — 64
        cancelled streams inside one TTL would otherwise pin the
        receiver's session cap and 409 every fresh session. Not counted
        as an abort: nothing degraded, there is simply no commit coming."""
        with self._mu:
            if self.aborted:
                return
            self.aborted = True
        self._opened.set()
        if self._offer_session is not None:
            self._offer_session.retract_all_later()
        if self.chunks_sent:
            self._notify_peer_abort()

    # ------------------------------------------------------------- commit

    def wait_drained(self, timeout: float = 30.0) -> bool:
        """Block until every enqueued chunk job finished (delivered or
        failed) — the commit must not race its own session's tail."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True

    def close(self) -> None:
        """Commit delivered (or request finished without a handoff): drop
        any offer keepalives still alive. A chunk job still in flight
        (wait_drained timed out) may have a peer MID-PULL on its offer —
        those get the grace window instead of an immediate retract, which
        could free the device buffer under the pull."""
        if self._offer_session is None:
            return
        with self._cv:
            pending = self._pending
        if pending > 0:
            self._offer_session.retract_all_later()
        else:
            self._offer_session.retract_all()


class KVHandoffMixin:
    def _init_kv_handoff(self) -> None:  # graftlint: init-only
        """Streaming-session state + handoff observability. Called from
        InstanceServer.__init__ once self.metrics exists; the series land
        in the instance exposition next to the engine's."""
        from xllm_service_tpu.obs import LATENCY_BUCKETS_MS

        # Receiver session table: sid -> {ts, expected, chunks, blocks}.
        self._kv_sessions: Dict[str, Dict[str, Any]] = {}  # guarded by: self._kv_sessions_mu
        self._kv_sessions_mu = threading.Lock()
        # Overlap accounting: numerator = full blocks that migrated through
        # stream chunks (delivered before prefill-done), denominator = ALL
        # migrated full blocks (streamed + commit-carried, monolithic
        # handoffs included).
        self._kv_stream_blocks_streamed = 0
        self._kv_mig_blocks_total = 0
        self._kv_stats_mu = threading.Lock()  # transfer-pool writers
        # (mode, stall_ms) ring for bench_serving --pd phase snapshots.
        self._kv_stall_samples: collections.deque = collections.deque(
            maxlen=1024
        )
        self._m_kv_stream_chunks = self.metrics.counter(
            "xllm_kv_stream_chunks_total",
            "Pipelined-handoff chunks delivered to decode peers (sender "
            "side)",
        )
        self._m_kv_stream_landed = self.metrics.counter(
            "xllm_kv_stream_chunks_landed_total",
            "Pipelined-handoff chunks accepted for landing into the local "
            "prefix cache (receiver side; landing runs on the engine "
            "thread — failures there count in "
            "xllm_engine_kv_chunk_land_errors_total)",
        )
        self._m_kv_stream_aborts = self.metrics.counter(
            "xllm_kv_stream_aborts_total",
            "Streaming handoff sessions aborted (commit fell back to the "
            "monolithic payload)",
        )
        self._m_kv_stall = self.metrics.histogram(
            "xllm_kv_handoff_stall_ms",
            "Prefill-done to decode-peer admission: master first-token ack "
            "wait + residual KV delivery (the PD critical-path stall)",
            buckets=LATENCY_BUCKETS_MS,
        )
        self.metrics.gauge(
            "xllm_kv_stream_overlap_frac",
            "Fraction of migrated full KV blocks that left before "
            "prefill-done (streamed chunks over all migrated blocks)",
        ).set_function(
            lambda: self._kv_stream_blocks_streamed
            / max(self._kv_mig_blocks_total, 1)
        )

    def _open_kv_stream(
        self, srid: str, decode_name: str, epoch=None, trace=None
    ) -> Optional[_KVStreamSession]:
        """Create the pipelined-handoff session for a PD-split request (or
        None when the escape hatch disables streaming). Costless for
        single-chunk prompts: the engine only streams on PARTIAL prefill
        chunks, so an unused session never opens on the wire. `epoch` is
        the dispatching master's fencing epoch, carried on the session
        OPEN so the decode peer can reject deposed-master control traffic."""
        if not _pd_streaming_enabled(self.cfg):
            return None
        try:
            epoch = int(epoch or 0)
        except (TypeError, ValueError):
            epoch = 0
        return _KVStreamSession(
            self, srid, decode_name, epoch=epoch, trace=trace
        )

    def _transfer_loop(self, q=None) -> None:
        q = q if q is not None else self._transfer_q
        while True:
            job = q.get()
            if job is None:
                return
            try:
                job()
            except Exception:
                logger.exception("KV transfer job failed")

    def _peer_on_bytes_plane(self, decode_name: str) -> bool:
        """True when the peer's RESOLVED address is capability-cached onto
        the bytes plane — a device payload queued for it would pin HBM for
        nothing (an unresolved peer stays device-resident optimistically;
        the first rejected pull fixes the cache)."""
        addr = self._peer_addrs.get(decode_name, "")
        return bool(addr) and addr in self._peer_no_pull

    def _resolve_instance_addr(self, name: str) -> str:
        addr = self._peer_addrs.get(name)
        if addr:
            return addr
        meta = self._master.instance_info(name) if self._master else None
        if meta is None:
            return ""
        self._peer_addrs[name] = meta.http_address
        return meta.http_address

    def _make_handoff_sender(
        self,
        srid: str,
        decode_name: str,
        body: Dict,
        detoks: Optional[Dict[int, IncrementalDetokenizer]] = None,
        seed: Optional[int] = None,
        respond_via_self: bool = False,
        kv_stream: Optional[_KVStreamSession] = None,
    ):
        sampling_fields = {
            k: body[k]
            for k in (
                "max_tokens", "max_completion_tokens", "temperature",
                "top_p", "top_k", "seed", "logprobs", "top_logprobs",
                "ignore_eos", "presence_penalty", "frequency_penalty",
                "logit_bias", "min_p",
            )
            if k in body
        }
        rf = body.get("response_format")
        rf = rf if isinstance(rf, dict) else {}
        guided_mode = {
            "json_object": "json", "json_schema": "json_schema"
        }.get(rf.get("type"))
        guided_schema = None
        if guided_mode == "json_schema":
            js = rf.get("json_schema")
            guided_schema = (
                js.get("schema") if isinstance(js, dict) else None
            )
        # adapter travels by NAME: rows are executor-local
        lora_name = (
            body.get("model")
            if body.get("model") in getattr(self, "lora_names", {})
            else ""
        )
        if seed is not None:
            # Forward the RESOLVED seed (possibly drawn at random for an
            # unseeded request) so the decode peer continues the same
            # RNG stream instead of drawing its own.
            sampling_fields["seed"] = seed

        def transfer(handoff, t_pf_done: float) -> None:
            # Runs on the transfer thread (never the engine thread): waits
            # for the master to ack the first-token push, then POSTs the KV
            # payload to the decode peer. The engine already released the
            # sequence's slot and blocks before enqueueing this job, so a
            # slow master/peer delays only this handoff, not the engine.
            #
            # TOCTOU guard: send() kept the KV device-resident because a
            # local peer existed at enqueue time; if that peer deregistered
            # since, copy to host NOW — before the ack wait below — so a
            # device export never sits pinned in HBM through it. With the
            # pull plane enabled, device-residency through the ack wait is
            # the point (the peer pulls from device memory), so the copy
            # is skipped.
            if (
                _device_resident(handoff.kv)
                and self._local_peer(decode_name) is None
                and (
                    self._kv_transfer is None
                    or self._peer_on_bytes_plane(decode_name)
                )
            ):
                handoff = dataclasses.replace(
                    handoff, kv=_host_kv(handoff.kv)
                )
            with self._push_acked_mu:
                acked = self._push_acked.get(srid)
            err = ""
            # Cross-instance ordering: the first token must be acked by the
            # master before the decode peer can start pushing, or a client
            # could see token 2 before token 1. The event stays in the dict
            # until AFTER the wait — popping first would race the ack.
            if acked is not None and not acked.wait(60.0):
                err = "first-token push never acked by master"
            with self._push_acked_mu:
                self._push_acked.pop(srid, None)
            if not err:
                extra = {
                    "service_request_id": srid,
                    "sampling": sampling_fields,
                    "guided": guided_mode,
                    "guided_schema": guided_schema,
                    "lora": lora_name,
                    "offline": bool(body.get("offline", False)),
                }
                if body.get("master_epoch"):
                    # Epoch fence rides the handoff control header too:
                    # the decode peer must reject a commit descending
                    # from a deposed master's dispatch.
                    extra["master_epoch"] = body["master_epoch"]
                if isinstance(body.get("trace"), dict):
                    # Trace context follows the request across the PD
                    # boundary: the decode peer's admission span joins
                    # the dispatching request's timeline.
                    extra["trace"] = body["trace"]
                if kv_stream is not None and kv_stream.chunks_sent:
                    # Streamed session: the commit trails its own chunks.
                    # Blocks land order-independently at the peer, but a
                    # commit overtaking an in-flight chunk would miss its
                    # prefix match and recompute for nothing.
                    drained = kv_stream.wait_drained(30.0)
                    if (
                        not drained or kv_stream.aborted
                    ) and handoff.kv_start_block > 0:
                        # A chunk died AFTER the engine built the
                        # tail-only payload (the full export is gone with
                        # the engine's blocks): the commit still lands and
                        # the peer recomputes the hole — byte-identical,
                        # just slower. Surface it; the overlap accounting
                        # below counts only blocks actually delivered.
                        logger.warning(
                            "KV stream session %s lost chunks after the "
                            "commit was built (drained=%s aborted=%s); "
                            "decode peer will recompute the gap",
                            kv_stream.session_id, drained,
                            kv_stream.aborted,
                        )
                    extra["kv_stream"] = {
                        "id": kv_stream.session_id,
                        "op": "commit",
                        "chunks": kv_stream.chunks_delivered,
                    }
                if respond_via_self:
                    # Alternate topology: decode relays its generations
                    # back through this (prefill) instance.
                    extra["respond_addr"] = self.address
                # Detokenizer carry-over: the decode peer continues from
                # this side's exact byte/char position.
                d0 = (detoks or {}).get(0)
                if d0 is not None:
                    ids, emitted = d0.export_state()
                    extra["detok_ids"] = ids
                    extra["detok_emitted"] = emitted
                peer = self._local_peer(decode_name)
                if peer is not None:
                    # Colocated peer: direct in-process import, no
                    # serialization (ICI-path analog).
                    try:
                        peer._admit_import(handoff, extra)
                    except Exception as e:
                        err = f"local decode peer import failed: {e}"
                else:
                    addr = self._resolve_instance_addr(decode_name)
                    if not addr:
                        err = f"decode instance {decode_name} unknown"
                    else:
                        err = self._post_handoff(addr, handoff, extra)
            if not err:
                # Handoff complete: this instance is done with the request
                # (the decode peer owns cancellation from here — including
                # its reconcile-manifest entry).
                with self._srid_mu:
                    self._srid_map.pop(srid, None)
                    self._srid_forget_locked(srid)
                # Stall + overlap observability: the stall spans prefill-
                # done to decode-peer admission; the overlap counters feed
                # the xllm_kv_stream_overlap_frac gauge. Only blocks the
                # session actually DELIVERED count as streamed — a chunk
                # lost after the tail-only payload was built must not
                # inflate the overlap fraction.
                streamed = int(getattr(handoff, "kv_start_block", 0) or 0)
                if kv_stream is not None:
                    streamed = min(streamed, kv_stream.blocks_delivered)
                stall_ms = (time.monotonic() - t_pf_done) * 1000
                self._m_kv_stall.observe(stall_ms)
                self._kv_stall_samples.append(
                    ("streamed" if streamed > 0 else "mono", stall_ms)
                )
                self._span(
                    srid, "handoff_commit",
                    peer=decode_name, stall_ms=round(stall_ms, 3),
                    streamed_blocks=streamed,
                )
                stall_thresh = float(
                    os.environ.get("XLLM_TRACE_STALL_MS", "")
                    or getattr(self.cfg, "trace_stall_ms", 2000.0)
                    or 2000.0
                )
                if stall_ms > stall_thresh:
                    self.flight.trigger(
                        "kv_handoff_stall", srid,
                        stall_ms=round(stall_ms, 3),
                        threshold_ms=stall_thresh, peer=decode_name,
                    )
                with self._kv_stats_mu:  # transfer pool: concurrent commits
                    self._kv_stream_blocks_streamed += streamed
                    self._kv_mig_blocks_total += int(handoff.num_full_blocks)
                if kv_stream is not None:
                    kv_stream.close()
            if err:
                if kv_stream is not None:
                    kv_stream._fail(f"commit failed: {err}")
                logger.error("handoff for %s failed: %s", srid, err)
                out = RequestOutput(
                    request_id=handoff.request_id,
                    service_request_id=srid,
                    status=Status(StatusCode.UNAVAILABLE, err),
                    finished=True,
                )
                with self._srid_mu:
                    self._srid_map.pop(srid, None)
                    self._srid_forget_locked(srid)
                self._push_q.put([out])

        def send(handoff) -> None:
            # Engine thread, in mid-step: the first token's output is
            # still collected. Hand it over BEFORE the transfer is queued:
            # the decode peer continues this side's detokenizer, and the
            # first token is on the push queue before the peer can push.
            self._hand_over()
            t_pf_done = time.monotonic()  # prefill just finished
            self._span(
                srid, "handoff_send",
                peer=decode_name,
                blocks=int(getattr(handoff, "num_full_blocks", 0) or 0),
            )
            # Engine-thread side. The KV export arrives as a DEVICE array;
            # it may only stay device-resident if a colocated peer will
            # take it directly (in-process import) or the pull plane will
            # serve it (the decode peer pulls from device memory) — on the
            # bytes path it would otherwise sit pinned in HBM through the
            # queue + up-to-60s ack wait while the engine has already
            # freed and re-budgeted those blocks (round-2 review finding).
            # Copy to host here for the bytes path; a peer that
            # (de)registers between enqueue and transfer still works —
            # both import paths accept either array kind.
            # NO host copy here for a bytes-plane-cached peer (unlike the
            # transfer()-side guard): the conversion of a full monolithic
            # payload is a blocking device sync that would stall the
            # ENGINE thread; queue pinning is bounded (maxsize 8) and
            # transfer() converts at dequeue, before the ack wait.
            if (
                handoff.kv is not None
                and self._local_peer(decode_name) is None
                and self._kv_transfer is None
            ):
                handoff = dataclasses.replace(
                    handoff, kv=_host_kv(handoff.kv)
                )
            self._transfer_q.put(lambda: transfer(handoff, t_pf_done))

        return send

    def _post_kv_frame(
        self,
        addr: str,
        header: Dict[str, Any],
        kv,
        offer_session=None,
        final_codes: tuple = (),
    ) -> str:
        """POST one /kv/import frame to `addr`; '' on success. The shared
        delivery protocol of the monolithic handoff and the streamed
        chunks: a device-resident `kv` is OFFERED on this process's
        transfer server (under `offer_session` when given, so a streaming
        session can bulk-retract on abort) and the POST carries only
        {addr, uuid, shape, dtype} — the peer pulls device-to-device
        before acking (runtime/transfer.py). A transport error leaves the
        offer on the grace window (the peer may STILL be pulling — an
        immediate retract could free the buffer under it); a rejected
        pull header caches the peer on the bytes plane (`_peer_no_pull`)
        and retries ONCE with body bytes, unless the status is in
        `final_codes` (e.g. a 409 session refusal, where a bytes retry
        would just fail again). Host (np) payloads ride the body."""
        xfer = self._kv_transfer
        use_pull = (
            xfer is not None
            and _device_resident(kv)
            and addr not in self._peer_no_pull
        )
        if use_pull:
            offers = offer_session if offer_session is not None else xfer
            uuid = offers.offer([kv])
            pull_header = dict(header)
            pull_header["kv_pull"] = {
                "addr": xfer.address,
                "uuid": uuid,
                "shape": [int(s) for s in kv.shape],
                "dtype": str(kv.dtype),
            }
            try:
                code, resp = post_bytes(
                    addr, "/kv/import", kv_frame_to_bytes(pull_header)
                )
            except Exception as e:
                # Lifetime hands over to the grace timer; a session-level
                # bulk retract must not cancel it (the peer may be
                # mid-pull), so the session forgets the uuid.
                xfer.retract_later(uuid)
                if offer_session is not None:
                    offer_session.forget(uuid)
                return f"decode peer unreachable: {e}"
            # A response means the peer finished (or never started) its
            # pull — the offer's keepalive can drop now.
            offers.retract(uuid)
            if code == 200:
                return ""
            if code in final_codes:
                return f"decode peer refused /kv/import: {resp}"
            # Capability cache: ONLY a peer that reports having no
            # transfer server (the _resolve_kv_pull rejection) rejects
            # every pull header — cache it on the bytes plane. Any other
            # rejection (transient pull failure, shape gate, fault
            # injection) retries on bytes WITHOUT poisoning the cache,
            # or future handoffs to a healthy pull peer would pay host
            # copies forever.
            try:
                msg = str((resp or {}).get("error", {}).get("message", ""))
            except Exception:
                msg = ""
            if "no transfer server" in msg:
                logger.warning(
                    "peer %s has no transfer server; using the bytes "
                    "plane for it from now on", addr,
                )
                self._peer_no_pull.add(addr)
            else:
                logger.warning(
                    "pull-plane /kv/import rejected by %s (%s); retrying "
                    "this message on the bytes plane", addr, resp,
                )
            kv = _host_kv(kv)
        try:
            code, resp = post_bytes(
                addr, "/kv/import", kv_frame_to_bytes(header, kv)
            )
        except Exception as e:
            return f"decode peer unreachable: {e}"
        if code != 200:
            return f"decode peer rejected /kv/import: {resp}"
        return ""

    def _post_handoff(self, addr: str, handoff, extra: Dict[str, Any]) -> str:
        """POST one handoff to a cross-process decode peer; returns "" on
        success, an error string otherwise (delivery protocol:
        _post_kv_frame)."""
        return self._post_kv_frame(
            addr, handoff_header(handoff, extra), handoff.kv
        )

    def _local_peer(self, decode_name: str) -> Optional["InstanceServer"]:
        """The colocated in-process peer eligible for direct (device-
        resident) KV handoff, or None. BOTH sides must opt in, and both
        must belong to the same master (name collisions across stacks in
        one process must not cross-deliver KV)."""
        if not self.cfg.enable_local_kv_transfer:
            return None
        with _LOCAL_MU:
            peer = _LOCAL_INSTANCES.get(decode_name)
        if peer is None or peer is self:
            return None
        if not peer.cfg.enable_local_kv_transfer or getattr(
            peer._master, "_addr", None
        ) != getattr(self._master, "_addr", ""):
            return None
        return peer

    def _resolve_kv_pull(self, p: Dict[str, Any]):
        """Pull-plane resolution for one /kv/import message: fetch the
        offered array straight from the sender's device memory BEFORE
        acking (the offer's lifetime is bounded by this round-trip and
        pull failures surface in the sender's response). Returns
        (kv, err) with exactly one side set."""
        if self._kv_transfer is None:
            return None, (
                "kv_pull offered but this instance has no transfer server "
                "(enable_kv_transfer_server)"
            )
        # Land the pull straight onto the local executor's payload
        # sharding (migration_sharding — the kv_cache_sharding-derived
        # layout): a tp-sharded consumer never bounces the payload
        # through one device and a later reshard; on a 1-device engine
        # this resolves to the same single-device landing as before.
        sharding = None
        ex = getattr(self.engine, "executor", None)
        if ex is not None and hasattr(ex, "migration_sharding"):
            try:
                sharding = ex.migration_sharding()
            except Exception:
                sharding = None
        try:
            kv = self._kv_transfer.pull_single(
                p["addr"], int(p["uuid"]), p["shape"],
                resolve_kv_dtype(p["dtype"]), sharding=sharding,
            )
        except Exception as e:
            return None, f"kv pull failed: {e}"
        return kv, ""

    def _handle_kv_import(self, h: HttpJsonApi) -> None:
        try:
            n = int(h.headers.get("Content-Length", 0))
            data = h.rfile.read(n)
            header, body = kv_frame_split(data)
        except Exception as e:
            h.send_error_json(400, f"bad handoff payload: {e}")
            return
        # Epoch fence on the /kv/import CONTROL plane: opens and commits
        # descend from a master's routing decision, so a deposed master's
        # pair choice must be rejectable here exactly like its dispatch.
        if self._fence_reject(h, header):
            return
        if header.get("fabric_blocks"):
            # Coordinated-eviction re-homing (docs/KV_CACHE.md): a peer is
            # shipping the last fleet replica of cold-tier victims.
            self._handle_fabric_import(h, header, body)
            return
        ss = header.get("kv_stream") or {}
        if ss and ss.get("op") != "commit":
            # Streaming-session control message (open / chunk / abort);
            # commits fall through to the ordinary handoff admission below.
            self._handle_kv_stream_msg(h, ss, header, body)
            return
        if ss:
            with self._kv_sessions_mu:
                self._kv_sessions.pop(str(ss.get("id", "")), None)
        try:
            handoff = handoff_from_parts(header, body)
        except Exception as e:
            h.send_error_json(400, f"bad handoff payload: {e}")
            return
        if "kv_pull" in header:
            kv, err = self._resolve_kv_pull(header["kv_pull"])
            if err:
                h.send_error_json(400, err)
                return
            handoff = dataclasses.replace(handoff, kv=kv)
        rid = self._admit_import(handoff, header)
        h.send_json({"ok": True, "request_id": rid})

    def _kv_session_open(self, sid: str, ss: Dict[str, Any]) -> str:
        """Session-open admission: reap stale sessions, bound the table,
        and soft-reserve the expected block count against the pool (racy
        reads by design — the engine thread owns the manager; a reservation
        miss only degrades the session to monolithic, and real pressure at
        landing time still degrades to recompute)."""
        expected = max(int(ss.get("expected_blocks", 0) or 0), 0)
        bm = getattr(self.engine, "block_mgr", None)
        now = time.monotonic()
        with self._kv_sessions_mu:
            for key in [
                k
                for k, v in self._kv_sessions.items()
                if now - v["ts"] > _KV_SESSION_TTL_S
            ]:
                del self._kv_sessions[key]
            if sid in self._kv_sessions:
                return ""  # duplicate open (sender retry): keep the entry
            if len(self._kv_sessions) >= _KV_SESSION_CAP:
                return "too many open KV stream sessions"
            if bm is not None and expected:
                # free-list blocks INCLUDE evictable cached ones (the
                # landing path may LRU-evict), which is exactly the
                # reservation semantics wanted here.
                free = int(getattr(bm, "num_free_blocks", 0))
                if expected > free:
                    return (
                        f"cannot reserve {expected} blocks "
                        f"({free} free)"
                    )
            self._kv_sessions[sid] = {
                "ts": now, "expected": expected, "chunks": 0, "blocks": 0,
            }
        return ""

    def _handle_kv_stream_msg(
        self,
        h: HttpJsonApi,
        ss: Dict[str, Any],
        header: Dict[str, Any],
        body: bytes,
    ) -> None:
        """Receive side of the pipelined handoff: land one chunk's blocks
        into the local prefix cache (engine thread does the actual
        allocate/import/commit), keyed only by their chained hashes — the
        session's later commit picks them up through the ordinary prefix
        match, so chunk order (and even chunk loss) never affects
        correctness."""
        sid = str(ss.get("id", ""))
        op = ss.get("op", "")
        if op == "abort":
            with self._kv_sessions_mu:
                self._kv_sessions.pop(sid, None)
            h.send_json({"ok": True})
            return
        if op not in ("open", "chunk"):
            h.send_error_json(400, f"bad kv_stream op {op!r}")
            return
        try:
            faults.point(
                "kv_stream.recv",
                instance=self.name, session=sid,
                srid=header.get("service_request_id", ""),
                chunk=ss.get("idx", -1),
            )
        except faults.FaultInjected as fi:
            h.send_error_json(503, str(fi))
            return
        if not hasattr(self.engine, "import_kv_blocks"):
            h.send_error_json(
                400, "this instance cannot land streamed KV chunks"
            )
            return
        if op == "open":
            err = self._kv_session_open(sid, ss)
            if err:
                h.send_error_json(409, err)
                return
        else:
            # Session gate: chunks land blocks (and can LRU-evict hot
            # cache) — only sessions that passed the open-time
            # reservation may do that. A refused/reaped/never-opened
            # session's chunks get 409, aborting the sender to the
            # monolithic fallback.
            with self._kv_sessions_mu:
                known = sid in self._kv_sessions
            if not known:
                h.send_error_json(409, f"unknown KV stream session {sid}")
                return
        try:
            hashes = [
                bytes.fromhex(x) for x in header.get("block_hashes", [])
            ]
        except ValueError:
            h.send_error_json(400, "malformed block hashes")
            return
        if not hashes:
            h.send_error_json(400, "stream chunk carries no blocks")
            return
        if "kv_pull" in header:
            kv, err = self._resolve_kv_pull(header["kv_pull"])
            if err:
                h.send_error_json(400, err)
                return
        else:
            try:
                kv = kv_frame_array(header, body)
            except Exception:
                kv = None
            if kv is None:
                h.send_error_json(400, "stream chunk carries no KV payload")
                return
        # Cheap shape gate HERE (the engine lands chunks asynchronously,
        # after this response): a PD pair config mismatch must surface to
        # the sender so it aborts to the monolithic path instead of
        # streaming garbage all session long.
        ex = getattr(self.engine, "executor", None)
        if ex is not None and hasattr(ex, "migration_shape"):
            expect = ex.migration_shape(len(hashes))
            if tuple(kv.shape) != tuple(expect):
                h.send_error_json(
                    400,
                    f"stream chunk KV shape {tuple(kv.shape)} != local "
                    f"cache layout {tuple(expect)}",
                )
                return
        self.engine.import_kv_blocks(hashes, kv)
        land_srid = str(header.get("service_request_id", ""))
        self._span(
            land_srid, "kv_chunk_landed", blocks=len(hashes), session=sid
        )
        with self._kv_sessions_mu:
            ent = self._kv_sessions.get(sid)
            if ent is not None:
                ent["chunks"] += 1
                ent["blocks"] += len(hashes)
                # Keep-alive: a >TTL prefill (huge context on a loaded
                # chip) must not get its LIVE session reaped out from
                # under its own chunks.
                ent["ts"] = time.monotonic()
        self._m_kv_stream_landed.inc()
        h.send_json({"ok": True, "session": sid})

    def _admit_import(self, handoff, header: Dict[str, Any]) -> str:
        """Decode-side admission of a handed-off sequence — shared by the
        HTTP /kv/import route and the in-process direct path (colocated
        peers skip serialization entirely; the single-host analog of the
        ICI device-to-device KV transfer)."""
        from xllm_service_tpu.runtime.engine import EngineRequest

        srid = header.get("service_request_id", "")
        sampling = sampling_from_body(header.get("sampling", {}), self.cfg)
        guided = header.get("guided")
        schema = header.get("guided_schema")
        if guided and self._ensure_guided_context():
            # decode peer cannot express the mask (tokenizer mismatch):
            # degrade to unconstrained rather than drop the request
            guided = schema = None
        if guided == "json_schema" and not isinstance(schema, dict):
            guided = schema = None
        lora_name = header.get("lora") or ""
        adapter_idx = getattr(self, "lora_names", {}).get(lora_name, 0)
        if lora_name and not adapter_idx:
            # Continuing on the base model would splice two different
            # models into one response — reject instead (the prefill side
            # also colocates LoRA requests, so this is belt and braces).
            logger.error(
                "handoff names adapter %r this instance does not serve; "
                "rejecting", lora_name,
            )
            self._push_q.put([RequestOutput(
                request_id=header.get("service_request_id", ""),
                service_request_id=srid,
                status=Status(
                    StatusCode.INVALID_ARGUMENT,
                    f"decode instance does not serve adapter {lora_name!r}",
                ),
                finished=True,
            )])
            return ""
        rid = generate_uuid(16)
        with self._srid_mu:
            self._srid_map.setdefault(srid, []).append(rid)
        # Fence high-water + reconcile-manifest entry for the adopted
        # sequence (colocated imports bypass the HTTP fence; the epoch
        # still raises the local high-water). The first token was already
        # delivered by the prefill side: classify as an open decode slot.
        self._fence_epoch_check(header.get("master_epoch"))
        self._srid_track(
            srid, max(len(handoff.token_ids) - 1, 0),
            header.get("master_epoch"), delivered=1,
        )
        relay_addr = header.get("respond_addr", "")
        if relay_addr:
            self._relay_addrs[srid] = relay_addr
        self._span(
            srid, "decode_admit",
            tokens=len(handoff.token_ids),
            full_blocks=int(getattr(handoff, "num_full_blocks", 0) or 0),
        )
        detoks: Dict[int, IncrementalDetokenizer] = {}
        if "detok_ids" in header:
            detoks[0] = IncrementalDetokenizer.from_state(
                self.tokenizer, header["detok_ids"],
                header.get("detok_emitted", 0),
            )
        self.engine.import_sequence(
            EngineRequest(
                request_id=rid,
                prompt_token_ids=handoff.token_ids[:-1],
                sampling=sampling,
                callback=self._make_push_callback(srid, detoks),
                guided=guided,
                schema=schema,
                adapter_idx=adapter_idx,
                offline=bool(header.get("offline", False)),
            ),
            handoff,
        )
        return rid
