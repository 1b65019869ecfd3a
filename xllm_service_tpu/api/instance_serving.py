"""OpenAI serving paths of the instance server.

Split from api/instance.py (round-3 de-monolith): forwarded-traffic
fan-out (n/best_of), direct client serving (stream + accumulate),
best_of selection/response shaping, prompt tokenization, and the
generations push callback. Mixed into InstanceServer; `self` is the
server.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Any, Dict, List, Optional, Tuple

from xllm_service_tpu.api.http_utils import HttpJsonApi, SseWriter
from xllm_service_tpu.api.protocol import parse_prompt_field, sampling_from_body
from xllm_service_tpu.common.shortuuid import generate_uuid
from xllm_service_tpu.common.types import RequestOutput, Status, StatusCode
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.service.request import ServiceRequest
from xllm_service_tpu.service.response_handler import accumulate_sequences
from xllm_service_tpu.tokenizer import parse_messages
from xllm_service_tpu.tokenizer.tokenizer import IncrementalDetokenizer

logger = logging.getLogger("xllm_service_tpu.api.instance")


class ServingMixin:
    def _make_push_callback(
        self,
        srid: str,
        detoks: Optional[Dict[int, IncrementalDetokenizer]] = None,
    ):
        if detoks is None:
            detoks = {}
        step_open = self._step_open
        pending = self._pending_outs

        def callback(out: RequestOutput) -> bool:
            out.service_request_id = srid
            if step_open is not None and step_open():
                # Engine thread, inside a step whose end runs
                # _hand_over: collect, and leave the rest to it.
                pending.append((out, detoks))
            else:
                # No boundary to wait for (FakeEngine, a foreign thread,
                # a call outside a step): all of it, now.
                self._push_outputs([(out, detoks)])
            return True

        return callback

    def _hand_over(self) -> None:
        """The engine's step listener (engine thread): the outputs the
        push callbacks collected since the last boundary leave as ONE
        list. If that raises, what it held is not dropped in silence: it
        is logged and the requests are cancelled, as a raising callback
        cancels its own."""
        held = self._pending_outs[:]
        if not held:
            return
        del self._pending_outs[:]
        self._m_handover.observe(len(held))
        try:
            self._push_outputs(held)
        except Exception:
            logger.exception(
                "hand-over of %d outputs failed; cancelling their requests",
                len(held),
            )
            for out, _ in held:
                self.engine.cancel(out.request_id)

    def _push_outputs(self, held) -> None:
        """Everything a pushed output needs besides its row's booking,
        once for the list: text, the reconcile manifest's delivered
        counts under one `_srid_mu`, the reaping of finished requests,
        ONE queue put. `held` is [(output, the request's detokenizers)]
        in booking order; an output whose detokenization raises fails
        its own request and no other."""
        outs: List[RequestOutput] = []
        failed = set()
        for out, detoks in held:
            if out.request_id in failed:
                continue  # a finished output is its request's last
            try:
                self._detokenize(out, detoks)
            except Exception:
                logger.exception(
                    "detokenization failed for %s; failing the request",
                    out.service_request_id,
                )
                failed.add(out.request_id)
                self.engine.cancel(out.request_id)
                out = RequestOutput(
                    request_id=out.request_id,
                    service_request_id=out.service_request_id,
                    status=Status(
                        StatusCode.UNKNOWN, "detokenization failed"
                    ),
                    finished=True,
                )
            outs.append(out)
        finished = []
        with self._srid_mu:
            for out in outs:
                srid = out.service_request_id
                info = self._srid_info.get(srid)
                if info is not None:
                    for s in out.outputs:
                        info["delivered"] += len(s.token_ids)
                if out.finished:
                    self._srid_map.pop(srid, None)
                    self._srid_forget_locked(srid)
                    finished.append(srid)
        for srid in finished:
            # A prefill_only request that finishes on its first token
            # (EOS / max_tokens=1 / reject / cancel) never runs its
            # handoff — reap the ack event here or it leaks forever.
            with self._push_acked_mu:
                self._push_acked.pop(srid, None)
            # Same for the streamed-media handle: without this, a
            # finished request's embedding arrays stay pinned in
            # _mm_streams until the NEXT mm request triggers the TTL
            # reap — indefinitely on an instance gone text-only.
            self._mm_stream_discard(srid)
        self._push_q.put(outs)

    def _serve_fanout_forwarded(
        self,
        srid: str,
        token_ids: List[int],
        sampling: SamplingParams,
        n: int,
        best_of: int,
        guided: Optional[str] = None,
        schema: Optional[dict] = None,
        adapter_idx: int = 0,
        offline: bool = False,
    ) -> None:
        """Run n (or best_of) sequences as independent engine requests and
        push INDEXED deltas under one service_request_id. The prompt's KV
        blocks are shared through the prefix cache. best_of buffers all
        children and pushes only the top-n (by mean logprob) at the end."""
        from xllm_service_tpu.common.types import Usage
        from xllm_service_tpu.runtime.engine import EngineRequest

        total = best_of or n
        detoks: Dict[int, IncrementalDetokenizer] = {}
        agg_mu = threading.Lock()
        state = {
            "remaining": total,
            "generated": [0] * total,
            "logprob_sum": [0.0] * total,
            "buffered": {} if best_of else None,  # index -> merged SequenceOutput
            "aborted": False,
        }
        want_logprobs = sampling.logprobs

        def make_cb(i: int):
            def cb(out: RequestOutput) -> bool:
                out.service_request_id = srid
                for s in out.outputs:
                    s.index = i
                    for lp in s.logprobs:
                        state["logprob_sum"][i] += lp.data.logprob
                with agg_mu:
                    if state["aborted"]:
                        return False
                    if out.usage:
                        state["generated"][i] = out.usage.num_generated_tokens
                    last = False
                    if out.finished:
                        state["remaining"] -= 1
                        last = state["remaining"] == 0
                self._srid_note_delivered(
                    srid, sum(len(s.token_ids) for s in out.outputs)
                )
                if not out.status.ok() and not out.cancelled:
                    # Child error (reject/engine failure): surface it ONCE,
                    # cancel the siblings, drop the request.
                    with agg_mu:
                        state["aborted"] = True
                    with self._srid_mu:
                        others = self._srid_map.pop(srid, None) or []
                        self._srid_forget_locked(srid)
                    for other in others:
                        self.engine.cancel(other)
                    out.finished = True
                    self._push_q.put([out])
                    return False
                if state["buffered"] is not None:
                    # best_of: hold everything until all children finish.
                    with agg_mu:
                        accumulate_sequences(state["buffered"], out)
                    if last:
                        self._finish_best_of(
                            srid, state, token_ids, n, want_logprobs, detoks
                        )
                    return True
                # n>1 streaming/accumulating path: push indexed deltas; only
                # the LAST child's finish carries finished + merged usage
                # (per-seq finish_reason still reaches the client).
                self._detokenize(out, detoks)
                if out.finished and not last:
                    out.finished = False
                    out.usage = None
                elif out.finished and last:
                    out.usage = Usage(
                        num_prompt_tokens=len(token_ids),
                        num_generated_tokens=sum(state["generated"]),
                    )
                    with self._srid_mu:
                        self._srid_map.pop(srid, None)
                        self._srid_forget_locked(srid)
                self._push_q.put([out])
                return True

            return cb

        # Register the rids BEFORE submitting: a fast-finishing child pops
        # the srid entry, and a late registration would resurrect it (leak)
        # or let a /cancel in the window find nothing to cancel.
        rids = [generate_uuid(16) for _ in range(total)]
        with self._srid_mu:
            self._srid_map.setdefault(srid, []).extend(rids)
        for i, rid in enumerate(rids):
            self.engine.add_request(
                EngineRequest(
                    request_id=rid,
                    prompt_token_ids=list(token_ids),
                    sampling=self._child_sampling(
                        sampling, i, need_logprobs=bool(best_of)
                    ),
                    callback=make_cb(i),
                    guided=guided,
                    schema=schema,
                    offline=offline,
                    adapter_idx=adapter_idx,
                )
            )

    def _finish_best_of(
        self,
        srid: str,
        state: Dict[str, Any],
        token_ids: List[int],
        n: int,
        want_logprobs: bool,
        detoks: Dict[int, IncrementalDetokenizer],
    ) -> None:
        """All best_of children done: rank by mean logprob, re-index the
        top n as choices 0..n-1, push ONE final output."""
        from xllm_service_tpu.common.types import Usage

        merged = state["buffered"]
        order = sorted(
            merged,
            key=lambda i: (
                state["logprob_sum"][i] / max(len(merged[i].token_ids), 1)
            ),
            reverse=True,
        )
        winners = []
        for new_idx, old_idx in enumerate(order[:n]):
            s = merged[old_idx]
            s.index = new_idx
            if not want_logprobs:
                s.logprobs = []
            winners.append(s)
        final = RequestOutput(
            request_id=srid,
            service_request_id=srid,
            outputs=winners,
            usage=Usage(
                num_prompt_tokens=len(token_ids),
                num_generated_tokens=sum(state["generated"]),
            ),
            finished=True,
        )
        self._detokenize(final, detoks)
        with self._srid_mu:
            self._srid_map.pop(srid, None)
            self._srid_forget_locked(srid)
        self._push_q.put([final])

    def _prompt_tokens(self, body: Dict[str, Any], chat: bool) -> List[int]:
        # Forwarded traffic arrives pre-tokenized (the injection contract,
        # service.cpp:334-341) — never re-tokenize.
        if body.get("token_ids"):
            return [int(t) for t in body["token_ids"]]
        if chat:
            prompt = self.chat_template.apply(
                parse_messages(body.get("messages", [])), body.get("tools")
            )
        else:
            prompt, token_ids, err = parse_prompt_field(body.get("prompt", ""))
            if err:
                raise ValueError(err)
            if token_ids:
                return token_ids
        return self.tokenizer.encode(prompt)

    @staticmethod
    def _n_sequences(body: Dict[str, Any], chat: bool) -> Tuple[int, int, str]:
        """Parse (n, best_of, error). best_of is the completions-only
        over-generation count (>= n, select top-n by logprob); chat has no
        best_of. Errors mirror OpenAI validation."""
        try:
            n = max(int(body.get("n") or 1), 1)
        except (TypeError, ValueError):
            return 1, 0, "invalid n"
        best_of = 0
        if not chat and body.get("best_of") is not None:
            try:
                best_of = int(body["best_of"])
            except (TypeError, ValueError):
                return n, 0, "invalid best_of"
            if best_of < n:
                return n, best_of, "best_of must be >= n"
            if body.get("stream"):
                return n, best_of, "best_of is not supported with streaming"
        return n, best_of, ""

    def _vocab_size(self):
        ex = getattr(self.engine, "executor", None)
        return getattr(getattr(ex, "cfg", None), "vocab_size", None)

    def _parse_guided(
        self, body: Dict[str, Any]
    ) -> Tuple[Optional[str], Optional[dict], str]:
        """OpenAI response_format -> (guided mode, schema, error).
        {"type": "json_object"} constrains to any JSON object;
        {"type": "json_schema", "json_schema": {"schema": ...}} to the
        given schema (strict subset — guided/schema_fsm); "text"/absent
        pass through."""
        rf = body.get("response_format")
        if not rf:
            return None, None, ""
        if not isinstance(rf, dict) or "type" not in rf:
            return None, None, "response_format must be an object with a type"
        if rf["type"] in ("text", None):
            return None, None, ""
        if rf["type"] == "json_schema":
            js = rf.get("json_schema")
            schema = js.get("schema") if isinstance(js, dict) else None
            if not isinstance(schema, dict):
                return None, None, (
                    "response_format json_schema requires "
                    "json_schema.schema (an object)"
                )
            from xllm_service_tpu.guided import schema_fsm

            try:
                schema_fsm.compile_schema(schema)
            except schema_fsm.SchemaError as e:
                return None, None, f"unsupported json_schema: {e}"
            err = self._ensure_guided_context()
            if not err:
                # HTTP-thread prewarm: compute the canonical-path token
                # bitmaps NOW so the engine step loop (all running
                # decodes) never stalls behind the first-visit vocab
                # byte walk (advisor finding, round 4).
                try:
                    self.engine.prewarm_schema(schema)
                except Exception:
                    pass  # prewarm is an optimization, never a gate
            return (("json_schema", schema, "") if not err
                    else (None, None, err))
        if rf["type"] != "json_object":
            return None, None, (
                f"response_format type {rf['type']!r} is not supported "
                f"(json_schema, json_object or text)"
            )
        err = self._ensure_guided_context()
        return ("json", None, "") if not err else (None, None, err)

    def _ensure_guided_context(self) -> str:
        """Build + install the JSON-mode mask table once (persistent-
        cached next to the XLA jit cache when configured — the first
        build walks every vocab token through the automaton from every
        abstract state, ~a minute for 128K vocabs)."""
        if getattr(self, "_guided_ready", False):
            return ""
        if not hasattr(self, "_guided_build_lock"):
            self._guided_build_lock = threading.Lock()
        with self._guided_build_lock:
            if getattr(self, "_guided_ready", False):
                return ""
            return self._build_guided_context()

    def _build_guided_context(self) -> str:
        if not hasattr(self.engine, "set_guided_context"):
            return "guided decoding requires a real engine"
        vocab = self._vocab_size()
        if not vocab:
            return "guided decoding requires a real engine"
        tb = self.tokenizer.token_bytes_table(vocab)
        if tb is None:
            return "guided json is not supported for this tokenizer"
        from xllm_service_tpu.guided import json_fsm

        eos = sorted(
            set(self.engine.eos_token_ids)
            | ({self.tokenizer.eos_token_id}
               if self.tokenizer.eos_token_id is not None else set())
        )
        table = self._load_guided_cache(tb, eos)
        if table is None:
            table = json_fsm.token_mask_table(tb, eos)
            self._store_guided_cache(tb, eos, table)
        # eos travels with the table: schema bitmaps must allow the SAME
        # eos set the json_object table was built with (the engine's own
        # set is empty in service deployments).
        self.engine.set_guided_context(table, tb, eos_ids=eos)
        self._guided_ready = True
        return ""

    def _guided_cache_path(self, tb, eos):
        import hashlib
        import os
        import tempfile

        from xllm_service_tpu.guided import json_fsm

        h = hashlib.sha256()
        for t in tb:
            h.update(t + b"\x00")
        h.update(repr(eos).encode())
        h.update(
            f"v{json_fsm.FSM_VERSION}:{json_fsm.NUM_MASK_STATES}".encode()
        )
        base = self.cfg.compilation_cache_dir or tempfile.gettempdir()
        return os.path.join(base, f"xllm-json-mask-{h.hexdigest()[:16]}.npy")

    def _load_guided_cache(self, tb, eos):
        import os

        import numpy as np

        from xllm_service_tpu.guided import json_fsm

        path = self._guided_cache_path(tb, eos)
        if os.path.exists(path):
            try:
                table = np.load(path)
            except Exception:
                return None
            if table.shape == (json_fsm.NUM_MASK_STATES, len(tb)):
                return table
        return None

    def _store_guided_cache(self, tb, eos, table) -> None:
        import os
        import tempfile

        import numpy as np

        path = self._guided_cache_path(tb, eos)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(path), suffix=".npy"
            )
            os.close(fd)
            np.save(tmp, table)  # np.save keeps the .npy name as-is
            os.replace(tmp, path)
        except Exception:
            pass  # cache is best-effort

    @staticmethod
    def _child_sampling(sampling: SamplingParams, i: int, need_logprobs: bool):
        """Per-sequence sampling params: distinct RNG stream per choice
        (i=0 keeps the request seed so n=1 behavior is unchanged)."""
        seed = (sampling.seed + 0x9E3779B9 * i) & 0xFFFFFFFF
        return dataclasses.replace(
            sampling,
            seed=seed,
            logprobs=sampling.logprobs or need_logprobs,
        )

    def _serve(self, h: HttpJsonApi, body: Dict[str, Any], chat: bool) -> None:
        from xllm_service_tpu.runtime.engine import EngineRequest

        srid = body.get("service_request_id", "")
        try:
            token_ids = self._prompt_tokens(body, chat)
        except (ValueError, TypeError) as e:
            h.send_error_json(400, str(e))
            return
        if not token_ids:
            h.send_error_json(400, "empty prompt")
            return
        n, best_of, n_err = self._n_sequences(body, chat)
        if n_err:
            h.send_error_json(400, n_err)
            return
        try:
            sampling = sampling_from_body(
                body, self.cfg, vocab_size=self._vocab_size()
            )
        except ValueError as e:
            h.send_error_json(400, str(e))
            return
        guided, guided_schema, gerr = self._parse_guided(body)
        if gerr:
            h.send_error_json(400, gerr)
            return
        # Multi-LoRA: an OpenAI `model` naming a registered adapter routes
        # to its row; anything else runs the base model.
        adapter_idx = getattr(self, "lora_names", {}).get(
            body.get("model"), 0
        )
        # Hybrid scheduling: offline work admits behind online work and
        # its running decodes preempt under online bursts (engine-level;
        # the master additionally parks offline admissions).
        offline = bool(body.get("offline", False))

        if srid and self._master is not None:
            # Prefix-fabric peer fetch (docs/KV_CACHE.md): the master's
            # dispatch hint says a peer holds more of this prompt's
            # prefix than we do — pull the gap while the engine
            # chunk-prefills the tail. Best-effort, never a gate.
            fab = body.get("kv_fabric")
            if fab and not body.get("mm_positions") and not adapter_idx:
                try:
                    self._fabric_prefetch(
                        token_ids, fab, srid=srid, trace=body.get("trace")
                    )
                except Exception:
                    logger.exception("fabric prefetch failed; recomputing")

        if srid and self._master is not None and (n > 1 or best_of > 1):
            # Reconcile-manifest entry (docs/FAULT_TOLERANCE.md) — after
            # every validation reject, so a refused request can't leak a
            # tracking entry that only a takeover scan would collect.
            self._srid_track(
                srid, len(token_ids), body.get("master_epoch")
            )
            self._span(
                srid, "admit", prompt_tokens=len(token_ids), fanout=True
            )
            # Fan-out mode: PD split is skipped for multi-sequence requests
            # (a per-child handoff would need sub-request ids on the wire);
            # this instance serves all sequences and pushes indexed deltas.
            self._serve_fanout_forwarded(
                srid, token_ids, sampling, n, best_of, guided=guided,
                schema=guided_schema, adapter_idx=adapter_idx,
                offline=offline,
            )
            h.send_json({"ok": True, "service_request_id": srid})
            return
        rid = generate_uuid(16)
        # Mid-stream failover resume (docs/FAULT_TOLERANCE.md): the last
        # `resume_from` token_ids are replayed output from a dead
        # instance. The generation budget shrinks by the replayed count
        # (the client already holds those tokens), and the engine-side
        # marker keeps deterministic engines' continuations aligned.
        resume_from = int(body.get("resume_from") or 0)
        if resume_from:
            if (
                resume_from < 0  # would INFLATE the budget below
                or resume_from >= len(token_ids)
                or n > 1
                or best_of > 1
            ):
                h.send_error_json(400, "invalid resume_from")
                return
            sampling = dataclasses.replace(
                sampling,
                max_new_tokens=max(sampling.max_new_tokens - resume_from, 1),
            )

        if srid and self._master is not None:
            # Forwarded mode: ack now, stream back over /rpc/generations.
            mm_embeds = mm_positions = mm_stream = None
            if body.get("mm_positions"):
                from xllm_service_tpu.api.instance_mm import (
                    _encoder_fabric_enabled,
                )

                if _encoder_fabric_enabled(self.cfg):
                    # Encoder fabric (docs/EPD.md): admit NOW with a
                    # stream handle — the engine prefills text chunks
                    # while the encoder's per-item session lands
                    # embeddings, adopting them at chunk boundaries.
                    mm_positions = [int(p) for p in body["mm_positions"]]
                    mm_stream = self._mm_stream_attach(srid, mm_positions)
                    mm_stream.note_admitted()
                else:
                    # Legacy synchronous EPD: the encoder pushed this
                    # request's media embeddings to /mm/import before the
                    # master forwarded the text (usually already landed).
                    mm = self._pop_mm_import(srid, timeout=60.0)
                    if mm is None:
                        h.send_error_json(
                            503, "media embeddings never arrived"
                        )
                        return
                    mm_embeds, mm_positions = mm
                    if len(mm_positions) != len(body["mm_positions"]):
                        # Encoder and service disagree on media-token
                        # count — reject rather than pair mismatched
                        # arrays (an embeds/positions desync would crash
                        # the engine step).
                        h.send_error_json(
                            502,
                            f"encoder produced {len(mm_positions)} media "
                            f"tokens but the request has "
                            f"{len(body['mm_positions'])} placeholders",
                        )
                        return
            with self._srid_mu:
                self._srid_map.setdefault(srid, []).append(rid)
            # Manifest entry rides the same admission (after the mm/
            # resume rejects above — see the fan-out branch's comment).
            self._srid_track(
                srid, len(token_ids), body.get("master_epoch")
            )
            # Instance-side span: one admission record per forwarded
            # request, clocked on THIS process (the trace collector
            # aligns it with the master's dispatch span).
            self._span(srid, "admit", prompt_tokens=len(token_ids))
            detoks: Dict[int, IncrementalDetokenizer] = {}
            callback = self._make_push_callback(srid, detoks)
            routing = body.get("routing") or {}
            decode_name = routing.get("decode_name", "")
            if mm_embeds is not None or mm_stream is not None:
                # Media requests serve colocated: the recomputed tail on a
                # decode peer would need the embeddings too.
                decode_name = ""
            if adapter_idx:
                # LoRA requests serve colocated too: adapter KV never
                # commits (adapter-blind hashes), so a PD split would ship
                # a zero-block handoff and the decode peer would silently
                # recompute the whole prompt.
                decode_name = ""
            if resume_from:
                # Resumed requests serve colocated: the replay already
                # paid one re-prefill; a PD handoff would bolt a second
                # migration onto a recovery path that must stay simple.
                decode_name = ""
            if decode_name and decode_name != self.name:
                # PD disaggregation: this instance is the prefill side —
                # emit the first token, then migrate KV to the decode peer
                # (reference topology: rpc_service/service.h:61-71). The
                # streaming session (pipelined per-chunk KV export,
                # docs/PD_DISAGGREGATION.md) opens here, at ADMIT time:
                # the master already routed the decode peer, so chunk 0
                # can leave before prefill-done.
                with self._push_acked_mu:
                    self._push_acked[srid] = threading.Event()
                kv_stream = self._open_kv_stream(
                    srid, decode_name, epoch=body.get("master_epoch"),
                    trace=body.get("trace"),
                )
                self.engine.add_request(
                    EngineRequest(
                        request_id=rid,
                        prompt_token_ids=token_ids,
                        sampling=sampling,
                        callback=callback,
                        guided=guided,
                        schema=guided_schema,
                        offline=offline,
                        adapter_idx=adapter_idx,
                        prefill_only=True,
                        kv_stream=kv_stream,
                        handoff=self._make_handoff_sender(
                            srid, decode_name, body, detoks,
                            seed=sampling.seed,
                            respond_via_self=(
                                routing.get("decode_response_to_service", True)
                                is False
                            ),
                            kv_stream=kv_stream,
                        ),
                    )
                )
            else:
                self.engine.add_request(
                    EngineRequest(
                        request_id=rid,
                        prompt_token_ids=token_ids,
                        sampling=sampling,
                        callback=callback,
                        guided=guided,
                        schema=guided_schema,
                        offline=offline,
                        adapter_idx=adapter_idx,
                        mm_embeds=mm_embeds,
                        mm_positions=mm_positions,
                        mm_grids=body.get("mm_grids"),
                        mm_stream=mm_stream,
                        resume_from=resume_from,
                    )
                )
            h.send_json({"ok": True, "service_request_id": srid, "request_id": rid})
            return

        # Direct mode: this instance is the whole stack for one request.
        self._serve_direct(
            h, body, chat, token_ids, sampling, rid, n, best_of,
            guided=guided, schema=guided_schema, adapter_idx=adapter_idx,
            offline=offline,
        )

    def _serve_direct(
        self,
        h: HttpJsonApi,
        body: Dict[str, Any],
        chat: bool,
        token_ids: List[int],
        sampling: SamplingParams,
        rid: str,
        n: int = 1,
        best_of: int = 0,
        guided: Optional[str] = None,
        schema: Optional[dict] = None,
        adapter_idx: int = 0,
        offline: bool = False,
    ) -> None:
        from xllm_service_tpu.runtime.engine import EngineRequest

        total = best_of or n

        req = ServiceRequest(
            service_request_id=("chatcmpl-" if chat else "cmpl-") + rid,
            model=body.get("model", self.cfg.model),
            stream=bool(body.get("stream", False)),
            include_usage=bool(
                (body.get("stream_options") or {}).get("include_usage", False)
            ),
            token_ids=token_ids,
        )
        if chat:
            req.messages = parse_messages(body.get("messages", []))
            req.tools = body.get("tools")  # tool-call extraction
        else:
            p = body.get("prompt", "")
            req.prompt = p if isinstance(p, str) else "".join(p)

        done = threading.Event()
        acc: List[RequestOutput] = []
        sse: Optional[SseWriter] = None
        # Per-choice: each choice's first chat chunk must carry the
        # assistant role (OpenAI stream semantics), not just the globally
        # first chunk.
        first_sent: Dict[int, bool] = {}
        agg_mu = threading.Lock()
        remaining = [total]
        lp_sums = [0.0] * total
        gen_counts = [0] * total

        detoks: Dict[int, IncrementalDetokenizer] = {}
        if req.stream:
            sse = SseWriter(h)

            class _Stream:
                def write(_, payload):
                    return sse.send(payload)

                def write_done(_):
                    ok = sse.send_done()
                    done.set()
                    return ok

            stream = _Stream()

            def make_callback(i: int):
                def callback(out: RequestOutput) -> bool:
                    if not out.status.ok() and not out.cancelled:
                        # Engine-side failure: surface it, don't end as a
                        # clean empty stream.
                        sse.send(
                            {"error": {"message": out.status.message,
                                       "code": int(out.status.code)}}
                        )
                        sse.close()
                        done.set()
                        return False
                    for s in out.outputs:
                        s.index = i
                        gen_counts[i] += len(s.token_ids)
                    with agg_mu:
                        last = True
                        if out.finished:
                            remaining[0] -= 1
                            last = remaining[0] == 0
                        if out.finished and not last:
                            # Suppress the per-child [DONE]; keep the
                            # choice's finish_reason chunk.
                            out.finished = False
                            out.usage = None
                        elif out.finished and out.usage and total > 1:
                            from xllm_service_tpu.common.types import Usage

                            out.usage = Usage(
                                num_prompt_tokens=len(token_ids),
                                num_generated_tokens=sum(gen_counts),
                            )
                    self._detokenize(out, detoks)
                    ok = self._responses.send_delta_to_client(
                        stream, req, out, first_sent.get(i, False)
                    )
                    first_sent[i] = True
                    if out.finished or not ok:
                        # All sequences finished, or the client
                        # disconnected — the exchange is over.
                        done.set()
                    return ok

                return callback
        else:

            def make_callback(i: int):
                def callback(out: RequestOutput) -> bool:
                    for s in out.outputs:
                        s.index = i
                        for lp in s.logprobs:
                            lp_sums[i] += lp.data.logprob
                    if not best_of:
                        self._detokenize(out, detoks)
                    with agg_mu:
                        acc.append(out)
                        if out.finished:
                            remaining[0] -= 1
                            if remaining[0] == 0:
                                done.set()
                    return True

                return callback

        rids = []
        for i in range(total):
            child_rid = rid if i == 0 else generate_uuid(16)
            rids.append(child_rid)
            self.engine.add_request(
                EngineRequest(
                    request_id=child_rid,
                    prompt_token_ids=list(token_ids),
                    sampling=self._child_sampling(
                        sampling, i, need_logprobs=bool(best_of)
                    ),
                    callback=make_callback(i),
                    guided=guided,
                    schema=schema,
                    offline=offline,
                    adapter_idx=adapter_idx,
                )
            )
        if not done.wait(600.0):
            for child_rid in rids:
                self.engine.cancel(child_rid)
            if sse is None:
                # Only a never-started exchange can still carry an error
                # response; an open SSE stream must not get a second head.
                h.send_error_json(504, "generation timeout")
            else:
                sse.close()
                h.close_connection = True
            return
        if not req.stream:
            if best_of:
                self._respond_best_of(
                    h, req, acc, lp_sums, n, sampling.logprobs, detoks
                )
            else:
                self._respond_accumulated(h, req, acc)

    def _respond_best_of(
        self,
        h: HttpJsonApi,
        req: ServiceRequest,
        acc: List[RequestOutput],
        lp_sums: List[float],
        n: int,
        want_logprobs: bool,
        detoks: Dict[int, IncrementalDetokenizer],
    ) -> None:
        """Rank best_of children by mean logprob, return the top n as
        choices 0..n-1 (completions API best_of semantics)."""
        from xllm_service_tpu.common.types import Usage

        if any(not o.status.ok() and not o.cancelled for o in acc):
            self._respond_accumulated(h, req, acc)  # error path
            return
        merged: Dict[int, Any] = {}
        for out in acc:
            accumulate_sequences(merged, out)
        order = sorted(
            merged,
            key=lambda i: lp_sums[i] / max(len(merged[i].token_ids), 1),
            reverse=True,
        )
        winners = []
        total_generated = sum(len(s.token_ids) for s in merged.values())
        for new_idx, old_idx in enumerate(order[:n]):
            s = merged[old_idx]
            s.index = new_idx
            if not want_logprobs:
                s.logprobs = []
            winners.append(s)
        final = RequestOutput(
            request_id=req.service_request_id,
            service_request_id=req.service_request_id,
            outputs=winners,
            usage=Usage(
                num_prompt_tokens=len(req.token_ids),
                num_generated_tokens=total_generated,
            ),
            finished=True,
        )
        self._detokenize(final, detoks)

        class _Once:
            def finish(_, payload):
                h.send_json(payload)
                return True

            def finish_with_error(_, code, msg):
                h.send_error_json(500, msg)
                return True

        self._responses.send_result_to_client(_Once(), req, final)

    def _respond_accumulated(
        self, h: HttpJsonApi, req: ServiceRequest, acc: List[RequestOutput]
    ) -> None:
        # With n>1 children interleaving, an errored child's output can sit
        # anywhere in acc — scan, don't just check the tail.
        err = next(
            (o for o in acc if not o.status.ok() and not o.cancelled), None
        )
        if err is not None:
            h.send_error_json(
                429 if err.status.code == StatusCode.RESOURCE_EXHAUSTED else 500,
                err.status.message,
            )
            return
        merged: Dict[int, Any] = {}
        usage = None
        for out in acc:
            accumulate_sequences(merged, out)
            if out.usage:
                usage = out.usage
        if usage is not None and len(merged) > 1:
            # n>1: per-child usage only counts its own tokens — report the
            # request-level total.
            from xllm_service_tpu.common.types import Usage

            usage = Usage(
                num_prompt_tokens=usage.num_prompt_tokens,
                num_generated_tokens=sum(
                    len(s.token_ids) for s in merged.values()
                ),
            )
        final = RequestOutput(
            request_id=req.service_request_id,
            service_request_id=req.service_request_id,
            outputs=sorted(merged.values(), key=lambda s: s.index),
            usage=usage,
            finished=True,
        )

        class _Once:
            def finish(_, payload):
                h.send_json(payload)
                return True

            def finish_with_error(_, code, msg):
                h.send_error_json(500, msg)
                return True

        self._responses.send_result_to_client(_Once(), req, final)
