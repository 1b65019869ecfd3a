"""OpenAI-compatible response construction.

Builds chat.completion[.chunk] / text_completion JSON (logprobs, usage,
finish_reason, the terminal `data: [DONE]`) from engine RequestOutputs
(reference: xllm_service/scheduler/response_handler.{h,cpp} — streaming chat
:20-114, streaming completion :116-196, non-stream :198-306) over a
transport-agnostic ClientStream so HTTP/SSE lives in the API tier
(the reference couples this to brpc call_data).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from xllm_service_tpu.common.types import (
    FinishReason,
    LogProb,
    RequestOutput,
    SequenceOutput,
    StatusCode,
)
from xllm_service_tpu.service.request import ServiceRequest


class ClientStream:
    """Transport seam (reference: StreamCallData/CallData, call_data.h).

    write/write_done return False when the client went away — the scheduler
    uses that to cancel upstream generation.

    `writes_can_block`: a write may wait for the client (a socket write).
    The scheduler then delivers on a thread of its own instead of the one
    that pushed the tokens, which has other requests' tokens to deliver."""

    writes_can_block = False

    def write(self, payload: Dict[str, Any]) -> bool:
        raise NotImplementedError

    def write_done(self) -> bool:
        """Terminal SSE `data: [DONE]` marker (no-op for non-stream)."""
        return True

    def finish(self, payload: Dict[str, Any]) -> bool:
        """Single non-streaming response body."""
        raise NotImplementedError

    def finish_with_error(self, code: StatusCode, message: str) -> bool:
        raise NotImplementedError


def _chat_logprobs(logprobs: List[LogProb]) -> Optional[Dict[str, Any]]:
    if not logprobs:
        return None
    content = []
    for lp in logprobs:
        content.append(
            {
                "token": lp.data.token,
                "logprob": lp.data.logprob,
                "bytes": list(lp.data.token.encode("utf-8")),
                "top_logprobs": [
                    {
                        "token": t.token,
                        "logprob": t.logprob,
                        "bytes": list(t.token.encode("utf-8")),
                    }
                    for t in lp.top_logprobs
                ],
            }
        )
    return {"content": content}


def _completion_logprobs(logprobs: List[LogProb]) -> Optional[Dict[str, Any]]:
    if not logprobs:
        return None
    return {
        "tokens": [lp.data.token for lp in logprobs],
        "token_logprobs": [lp.data.logprob for lp in logprobs],
        "top_logprobs": [
            {t.token: t.logprob for t in lp.top_logprobs} for lp in logprobs
        ],
        "text_offset": [],
    }


def _usage_json(output: RequestOutput) -> Optional[Dict[str, Any]]:
    if output.usage is None:
        return None
    return {
        "prompt_tokens": output.usage.num_prompt_tokens,
        "completion_tokens": output.usage.num_generated_tokens,
        "total_tokens": output.usage.num_total_tokens,
    }


def _finish_reason(seq: SequenceOutput) -> Optional[str]:
    return seq.finish_reason.to_string()


def accumulate_sequences(
    acc: Dict[int, SequenceOutput], output: RequestOutput
) -> None:
    """Merge one step's per-sequence deltas into an accumulator keyed by
    sequence index — the single merge used by both the service scheduler
    (non-stream responses) and the instance's direct mode."""
    for seq in output.outputs:
        cur = acc.get(seq.index)
        if cur is None:
            acc[seq.index] = SequenceOutput(
                index=seq.index,
                text=seq.text,
                token_ids=list(seq.token_ids),
                finish_reason=seq.finish_reason,
                logprobs=list(seq.logprobs),
            )
        else:
            cur.text += seq.text
            cur.token_ids.extend(seq.token_ids)
            cur.logprobs.extend(seq.logprobs)
            if seq.finish_reason != FinishReason.NONE:
                cur.finish_reason = seq.finish_reason


class ResponseHandler:
    """Stateless JSON builders + the stream/non-stream send policies
    (reference: response_handler.cpp)."""

    # ------------------------------------------------------------------ #
    # streaming
    # ------------------------------------------------------------------ #

    def send_delta_to_client(
        self,
        stream: ClientStream,
        request: ServiceRequest,
        output: RequestOutput,
        first_chunk_sent: bool,
    ) -> bool:
        """One generation step -> SSE chunk(s). Returns False if the client
        disconnected (reference: streaming paths, response_handler.cpp:20-196).
        """
        created = int(request.created_time)
        ok = True
        for seq in output.outputs:
            if request.is_chat:
                delta: Dict[str, Any] = {}
                if not first_chunk_sent:
                    delta["role"] = "assistant"
                if seq.text:
                    delta["content"] = seq.text
                chunk = {
                    "id": request.service_request_id,
                    "object": "chat.completion.chunk",
                    "created": created,
                    "model": request.model,
                    "choices": [
                        {
                            "index": seq.index,
                            "delta": delta,
                            "logprobs": _chat_logprobs(seq.logprobs),
                            "finish_reason": _finish_reason(seq),
                        }
                    ],
                }
            else:
                chunk = {
                    "id": request.service_request_id,
                    "object": "text_completion",
                    "created": created,
                    "model": request.model,
                    "choices": [
                        {
                            "index": seq.index,
                            "text": seq.text,
                            "logprobs": _completion_logprobs(seq.logprobs),
                            "finish_reason": _finish_reason(seq),
                        }
                    ],
                }
            request.trace("out", chunk)
            ok = stream.write(chunk) and ok
            if not ok:
                return False
        if output.finished:
            if request.include_usage and output.usage is not None:
                usage_chunk = {
                    "id": request.service_request_id,
                    "object": "chat.completion.chunk"
                    if request.is_chat
                    else "text_completion",
                    "created": created,
                    "model": request.model,
                    "choices": [],
                    "usage": _usage_json(output),
                }
                request.trace("out", usage_chunk)
                ok = stream.write(usage_chunk) and ok
            ok = stream.write_done() and ok
        return ok

    # ------------------------------------------------------------------ #
    # non-streaming
    # ------------------------------------------------------------------ #

    def send_result_to_client(
        self,
        stream: ClientStream,
        request: ServiceRequest,
        output: RequestOutput,
    ) -> bool:
        """Full accumulated result -> single response body
        (reference: response_handler.cpp:198-306)."""
        if not output.status.ok():
            return stream.finish_with_error(output.status.code, output.status.message)
        created = int(request.created_time)
        if request.is_chat:
            choices = []
            for seq in output.outputs:
                message: Dict[str, Any] = {
                    "role": "assistant", "content": seq.text,
                }
                finish = _finish_reason(seq) or "stop"
                if request.tools:
                    # service/tool_calls.py: Hermes/Qwen <tool_call>
                    # spans -> OpenAI message.tool_calls (non-streaming
                    # only; streaming emits the spans as content).
                    from xllm_service_tpu.service.tool_calls import (
                        parse_tool_calls,
                    )

                    content, calls = parse_tool_calls(
                        seq.text, request.service_request_id, seq.index
                    )
                    if calls:
                        message["content"] = content
                        message["tool_calls"] = calls
                        if finish == "stop":
                            finish = "tool_calls"
                choices.append({
                    "index": seq.index,
                    "message": message,
                    "logprobs": _chat_logprobs(seq.logprobs),
                    "finish_reason": finish,
                })
            body = {
                "id": request.service_request_id,
                "object": "chat.completion",
                "created": created,
                "model": request.model,
                "choices": choices,
            }
        else:
            choices = [
                {
                    "index": seq.index,
                    "text": seq.text,
                    "logprobs": _completion_logprobs(seq.logprobs),
                    "finish_reason": _finish_reason(seq) or "stop",
                }
                for seq in output.outputs
            ]
            body = {
                "id": request.service_request_id,
                "object": "text_completion",
                "created": created,
                "model": request.model,
                "choices": choices,
            }
        usage = _usage_json(output)
        if usage is not None:
            body["usage"] = usage
        request.trace("out", body)
        return stream.finish(body)
