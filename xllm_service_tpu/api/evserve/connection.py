"""Per-connection state for the event-loop front end.

One Connection owns one accepted socket. The loop thread does all socket
I/O and selector bookkeeping; the scheduler's delivering threads and pool
workers only ever touch the thread-safe outbox (`enqueue`), which wakes the
loop to drain (once per batch under `server.deferred_wakes()`).

Exchange lifecycle: the parser may buffer pipelined requests, but at most
one is in flight — the next starts only after the current response is
fully framed (Content-Length met or chunked terminator written). An SSE
exchange can outlive its pool worker by deferring (EvHandler.hold), so a
generation holds a connection, never a thread.
"""

from __future__ import annotations

import socket
import threading
import time
from collections import deque
from typing import Deque, List, Optional

from xllm_service_tpu.api.evserve.parser import HttpRequest, ParseError, RequestParser

# Coalesce outbox chunks up to this size per send() call: one syscall per
# readiness for the common SSE burst instead of one per token.
_SEND_COALESCE = 64 * 1024

# A client may pipeline, but a control-plane peer queueing this deep is
# abuse (each buffered request holds up to MAX_BODY_BYTES) — drop it.
_MAX_PIPELINED = 64


class Connection:
    def __init__(self, server, sock: socket.socket, addr):
        self.server = server
        self.sock = sock
        self.addr = addr
        self.parser = RequestParser(max_body_bytes=server.max_body_bytes)
        self._out: Deque[memoryview] = deque()
        self._out_bytes = 0
        self._mu = threading.Lock()
        self.closed = False
        self._close_after_flush = False
        # Loop-thread view of the selector registration (read may be paused
        # for backpressure; write tracks a non-empty outbox).
        self.events_mask = 0
        self.in_flight = None  # current EvHandler, loop-thread owned
        self.pending: Deque[HttpRequest] = deque()
        self.last_activity = time.monotonic()
        # Set (from the worker thread) when the current exchange switched to
        # chunked SSE — arms the slow-client buffer cap.
        self.streaming = False
        self.overflowed = False
        # Protocol error answered; later bytes are drained and DISCARDED —
        # the parser sits in a half-consumed state after a ParseError, so
        # feeding it again could buffer a rejected oversized body in full
        # and then dispatch the very request the client was told was bad.
        self.rejected = False

    # ------------------------------------------------------------------ #
    # any-thread side
    # ------------------------------------------------------------------ #

    def enqueue(self, data: bytes) -> bool:
        """Queue response bytes; returns False when the connection is gone
        (closed, or evicted as a slow client). Wakes the loop to flush."""
        if not data:
            return not self.closed
        with self._mu:
            if self.closed or self._close_after_flush:
                return False
            if (
                self.streaming
                and self._out_bytes + len(data) > self.server.max_stream_buffer
            ):
                # Slow client: the SSE producer outran the socket by a full
                # buffer. Drop the connection instead of buffering without
                # bound — the False return propagates up through SseWriter
                # to the scheduler, which cancels generation upstream.
                self.overflowed = True
                self.server.note_slow_client()
                self.server.post(self.close)
                return False
            self._out.append(memoryview(bytes(data)))
            self._out_bytes += len(data)
        self.server.request_flush(self)
        return True

    @property
    def buffered_bytes(self) -> int:
        return self._out_bytes

    # ------------------------------------------------------------------ #
    # loop-thread side
    # ------------------------------------------------------------------ #

    def on_readable(self) -> None:
        try:
            data = self.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self.close()
            return
        if not data:
            self.close()
            return
        self.last_activity = time.monotonic()
        if self.rejected:
            return  # drained and discarded; closing once the error flushes
        try:
            reqs = self.parser.feed(data)
        except ParseError as e:
            self.rejected = True
            body = (
                '{"error": {"message": %s, "type": "protocol_error"}}'
                % _json_str(e.message)
            ).encode()
            head = (
                f"HTTP/1.1 {e.status} {e.message[:40]}\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
            ).encode()
            with self._mu:
                self._out.append(memoryview(head + body))
                self._out_bytes += len(head) + len(body)
                self._close_after_flush = True
            self._flush_ready()
            return
        if reqs:
            self.pending.extend(reqs)
            if len(self.pending) > _MAX_PIPELINED:
                self.close()
                return
            self.maybe_start_next()

    def maybe_start_next(self) -> None:
        if self.in_flight is None and self.pending and not self.closed:
            req = self.pending.popleft()
            self.server.start_exchange(self, req)

    def exchange_complete(self, handler, close: bool) -> None:
        """Loop thread: the in-flight response is fully framed."""
        if handler is not self.in_flight:
            return  # stale completion after a hard close
        self.in_flight = None
        self.streaming = False
        self.last_activity = time.monotonic()
        if close or getattr(handler, "close_connection", False):
            with self._mu:
                self._close_after_flush = True
            self._flush_ready()
        else:
            self.maybe_start_next()

    def on_writable(self) -> None:
        self._flush_ready()

    def _flush_ready(self) -> None:
        """Send as much buffered output as the socket accepts; manage the
        EVENT_WRITE registration and deferred close."""
        if self.closed:
            return
        while True:
            with self._mu:
                if not self._out:
                    break
                chunk = self._out[0]
                # Coalesce small chunks (SSE events are ~100 bytes each).
                if len(chunk) < _SEND_COALESCE and len(self._out) > 1:
                    parts: List[memoryview] = []
                    size = 0
                    while self._out and size < _SEND_COALESCE:
                        parts.append(self._out.popleft())
                        size += len(parts[-1])
                    chunk = memoryview(b"".join(parts))
                    self._out.appendleft(chunk)
            try:
                n = self.sock.send(chunk)
            except (BlockingIOError, InterruptedError):
                n = 0
            except OSError:
                self.close()
                return
            if n:
                self.last_activity = time.monotonic()
                with self._mu:
                    self._out_bytes -= n
                    if n == len(chunk):
                        self._out.popleft()
                    else:
                        self._out[0] = chunk[n:]
                if n < len(chunk):
                    break  # socket full
            else:
                break
        with self._mu:
            empty = not self._out
            close_now = empty and self._close_after_flush
        if close_now:
            self.close()
            return
        self.server.update_interest(self, want_write=not empty)

    def close(self) -> None:
        """Loop thread: tear the connection down now. Any later enqueue
        returns False, which cancels its stream upstream."""
        if self.closed:
            return
        with self._mu:
            self.closed = True
            self._out.clear()
            self._out_bytes = 0
        self.server.forget_connection(self)
        try:
            self.sock.close()
        except OSError:
            pass
        # Finalize any held exchange NOW: without this, a client that dies
        # mid-stream leaks the active_streams gauge and pins the handler
        # (plus its deadline timer closure) for the full request timeout.
        h, self.in_flight = self.in_flight, None
        if h is not None:
            h._complete(close=True)


def _json_str(s: str) -> str:
    import json

    return json.dumps(s)
