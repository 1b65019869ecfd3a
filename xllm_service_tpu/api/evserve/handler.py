"""QuietHandler-compatible request adapter for the event-loop server.

Route code (master/instance handlers, SseWriter, HttpClientStream) is
written against the BaseHTTPRequestHandler surface: `headers`, `path`,
`send_response/send_header/end_headers`, `wfile.write`, plus the JSON
helpers. EvHandler provides that surface over a Connection outbox, so the
same handler functions run on either backend.

The one capability the threaded handler cannot offer: `hold()` without a
blocked thread. A deferred exchange parks the HTTP exchange on the
connection; the scheduler's deliveries stream into it and a loop timer
enforces the request deadline — 1k concurrent SSE streams cost 1k sockets,
not 1k threads.
"""

from __future__ import annotations

import io
import threading
from http.client import responses as _REASONS
from typing import Callable, Optional

from xllm_service_tpu.api.evserve.parser import HttpRequest
from xllm_service_tpu.api.http_utils import HttpJsonApi


class _BodyWriter:
    """wfile shim: write() enqueues on the connection, raising
    BrokenPipeError when the client is gone so SseWriter/HttpClientStream
    error paths fire exactly as they do on a real socket."""

    def __init__(self, handler: "EvHandler"):
        self._h = handler

    def write(self, data: bytes) -> int:
        self._h._write_body(data)
        return len(data)

    def flush(self) -> None:  # enqueue already woke the loop
        pass


class EvHandler(HttpJsonApi):
    protocol_version = "HTTP/1.1"
    # wfile.write appends to the connection's outbox: it never waits for
    # the client (QuietHandler's is a socket write and can).
    writes_can_block = False
    # Grace between the deadline fail() and abandoning the exchange
    # (class attr so tests can compress it).
    grace_s = 5.0

    def __init__(self, server, conn, request: HttpRequest):
        self.server = server
        self.conn = conn
        self.request = request
        self.headers = request.headers
        self.path = request.target
        self.command = request.method
        self.close_connection = not request.keep_alive
        self.wfile = _BodyWriter(self)
        # Raw-body readers (KV import posts octet-stream): the body is
        # already buffered, serve it back as a file.
        self.rfile = io.BytesIO(request.body)
        self._head_lines: list = []
        self._head_sent = False
        self._chunked = False
        self._content_length: Optional[int] = None
        self._body_written = 0
        self.deferred = False
        self._done = False
        self._done_mu = threading.Lock()
        self._timeout_handle = None
        self._grace_handle = None

    # -- HttpJsonApi contract ------------------------------------------- #
    def _read_body(self) -> bytes:
        return self.request.body

    # -- BaseHTTPRequestHandler surface --------------------------------- #
    def send_response(self, code: int, message: Optional[str] = None) -> None:
        reason = message or _REASONS.get(code, "")
        self._head_lines = [f"HTTP/1.1 {code} {reason}"]

    def send_header(self, keyword: str, value: str) -> None:
        k = keyword.lower()
        if k == "content-length":
            self._content_length = int(value)
        elif k == "transfer-encoding" and "chunked" in value.lower():
            self._chunked = True
            # Arms the slow-client buffer cap for this exchange.
            self.conn.streaming = True
        elif k == "connection" and "close" in value.lower():
            self.close_connection = True
        self._head_lines.append(f"{keyword}: {value}")

    def end_headers(self) -> None:
        if self._content_length is None and not self._chunked:
            # Unframed response: the only way to delimit it is to close.
            self.close_connection = True
        head = ("\r\n".join(self._head_lines) + "\r\n\r\n").encode("iso-8859-1")
        self._head_sent = True
        self.conn.enqueue(head)
        if self._content_length == 0:
            self._complete()

    def _write_body(self, data: bytes) -> None:
        if not self.conn.enqueue(data):
            raise BrokenPipeError("client disconnected")
        self._body_written += len(data)
        if (
            not self._chunked
            and self._content_length is not None
            and self._body_written >= self._content_length
        ):
            self._complete()

    # SseWriter.close() hook: the chunked terminator has been written.
    def on_sse_closed(self) -> None:
        self._complete()

    # -- deferred exchange ---------------------------------------------- #
    def hold(
        self, stream, timeout_s: float, fail: Callable[[], None]
    ) -> None:
        """Event-backend analog of the threaded handler's blocking
        `stream.done.wait()`: returns immediately, leaving the exchange
        parked on the connection. A loop timer enforces the deadline; a
        5 s grace follows the deadline fail (mirrors QuietHandler.hold)
        before the exchange is abandoned and the connection dropped."""
        def on_timeout() -> None:
            if stream.done.is_set():
                return
            try:
                fail()
            finally:
                # Arm under _done_mu: either _complete() already ran (don't
                # arm a timer nobody will cancel) or it will see the handle.
                with self._done_mu:
                    if not self._done:
                        self._grace_handle = self.server.call_later(
                            self.grace_s, on_grace
                        )

        def on_grace() -> None:
            if not stream.done.is_set():
                stream.abandon()
                self._complete(close=True)

        # Defer + gauge + timer all under _done_mu: a delivery completing the
        # exchange concurrently either beats this block (we return — no
        # timer armed, no gauge bump) or _complete() sees the armed handle
        # and cancels it. Arming outside the lock would leak a 600 s timer
        # closure (pinning handler+connection+body) per lost race, and let
        # note_stream_end run before note_stream_begin (gauge reads -1).
        with self._done_mu:
            if self._done:
                return
            self.deferred = True
            self.server.note_stream_begin()
            self._timeout_handle = self.server.call_later(
                timeout_s, on_timeout
            )

    def finalize_after_app(self) -> None:
        """Pool worker, after the route function returned: a non-deferred
        exchange must be complete by now; repair it if the handler fell
        through without responding."""
        if self.deferred or self._done:
            return
        if not self._head_sent:
            try:
                self.send_error_json(500, "handler produced no response")
            except Exception:
                self._complete(close=True)
        else:
            self._complete(close=True)

    def _complete(self, close: bool = False) -> None:
        with self._done_mu:
            if self._done:
                return
            self._done = True
            was_deferred = self.deferred
            handles = (self._timeout_handle, self._grace_handle)
            self._timeout_handle = self._grace_handle = None
        for h in handles:
            if h is not None:
                h.cancel()
        if was_deferred:
            self.server.note_stream_end()
        self.server.post(lambda: self.conn.exchange_complete(self, close))
