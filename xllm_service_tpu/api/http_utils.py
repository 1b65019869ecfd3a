"""HTTP plumbing shared by the master and instance servers.

Replaces the reference's brpc server/ProgressiveAttachment machinery
(call_data.h:83-201) with chunked SSE writes over one of two backends
(make_http_server): the stdlib ThreadingHTTPServer, or the evserve
selectors/epoll event loop that detaches streams from threads.
Keep-alive JSON POSTs between tiers reuse an http.client connection per
(thread, host) — the analog of the reference's cached brpc channels
(instance_mgr.cpp:334-353).
"""

from __future__ import annotations

import contextlib
import http.client
import json
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from xllm_service_tpu.common import faults


class RequestNotSentError(ConnectionError):
    """The request was never written to the socket — retrying it cannot
    double-apply a non-idempotent operation. Any other failure out of
    post_json/post_bytes is INDETERMINATE (the peer may have processed
    the request) and must not be blindly retried."""


def request_was_sent(exc: BaseException) -> bool:
    """True when `exc` leaves the request outcome indeterminate."""
    if isinstance(exc, RequestNotSentError):
        return False
    if isinstance(exc, faults.FaultInjected):
        return exc.sent
    return True


class HttpJsonApi:
    """JSON/routing helpers shared by BOTH server backends: QuietHandler
    (threaded, BaseHTTPRequestHandler) and evserve's EvHandler (event
    loop). Requires the host class to provide `headers`, `path`,
    `send_response/send_header/end_headers`, `wfile`, and `_read_body()`."""

    def read_json(self) -> Optional[Dict[str, Any]]:
        try:
            raw = self._read_body()
            return json.loads(raw.decode("utf-8")) if raw else {}
        except Exception:
            return None

    def send_json(
        self, obj: Any, status: int = 200,
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        data = json.dumps(obj).encode("utf-8")
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def x_request_id(self) -> str:
        """Client correlation id (reference: call_data.h:41-47 reads
        x-request-id, falling back to x-ms-client-request-id)."""
        return (
            self.headers.get("x-request-id")
            or self.headers.get("x-ms-client-request-id")
            or ""
        )

    def send_error_json(
        self, status: int, message: str,
        etype: str = "invalid_request_error",
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        self.send_json(
            {"error": {"message": message, "type": etype}}, status,
            extra_headers=extra_headers,
        )

    def query(self) -> Dict[str, str]:
        q = parse_qs(urlparse(self.path).query)
        return {k: v[0] for k, v in q.items()}

    @property
    def route(self) -> str:
        return urlparse(self.path).path


class QuietHandler(HttpJsonApi, BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # wfile.write is a socket write: it waits for a client that stopped
    # reading (EvHandler's appends to an outbox and never does).
    writes_can_block = True

    def log_message(self, fmt, *args):  # silence per-request stderr spam
        pass

    def _read_body(self) -> bytes:
        n = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(n) if n else b"{}"

    def hold(self, stream, timeout_s: float, fail) -> None:
        """Block this handler thread until the scheduler finishes the
        exchange (thread-per-stream semantics). On deadline, `fail()` asks
        the scheduler to fail the request; if that still hasn't run
        after a 5 s grace, the exchange is abandoned with no response and
        the connection dropped so no late write can reach a reused socket.
        The event backend's EvHandler.hold has the same contract without
        the blocked thread."""
        if stream.done.wait(timeout_s):
            return
        fail()
        if not stream.done.wait(5.0):
            stream.abandon()
            self.close_connection = True

class SseWriter:
    """Server-sent-events writer over a chunked HTTP/1.1 response
    (the ProgressiveAttachment analog, call_data.h:150-193). Thread-safe:
    the scheduler writes from whichever thread delivers."""

    def __init__(
        self,
        handler: BaseHTTPRequestHandler,
        extra_headers: Optional[Dict[str, str]] = None,
    ):
        self._h = handler
        self._mu = threading.Lock()
        self.closed = False
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.send_header("Connection", "keep-alive")
        handler.send_header("Transfer-Encoding", "chunked")
        for k, v in (extra_headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()

    def _chunk(self, data: bytes) -> bool:
        with self._mu:
            if self.closed:
                return False
            try:
                self._h.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
                self._h.wfile.flush()
                return True
            except (BrokenPipeError, ConnectionResetError, OSError):
                self.closed = True
                return False

    def send(self, payload: Dict[str, Any]) -> bool:
        return self._chunk(
            b"data: " + json.dumps(payload).encode("utf-8") + b"\n\n"
        )

    def send_done(self) -> bool:
        ok = self._chunk(b"data: [DONE]\n\n")
        self.close()
        return ok

    def close(self) -> None:
        with self._mu:
            if self.closed:
                return
            self.closed = True
            try:
                self._h.wfile.write(b"0\r\n\r\n")
                self._h.wfile.flush()
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass
        # Event backend: tells the EvHandler its chunked response is fully
        # framed so the exchange (and keep-alive slot) can complete.
        hook = getattr(self._h, "on_sse_closed", None)
        if hook is not None:
            hook()


class HttpServerThread:
    """One threaded HTTP server on its own accept thread (the reference runs
    each brpc server on a dedicated thread, master.cpp:38-58).

    stats() reports the request/accept counters the event backend also
    exposes, so the master's aggregated /metrics covers threaded planes
    too instead of silently omitting them."""

    def __init__(self, host: str, port: int, handler_cls):
        stats_mu = threading.Lock()

        class _Srv(ThreadingHTTPServer):
            daemon_threads = True
            allow_reuse_address = True
            request_queue_size = 128
            accepted_total = 0
            requests_total = 0

            def get_request(inner):
                req = super(_Srv, inner).get_request()
                with stats_mu:
                    _Srv.accepted_total += 1
                return req

            @staticmethod
            def count_request() -> None:
                with stats_mu:
                    _Srv.requests_total += 1

        self._srv_cls = _Srv
        self.server = _Srv((host, port), handler_cls)
        self.host, self.port = self.server.server_address[:2]
        self._thread = threading.Thread(
            target=self.server.serve_forever, name=f"http-{self.port}", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self._thread.join(timeout=2.0)

    def deferred_wakes(self):
        """No loop to wake (the event backend's twin coalesces them)."""
        return contextlib.nullcontext()

    def stats(self) -> Dict[str, Any]:
        return {
            "backend": "threaded",
            "accepted_total": self._srv_cls.accepted_total,
            "requests_total": self._srv_cls.requests_total,
        }


def make_http_server(
    backend: str,
    host: str,
    port: int,
    *,
    do_get=None,
    do_post=None,
    name: str = "http",
    workers: int = 32,
    max_connections: int = 4096,
    idle_timeout_s: float = 120.0,
    max_stream_buffer: int = 512 * 1024,
    drain_timeout_s: float = 5.0,
    max_body_bytes: int = 256 * 1024 * 1024,
):
    """Build one control-plane HTTP server on the selected backend.

    "threaded": stdlib ThreadingHTTPServer — a thread per connection plus a
    blocked thread per in-flight stream. "event": evserve's selectors/epoll
    loop — streams hold sockets, not threads, which is what carries the
    front end past ~1k concurrent SSE streams. Both return the same
    surface: start/stop/host/port/stats, and hand handlers the same
    HttpJsonApi + hold() API.
    """
    if backend == "threaded":

        class _Handler(QuietHandler):
            def do_GET(self):
                self.server.count_request()
                if do_get is None:
                    self.send_error_json(405, "method not allowed")
                else:
                    do_get(self)

            def do_POST(self):
                self.server.count_request()
                if do_post is None:
                    self.send_error_json(405, "method not allowed")
                else:
                    do_post(self)

        return HttpServerThread(host, port, _Handler)
    if backend != "event":
        raise ValueError(f"unknown http backend {backend!r}")

    from xllm_service_tpu.api.evserve import EventLoopHttpServer

    def app(h) -> None:
        if h.command == "GET" and do_get is not None:
            do_get(h)
        elif h.command == "POST" and do_post is not None:
            do_post(h)
        else:
            h.send_error_json(405, f"method {h.command} not allowed")

    return EventLoopHttpServer(
        host, port, app,
        name=name, workers=workers, max_connections=max_connections,
        idle_timeout_s=idle_timeout_s, max_stream_buffer=max_stream_buffer,
        drain_timeout_s=drain_timeout_s, max_body_bytes=max_body_bytes,
    )


# ---------------------------------------------------------------------------
# outbound JSON client with per-thread connection reuse
# ---------------------------------------------------------------------------

_tls = threading.local()


def _conn_for(addr: str, timeout: float) -> http.client.HTTPConnection:
    cache: Dict[str, http.client.HTTPConnection] = getattr(_tls, "conns", None) or {}
    _tls.conns = cache
    conn = cache.get(addr)
    if conn is None:
        host, _, port = addr.partition(":")
        conn = http.client.HTTPConnection(host, int(port or 80), timeout=timeout)
        cache[addr] = conn
    else:
        conn.timeout = timeout
        if conn.sock is not None:
            conn.sock.settimeout(timeout)
    return conn


def post_json(
    addr: str, path: str, body: Dict[str, Any], timeout: float = 30.0
) -> Tuple[int, Dict[str, Any]]:
    """POST with one retry, but ONLY on send-time failures (stale kept-alive
    connection). Once the request has been written, a failure is raised, not
    retried — POSTs here are not idempotent (a re-send would dispatch the
    same generation twice). Send-time failures surface as
    RequestNotSentError so callers (post_json_retrying) know a retry is
    safe; anything later is indeterminate."""
    payload = json.dumps(body).encode("utf-8")
    # Chaos hooks: "...send" simulates a request that never reaches the
    # peer (partition/refused), "...recv" one that was delivered but whose
    # response was lost (the indeterminate case).
    faults.point("post_json.send", addr=addr, path=path)
    for attempt in (0, 1):
        conn = _conn_for(addr, timeout)
        try:
            conn.request(
                "POST", path, body=payload,
                headers={"Content-Type": "application/json"},
            )
        except Exception as e:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            if attempt:
                raise RequestNotSentError(
                    f"POST {addr}{path} never sent: {e}"
                ) from e
            continue
        try:
            faults.point("post_json.recv", addr=addr, path=path)
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, (json.loads(data) if data else {})
        except Exception:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            raise
    raise RuntimeError("unreachable")


class RetryBudget:
    """Global retry budget (token bucket): every first attempt deposits
    `ratio` tokens, every retry withdraws one. Caps retry traffic at
    ~ratio x the request rate fleet-wide, so one flapping instance can't
    amplify into a retry storm. A `min_tokens` floor keeps sporadic
    failures retryable at low request rates."""

    def __init__(
        self, ratio: float = 0.2, min_tokens: float = 10.0,
        max_tokens: float = 100.0,
    ):
        self._ratio = float(ratio)
        self._min = float(min_tokens)
        self._max = float(max_tokens)
        self._tokens = self._min
        self._mu = threading.Lock()
        self.exhausted_total = 0  # withdrawals refused

    def deposit(self) -> None:
        with self._mu:
            self._tokens = min(self._tokens + self._ratio, self._max)

    def withdraw(self) -> bool:
        with self._mu:
            if self._tokens >= 1.0:
                self._tokens -= 1.0
                return True
            self.exhausted_total += 1
            return False

    @property
    def tokens(self) -> float:
        with self._mu:
            return self._tokens


def post_json_retrying(
    addr: str,
    path: str,
    body: Dict[str, Any],
    timeout: float = 30.0,
    *,
    attempts: int = 3,
    budget: Optional[RetryBudget] = None,
    idempotent: bool = False,
    backoff_base_s: float = 0.05,
    backoff_max_s: float = 2.0,
) -> Tuple[int, Dict[str, Any]]:
    """post_json under jittered exponential backoff.

    Retries are gated three ways: the per-call `attempts` bound, the
    shared `budget` (a refused withdrawal ends the retries immediately),
    and the idempotency rule — non-idempotent calls retry ONLY failures
    proven send-time (`request_was_sent` False); an indeterminate failure
    re-raises at once so a generation can never be dispatched twice.
    """
    if budget is not None:
        budget.deposit()
    last: Optional[BaseException] = None
    for i in range(max(attempts, 1)):
        if i:
            if budget is not None and not budget.withdraw():
                break
            delay = min(backoff_base_s * (2 ** (i - 1)), backoff_max_s)
            time.sleep(delay * random.uniform(0.5, 1.5))
        try:
            return post_json(addr, path, body, timeout=timeout)
        except Exception as e:  # noqa: BLE001 — classified below
            last = e
            if not idempotent and request_was_sent(e):
                raise
    assert last is not None
    raise last


def post_bytes_raw(
    addr: str, path: str, data: bytes, timeout: float = 60.0
) -> Tuple[int, bytes]:
    """Binary POST returning the RAW response body (the /kv/fetch reply is
    a kv frame, not JSON). Same send-time-only retry rule as post_json."""
    for attempt in (0, 1):
        conn = _conn_for(addr, timeout)
        try:
            conn.request(
                "POST", path, body=data,
                headers={"Content-Type": "application/octet-stream"},
            )
        except Exception as e:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            if attempt:
                raise RequestNotSentError(
                    f"POST {addr}{path} never sent: {e}"
                ) from e
            continue
        try:
            resp = conn.getresponse()
            return resp.status, resp.read()
        except Exception:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            raise
    raise RuntimeError("unreachable")


def post_bytes(
    addr: str, path: str, data: bytes, timeout: float = 60.0
) -> Tuple[int, Dict[str, Any]]:
    """Binary POST with a JSON response (KV handoff payloads) — the raw
    transport with the body parsed."""
    status, body = post_bytes_raw(addr, path, data, timeout=timeout)
    return status, (json.loads(body) if body else {})


def get_raw(
    addr: str, path: str, timeout: float = 30.0
) -> Tuple[int, bytes, str]:
    """GET returning (status, body bytes, content type) — for verbatim
    passthrough."""
    for attempt in (0, 1):
        conn = _conn_for(addr, timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return (
                resp.status,
                resp.read(),
                resp.getheader("Content-Type", "application/octet-stream"),
            )
        except Exception:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            if attempt:
                raise
    raise RuntimeError("unreachable")


def get_json(addr: str, path: str, timeout: float = 30.0) -> Tuple[int, Any]:
    for attempt in (0, 1):
        conn = _conn_for(addr, timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            data = resp.read()
            try:
                return resp.status, json.loads(data) if data else {}
            except json.JSONDecodeError:
                return resp.status, data.decode("utf-8", "replace")
        except Exception:
            conn.close()
            getattr(_tls, "conns", {}).pop(addr, None)
            if attempt:
                raise
    raise RuntimeError("unreachable")
