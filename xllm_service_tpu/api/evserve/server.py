"""selectors/epoll event-loop HTTP server for the control plane.

One loop thread owns every socket (accept, read, write readiness, timers,
idle sweep); a small worker pool runs route handlers; the scheduler's
deliveries stream SSE tokens by enqueueing into connection outboxes and
waking the loop through a socketpair (once per pushed batch:
`deferred_wakes`). Concurrency therefore scales with open sockets
— the ThreadingHTTPServer backend spends a thread per connection and a
second blocked thread per in-flight generation, which caps the control
plane near the thread budget; this backend carries >1k concurrent SSE
streams on loop + pool threads alone (tests/test_evserve.py drives 1024).

The reference's brpc front end is the same shape: an event-driven IO layer
with ProgressiveAttachment streams detached from worker threads
(call_data.h:150-193); this subsystem is its stdlib-only analog.
"""

from __future__ import annotations

import contextlib
import heapq
import logging
import selectors
import socket
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from itertools import count
from typing import Callable, Deque, Dict, List, Optional, Set

from xllm_service_tpu.api.evserve.connection import Connection
from xllm_service_tpu.api.evserve.handler import EvHandler
from xllm_service_tpu.api.evserve.parser import HttpRequest
from xllm_service_tpu.obs import MetricsRegistry

logger = logging.getLogger(__name__)

_IDLE_SWEEP_S = 1.0

# Loop-lag buckets (ms): the event loop's per-wakeup busy time is usually
# sub-millisecond — a fatter tail here means handlers or flushes are
# stalling every stream the loop carries.
_LOOP_LAG_BUCKETS_MS = (
    0.05, 0.1, 0.2, 0.5, 1, 2, 5, 10, 20, 50, 100, 200, 500, 1000,
)


class TimerHandle:
    __slots__ = ("deadline", "fn", "cancelled")

    def __init__(self, deadline: float, fn: Callable[[], None]):
        self.deadline = deadline
        self.fn = fn
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True
        # Drop the closure now: the heap entry itself lives until the
        # deadline lapses, and a deadline timer's closure holds the whole
        # handler/connection/request graph — at rate x timeout_s scale
        # that retention dominates memory, not the live concurrency.
        self.fn = None


class _Deferring(threading.local):
    """A thread's state under `deferred_wakes()`: off for every thread
    that never entered one."""

    on = False
    owed = False


class EventLoopHttpServer:
    """Uniform server surface (start/stop/host/port/stats) shared with
    HttpServerThread, selected by ServiceConfig.http_backend."""

    def __init__(
        self,
        host: str,
        port: int,
        app: Callable[[EvHandler], None],
        *,
        name: str = "evhttp",
        workers: int = 32,
        max_connections: int = 4096,
        idle_timeout_s: float = 120.0,
        max_stream_buffer: int = 512 * 1024,
        drain_timeout_s: float = 5.0,
        # Per-request body cap. The threaded backend never enforced one, so
        # the default must clear every legitimate control-plane body — the
        # biggest is a base64 multimodal part (video ~100 MB); 256 MB keeps
        # that headroom while still bounding a hostile Content-Length.
        max_body_bytes: int = 256 * 1024 * 1024,
    ):
        self._app = app
        self._name = name
        self.max_stream_buffer = max_stream_buffer
        self.max_body_bytes = max_body_bytes
        self._max_connections = max_connections
        self._idle_timeout_s = idle_timeout_s
        self._drain_timeout_s = drain_timeout_s

        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(min(max_connections, 1024))
        self._lsock.setblocking(False)
        self.host, self.port = self._lsock.getsockname()[:2]

        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._deferring = _Deferring()

        self._mu = threading.Lock()
        self._posted: Deque[Callable[[], None]] = deque()
        self._dirty: Set[Connection] = set()
        self._timers: List = []  # heap of (deadline, seq, TimerHandle)
        self._timer_seq = count()
        self._conns: Set[Connection] = set()

        self._pool = ThreadPoolExecutor(
            max_workers=max(2, workers), thread_name_prefix=f"{name}-worker"
        )
        self._running = False
        self._draining = False
        self._drain_deadline = 0.0
        self._thread = threading.Thread(
            target=self._loop, name=f"{name}-loop", daemon=True
        )

        # stats (gauges derived, counters monotonic)
        self._accepted_total = 0
        self._rejected_connections = 0
        self._requests_total = 0
        self._slow_client_closes = 0
        self._active_streams = 0

        # Per-plane registry (the master merges it under a plane label):
        # the loop-lag histogram is the event backend's health signal —
        # one loop thread carries every stream, so its busy time per
        # wakeup bounds how stale every connection's IO can get.
        self.metrics = MetricsRegistry()
        self._m_loop_lag = self.metrics.histogram(
            "xllm_http_loop_lag_ms",
            "Event-loop busy time per wakeup (non-select work)",
            buckets=_LOOP_LAG_BUCKETS_MS,
        )

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    def start(self) -> None:
        self._running = True
        self._sel.register(self._lsock, selectors.EVENT_READ, "listen")
        self._sel.register(self._wake_r, selectors.EVENT_READ, "wake")
        self._thread.start()

    def stop(self, drain_s: Optional[float] = None) -> None:
        """Stop accepting, give in-flight streams `drain_s` to finish, then
        tear everything down."""
        if not self._running:
            return
        timeout = self._drain_timeout_s if drain_s is None else drain_s

        def begin() -> None:
            self._draining = True
            self._drain_deadline = time.monotonic() + timeout
            try:
                self._sel.unregister(self._lsock)
            except (KeyError, ValueError):
                pass
            try:
                self._lsock.close()
            except OSError:
                pass

        self.post(begin)
        self._thread.join(timeout=timeout + 5.0)
        self._running = False
        self.wake()
        self._pool.shutdown(wait=False)

    # ------------------------------------------------------------------ #
    # any-thread API (Connection/EvHandler call these)
    # ------------------------------------------------------------------ #

    def wake(self) -> None:
        if self._deferring.on:
            self._deferring.owed = True
            return
        try:
            self._wake_w.send(b"\0")
        except (BlockingIOError, OSError):
            pass  # wake pipe saturated: loop is already waking

    @contextlib.contextmanager
    def deferred_wakes(self):
        """Until the block ends, this thread's wakes of the loop (one per
        enqueue, post and timer) are owed, and paid as ONE at the end: a
        thread that delivers a batch of tokens to many connections hands
        the loop all of them at once instead of trading the interpreter
        with it once per token. Nothing inside may wait for the loop."""
        d = self._deferring
        d.on, d.owed = True, False
        try:
            yield
        finally:
            d.on = False
            if d.owed:
                self.wake()

    def post(self, fn: Callable[[], None]) -> None:
        with self._mu:
            self._posted.append(fn)
        self.wake()

    def request_flush(self, conn: Connection) -> None:
        with self._mu:
            self._dirty.add(conn)
        self.wake()

    def call_later(self, delay_s: float, fn: Callable[[], None]) -> TimerHandle:
        t = TimerHandle(time.monotonic() + delay_s, fn)
        with self._mu:
            heapq.heappush(self._timers, (t.deadline, next(self._timer_seq), t))
        self.wake()
        return t

    def note_slow_client(self) -> None:
        with self._mu:
            self._slow_client_closes += 1

    def note_stream_begin(self) -> None:
        with self._mu:
            self._active_streams += 1

    def note_stream_end(self) -> None:
        with self._mu:
            self._active_streams -= 1

    def stats(self) -> Dict[str, int]:
        with self._mu:
            conns = list(self._conns)
            return {
                "backend": "event",
                "open_connections": len(conns),
                "active_streams": self._active_streams,
                "buffered_bytes": sum(c.buffered_bytes for c in conns),
                "accepted_total": self._accepted_total,
                "rejected_connections": self._rejected_connections,
                "requests_total": self._requests_total,
                "slow_client_closes": self._slow_client_closes,
            }

    # ------------------------------------------------------------------ #
    # loop-thread internals
    # ------------------------------------------------------------------ #

    def update_interest(self, conn: Connection, want_write: bool) -> None:
        """Loop thread: recompute the selector registration. Read pauses
        while a NON-streaming outbox sits over the buffer cap (streaming
        overflow drops the client in enqueue instead) — the socket stops
        accepting new pipelined requests until the client drains what it
        already owes us. Read can only pause with bytes buffered, so the
        mask is never empty."""
        want_read = (
            conn.streaming
            or conn.buffered_bytes <= self.max_stream_buffer
        )
        events = (
            (selectors.EVENT_READ if want_read else 0)
            | (selectors.EVENT_WRITE if want_write else 0)
        )
        if conn.closed or conn.events_mask == events:
            return
        try:
            self._sel.modify(conn.sock, events, conn)
            conn.events_mask = events
        except (KeyError, ValueError, OSError):
            pass

    def forget_connection(self, conn: Connection) -> None:
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError, OSError):
            pass
        with self._mu:
            self._conns.discard(conn)

    def start_exchange(self, conn: Connection, request: HttpRequest) -> None:
        with self._mu:
            self._requests_total += 1
        handler = EvHandler(self, conn, request)
        conn.in_flight = handler
        self._pool.submit(self._run_app, handler)

    def _run_app(self, handler: EvHandler) -> None:
        try:
            self._app(handler)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away mid-response
        except Exception:
            logger.exception("%s: handler crashed on %s %s",
                            self._name, handler.command, handler.path)
            if not handler._head_sent and not handler._done:
                try:
                    handler.send_error_json(500, "internal server error")
                except Exception:
                    pass
        finally:
            try:
                handler.finalize_after_app()
            except Exception:
                logger.exception("%s: finalize failed", self._name)

    def _accept(self) -> None:
        while True:
            try:
                sock, addr = self._lsock.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            self._accepted_total += 1
            if self._draining or len(self._conns) >= self._max_connections:
                self._rejected_connections += 1
                self._shed(sock)
                continue
            sock.setblocking(False)
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            conn = Connection(self, sock, addr)
            with self._mu:
                self._conns.add(conn)
            self._sel.register(sock, selectors.EVENT_READ, conn)
            conn.events_mask = selectors.EVENT_READ

    _SHED_RESPONSE = (
        b"HTTP/1.1 503 Service Unavailable\r\n"
        b"Content-Type: application/json\r\n"
        b'Content-Length: 63\r\nConnection: close\r\n\r\n'
        b'{"error": {"message": "server overloaded", "type": "shedding"}}'
    )

    def _shed(self, sock: socket.socket) -> None:
        """Refuse an over-capacity (or draining) connection with a one-shot
        503 — load balancers and clients see an explicit shed, not a hang.
        Drain whatever request bytes already arrived first so close() sends
        FIN rather than RST-ing the 503 out of the client's receive queue."""
        sock.setblocking(False)
        try:
            sock.recv(65536)
        except OSError:
            pass
        try:
            sock.send(self._SHED_RESPONSE)
            sock.shutdown(socket.SHUT_WR)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass

    def _next_timeout(self, now: float) -> float:
        with self._mu:
            if self._timers:
                deadline = self._timers[0][0]
                return max(0.0, min(deadline - now, _IDLE_SWEEP_S))
        return _IDLE_SWEEP_S

    def _loop(self) -> None:
        last_sweep = time.monotonic()
        while True:
            now = time.monotonic()
            if self._draining:
                busy = any(c.in_flight is not None for c in self._conns)
                if not busy or now >= self._drain_deadline:
                    break
            try:
                events = self._sel.select(self._next_timeout(now))
            except OSError:
                events = []
            busy_t0 = time.monotonic()
            for key, mask in events:
                tag = key.data
                if tag == "listen":
                    self._accept()
                elif tag == "wake":
                    try:
                        while self._wake_r.recv(4096):
                            pass
                    except (BlockingIOError, OSError):
                        pass
                else:
                    conn: Connection = tag
                    if mask & selectors.EVENT_READ and not conn.closed:
                        conn.on_readable()
                    if mask & selectors.EVENT_WRITE and not conn.closed:
                        conn.on_writable()
            self._run_posted()
            self._flush_dirty()
            self._fire_timers()
            now = time.monotonic()
            self._m_loop_lag.observe((now - busy_t0) * 1000.0)
            if now - last_sweep >= _IDLE_SWEEP_S:
                last_sweep = now
                self._sweep_idle(now)
        # drain finished (or timed out): hard-close the stragglers
        for conn in list(self._conns):
            conn.close()
        self._run_posted()
        try:
            self._sel.close()
        except OSError:
            pass
        for s in (self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass

    def _run_posted(self) -> None:
        while True:
            with self._mu:
                if not self._posted:
                    return
                fn = self._posted.popleft()
            try:
                fn()
            except Exception:
                logger.exception("%s: posted callback failed", self._name)

    def _flush_dirty(self) -> None:
        with self._mu:
            dirty = list(self._dirty)
            self._dirty.clear()
        for conn in dirty:
            if not conn.closed:
                conn._flush_ready()

    def _fire_timers(self) -> None:
        now = time.monotonic()
        due = []
        with self._mu:
            while self._timers and self._timers[0][0] <= now:
                _, _, t = heapq.heappop(self._timers)
                if not t.cancelled:
                    due.append(t)
        for t in due:
            # Timer bodies may touch the scheduler — never run them on the
            # loop thread.
            self._pool.submit(self._run_timer, t)

    @staticmethod
    def _run_timer(t: TimerHandle) -> None:
        fn = t.fn  # cancel() may null it concurrently
        try:
            if not t.cancelled and fn is not None:
                fn()
        except Exception:
            logger.exception("evserve timer failed")

    def _sweep_idle(self, now: float) -> None:
        if self._idle_timeout_s <= 0:
            return
        for conn in list(self._conns):
            if (
                conn.in_flight is None
                and not conn.pending
                and now - conn.last_activity > self._idle_timeout_s
            ):
                conn.close()
