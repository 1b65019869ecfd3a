"""Per-request ordered output: a strand per request, no thread per token.

The reference guarantees per-request token order by hashing each request to
one of 128 single-thread pools (reference: scheduler.h:112-117, dispatch at
scheduler.cpp:312-333). That costs one queue put and one thread wake per
token, and in a process that shares one interpreter with its engine the
wakes are what a decode step waits for. Here the order is kept without a
thread: every request owns a `Strand`, and whoever submits to an idle strand
runs it dry on its own thread, so a pushed batch of tokens is delivered in
one pass by the thread that received it.

What a strand cannot give is isolation from a write that blocks (the
threaded HTTP backend's socket write to a client that stopped reading).
Such a strand is run dry on one of `HopThreads`' daemon threads instead,
started as they are needed: a stalled client then holds one of them and
nobody else's tokens.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Callable, Deque, List, Optional

logger = logging.getLogger(__name__)


class HopThreads:
    """Up to `max_threads` daemon threads, none until the first submit, for
    what must not run on the submitter's thread."""

    def __init__(self, max_threads: int = 128, name: str = "ordered-out"):
        self._max = max(1, max_threads)
        self._name = name
        self._cv = threading.Condition()
        self._q: Deque[Callable[[], None]] = deque()
        self._idle = 0  # threads waiting that no submit has claimed yet
        self._threads: List[threading.Thread] = []
        self._stopped = False

    @property
    def num_threads(self) -> int:
        return len(self._threads)

    def submit(self, fn: Callable[[], None]) -> None:
        with self._cv:
            self._q.append(fn)
            if self._idle:
                self._idle -= 1
                self._cv.notify()
            elif len(self._threads) < self._max:
                t = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"{self._name}-{len(self._threads)}",
                )
                self._threads.append(t)
                t.start()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._q:
                    if self._stopped:
                        return
                    self._idle += 1
                    self._cv.wait()
                fn = self._q.popleft()
            try:
                fn()
            except Exception:
                logger.exception("hopped call failed")

    def shutdown(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=2.0)


class Strand:
    """One request's deliveries, failure and fences: never two at once, and
    in the order they were submitted, from whatever threads they come (a PD
    pair pushes one request from two instances; the failure detector and a
    resume's fence come from theirs). A call that raises is logged and the
    strand goes on. Submitting from inside a strand's own call is safe: the
    call is appended and runs when the current one returns."""

    __slots__ = ("_mu", "_q", "_busy")

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._q: Deque[Callable[[], None]] = deque()
        self._busy = False

    def submit(
        self, fn: Callable[[], None], hop: Optional[HopThreads] = None
    ) -> bool:
        """True when `fn` has run on the caller's thread by the time this
        returns. False when it was left to another thread: the one that
        holds the strand, or one of `hop`'s."""
        with self._mu:
            if self._busy:
                self._q.append(fn)
                return False
            self._busy = True
        if hop is not None:
            hop.submit(lambda: self._run_dry(fn))
            return False
        self._run_dry(fn)
        return True

    def _run_dry(self, fn: Callable[[], None]) -> None:
        while True:
            try:
                fn()
            except Exception:
                logger.exception("delivery failed")
            with self._mu:
                if not self._q:
                    self._busy = False
                    return
                fn = self._q.popleft()
