"""A step-synchronous fake engine: ONE thread, one callback per live request
per step, as the real engine's booking makes them (`engine._emit`).
`api/fake_engine.py` runs a thread per request, so its tokens reach the push
queue one by one and never as a batch; this one is what lets a CPU test see
the master deliver a pushed batch.

Request k's step-j tokens echo its prompt cyclically, so a client can check
its own stream's order without knowing about the others. A request finishes
after `steps` steps whatever its `max_tokens`: one that asks for more gets
more tokens a step (a client that asks for `steps * 16384` gets 16 KiB of
text a chunk, which is how the stalled-client test fills a socket).
"""

import threading
import time

from xllm_service_tpu.api.fake_engine import FakeEngine
from xllm_service_tpu.common.types import (
    FinishReason,
    RequestOutput,
    SequenceOutput,
    Status,
    StatusCode,
    Usage,
)


class StepEngine(FakeEngine):
    def __init__(self, steps: int = 50, step_s: float = 0.004,
                 gather: int = 1):
        super().__init__(token_delay_s=step_s, ttft_ms=step_s * 1000.0)
        self.steps = steps
        self.step_s = step_s
        # Hold the first step until this many requests are live, so that
        # every step of a test's run carries the whole batch.
        self.gather = gather
        self._live = []  # [req, steps done, tokens emitted]
        self._halt = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="step-engine", daemon=True
        )
        self.steps_run = 0
        self.peak_threads = 0

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._halt.set()
        self._thread.join(timeout=5.0)

    def add_request(self, req) -> None:
        self.requests_seen.append(req)
        with self._mu:
            self._live.append([req, 0, 0])
            self._active += 1

    def _loop(self) -> None:
        started = False
        while not self._halt.is_set():
            with self._mu:
                live = list(self._live)
            if not live or (not started and len(live) < self.gather):
                time.sleep(0.002)
                continue
            started = True
            t0 = time.monotonic()
            for entry in live:
                self._step_one(entry)
            self.steps_run += 1
            self.peak_threads = max(
                self.peak_threads, threading.active_count()
            )
            with self._mu:
                self._live = [e for e in self._live if e[1] >= 0]
                if not self._live:
                    started = False
            time.sleep(max(0.0, self.step_s - (time.monotonic() - t0)))

    def _step_one(self, entry) -> None:
        req, done, emitted = entry
        with self._mu:
            cancelled = self._cancelled.pop(req.request_id, False)
        if cancelled:
            entry[1] = -1
            with self._mu:
                self._active -= 1
            req.callback(RequestOutput(
                request_id=req.request_id,
                status=Status(StatusCode.CANCELLED, "cancelled"),
                finished=True, cancelled=True,
            ))
            return
        prompt = req.prompt_token_ids
        want = req.sampling.max_new_tokens
        last = done + 1 >= self.steps
        n = want - emitted if last else max(1, want // self.steps)
        toks = [prompt[(emitted + i) % len(prompt)] for i in range(n)]
        entry[1], entry[2] = (-1 if last else done + 1), emitted + n
        if last:
            with self._mu:
                self._active -= 1
        req.callback(RequestOutput(
            request_id=req.request_id,
            outputs=[SequenceOutput(
                index=0, token_ids=toks,
                finish_reason=FinishReason.LENGTH if last
                else FinishReason.NONE,
            )],
            usage=Usage(len(prompt), emitted + n),
            finished=last,
        ))
