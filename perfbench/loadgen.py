"""Drives the program's public serve path with a run's traffic.

Only ``ServeFrontend.submit`` and ``ServeFrontend.step`` are called, the
two calls the program's own ``serve_open_loop`` makes. Every time is
the harness's own ``time.perf_counter``: a request counts from when it
was due, and a token from when ``step`` returned with it in the
request's handle.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from perfbench.traffic import TrafficRequest


@dataclass
class Record:
    """What the harness saw of one request."""
    req: TrafficRequest
    due_t: float                       # absolute due time
    handle: object = None
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finish: str = ""                   # finish reason once resolved
    prompt_tokens: int = 0             # from the response's usage
    cached_tokens: int = 0

    @property
    def first_t(self) -> Optional[float]:
        return self.token_t[0] if self.token_t else None


def delivered(handle) -> int:
    """Tokens the handle holds so far. The handle streams through its
    event buffer; reading its length is the non-blocking poll a loop
    of many handles on one thread needs (``tokens()`` would step the
    serve loop itself)."""
    return sum(1 for ev in handle._events if ev.kind == "token")


class OpenLoop:
    """Submits each request at its due time, steps the serve loop in
    between, and stamps every token as its handle receives it."""

    def __init__(self, frontend, make_request: Callable[[TrafficRequest], object],
                 annotate: Optional[Callable[[str], object]] = None,
                 clock=time.perf_counter):
        self.fe = frontend
        self.make_request = make_request
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.clock = clock
        self.step_times: List[tuple] = []   # (start, seconds) per step
        self.live: List[Record] = []

    def _submit(self, rec: Record) -> None:
        request = self.make_request(rec.req)
        with self.annotate("harness.submit"):
            rec.handle = self.fe.submit(request)
        self.live.append(rec)
        self._poll(rec, self.clock())

    def _poll(self, rec: Record, t: float) -> None:
        n = delivered(rec.handle)
        while len(rec.token_t) < n:
            rec.token_t.append(t)
        if rec.handle.done():
            self._resolve(rec)

    def _resolve(self, rec: Record) -> None:
        resp = rec.handle.response
        rec.finish = resp.finish_reason
        rec.tokens = list(resp.new_tokens)
        u = resp.usage
        rec.prompt_tokens = u.prompt_tokens
        rec.cached_tokens = u.cached_tokens
        rec.handle = None
        self.live.remove(rec)

    def step(self) -> None:
        with self.annotate("harness.step"):
            a = self.clock()
            self.fe.step()
            t = self.clock()
        self.step_times.append((a, t - a))
        for rec in list(self.live):
            self._poll(rec, t)

    def run(self, reqs: List[TrafficRequest], t0: float,
            until: Callable[[float, List[Record]], bool],
            on_tick: Optional[Callable[[float], None]] = None
            ) -> List[Record]:
        """Serve ``reqs`` due at ``t0 + due_s`` until ``until(now,
        records)`` holds; ``on_tick(now)`` is called once per loop turn
        (the caller's window bookkeeping). Returns a record per
        submitted request."""
        records: List[Record] = []
        i, n = 0, len(reqs)
        while True:
            now = self.clock()
            if on_tick is not None:
                on_tick(now)
            while i < n and t0 + reqs[i].due_s <= now:
                rec = Record(req=reqs[i], due_t=t0 + reqs[i].due_s)
                records.append(rec)
                self._submit(rec)
                i += 1
            if until(self.clock(), records):
                return records
            if self.fe.has_work():
                self.step()
            elif i < n:
                wait = t0 + reqs[i].due_s - self.clock()
                if wait > 0:
                    with self.annotate("harness.wait"):
                        time.sleep(min(wait, 0.05))
            else:
                return records

    def cancel_all(self) -> None:
        """Cancel what is still in flight; the partial responses keep
        the tokens already served."""
        for rec in list(self.live):
            rec.handle.cancel()
            t = self.clock()
            self._poll(rec, t)
