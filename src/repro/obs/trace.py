"""Per-request lifecycle tracing for the serve plane.

One ``Span`` per request, assembled ENTIRELY from host-side event points
that already exist on the serve path — submit (frontend), admit (slot
occupied), each prefill chunk, first token, decode/burst token replay,
and the terminal resolution (finish / shed / cancel / timeout).  Every
timestamp is ``time.perf_counter()`` taken in host code the engine was
already running (the ``drain_deltas()``/``_consume_reason`` replay), so
tracing adds ZERO device->host syncs: the PR-5 transfer-guard contract
(decode moves only ``(max_batch,)`` int32 ids) holds with tracing on.

The tracer doubles as the per-service latency instrument: when built
with a ``MetricsRegistry`` it observes ``queue_wait_s`` at admit,
``ttft_s`` at first token, ``itl_s`` per decode token (burst iterations
spread their replay wall evenly over the K tokens) and ``e2e_s`` at
finish, labeled by model — the TTFT/ITL distributions Algorithm-1-style
control loops need, at histogram-update cost.

Beside the request spans, ``phase(name)`` marks a phase of the serve
step (``gateway.step``, ``engine.decode``, ``engine.sync``, ...) on the
profiler's own clock: it is ``jax.profiler.TraceAnnotation``, so its
spans land in the same trace as the device's ops and exist only while a
profile is being captured (about half a microsecond per span
otherwise). Request spans are always on and exported; phase spans are
read from a profiler trace.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax

from repro.obs.metrics import MetricsRegistry

# ``with phase("engine.decode"): ...`` -- a span of the serve step in the
# profiler's trace; no keyword arguments (nothing reads them, and they
# double the cost of a span)
phase = jax.profiler.TraceAnnotation


@dataclass
class Span:
    """One request's lifecycle. Timestamps are ``perf_counter`` values;
    0.0 means the phase never happened (e.g. shed before admission)."""
    uid: int
    model: str = ""
    backend: str = ""
    submit_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    last_token_t: float = 0.0
    chunks: int = 0                   # prefill passes
    chunk_tokens: int = 0             # prompt tokens actually prefilled
    decode_tokens: int = 0            # tokens sampled (incl. first)
    outcome: str = ""                 # stop|length|shed|cancelled|timeout
    chip_seconds: float = 0.0         # attributed device-seconds x chips
    cost_usd: float = 0.0             # chip_seconds at USD_PER_CHIP_HOUR
    # (event, t, value) in order: submit/admit/chunk/first_token/
    # decode (one entry per drain, value = tokens)/finish
    events: List[Tuple[str, float, float]] = field(default_factory=list)

    # -- derived phase durations ----------------------------------------
    @property
    def queue_wait_s(self) -> float:
        return max(self.admit_t - self.submit_t, 0.0) if self.admit_t else 0.0

    @property
    def prefill_s(self) -> float:
        if not (self.admit_t and self.first_token_t):
            return 0.0
        return max(self.first_token_t - self.admit_t, 0.0)

    @property
    def decode_s(self) -> float:
        if not (self.first_token_t and self.finish_t):
            return 0.0
        return max(self.finish_t - self.first_token_t, 0.0)

    @property
    def ttft_s(self) -> float:
        if not (self.submit_t and self.first_token_t):
            return 0.0
        return max(self.first_token_t - self.submit_t, 0.0)

    @property
    def e2e_s(self) -> float:
        return max(self.finish_t - self.submit_t, 0.0) if self.finish_t else 0.0

    def complete(self) -> bool:
        """Full lifecycle recorded: queue -> prefill chunk(s) -> first
        token -> decode -> finish."""
        return bool(self.admit_t and self.chunks >= 1 and self.first_token_t
                    and self.decode_tokens >= 1 and self.finish_t
                    and self.outcome)

    def to_dict(self) -> dict:
        return {
            "uid": self.uid, "model": self.model, "backend": self.backend,
            "outcome": self.outcome, "submit_t": self.submit_t,
            "admit_t": self.admit_t, "first_token_t": self.first_token_t,
            "finish_t": self.finish_t, "queue_wait_s": self.queue_wait_s,
            "prefill_s": self.prefill_s, "decode_s": self.decode_s,
            "ttft_s": self.ttft_s, "e2e_s": self.e2e_s,
            "chunks": self.chunks, "chunk_tokens": self.chunk_tokens,
            "decode_tokens": self.decode_tokens,
            "chip_seconds": self.chip_seconds, "cost_usd": self.cost_usd,
            "events": [list(e) for e in self.events],
        }


class Tracer:
    """Collects spans. Open spans live in a uid-keyed dict; finished
    spans move to a bounded ring (``max_spans``) for export. Events for
    unknown uids open a span lazily at admit (standalone engines), and
    negative uids (warm-up probes) are ignored so compile-time TTFTs
    never pollute the distributions."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 max_spans: int = 4096, keep_events: bool = True):
        self.registry = registry
        self.keep_events = keep_events
        self._live: Dict[int, Span] = {}
        self.finished: Deque[Span] = deque(maxlen=max_spans)

    def __len__(self) -> int:
        return len(self.finished)

    # -- lifecycle event points -----------------------------------------
    def on_submit(self, uid: int, model: str, backend: str,
                  t: float) -> None:
        if uid < 0:
            return
        span = Span(uid=uid, model=model, backend=backend, submit_t=t)
        if self.keep_events:
            span.events.append(("submit", t, 0.0))
        self._live[uid] = span

    def on_admit(self, uid: int, t: float, arrival_t: float = 0.0,
                 model: str = "", backend: str = "") -> None:
        if uid < 0:
            return
        span = self._live.get(uid)
        if span is None:                # standalone engine: open lazily
            span = Span(uid=uid, model=model, backend=backend,
                        submit_t=arrival_t or t)
            self._live[uid] = span
        span.admit_t = t
        if self.keep_events:
            span.events.append(("admit", t, 0.0))
        if self.registry is not None:
            self.registry.histogram("queue_wait_s", span.model).observe(
                span.queue_wait_s)

    def on_chunk(self, uid: int, t: float, n: int) -> None:
        span = self._live.get(uid)
        if span is None:
            return
        span.chunks += 1
        span.chunk_tokens += n
        if self.keep_events:
            span.events.append(("chunk", t, float(n)))

    def on_first_token(self, uid: int, t: float) -> None:
        span = self._live.get(uid)
        if span is None:
            return
        span.first_token_t = t
        span.last_token_t = t
        span.decode_tokens += 1
        if self.keep_events:
            span.events.append(("first_token", t, 1.0))
        if self.registry is not None:
            self.registry.histogram("ttft_s", span.model).observe(span.ttft_s)

    def on_tokens(self, uid: int, t: float, n: int = 1) -> None:
        """``n`` decode tokens landed for ``uid`` at host time ``t`` —
        one call per request per drain (a burst replay passes its whole
        accepted run, and the wall since the previous token spreads
        evenly over it)."""
        span = self._live.get(uid)
        if span is None or n <= 0:
            return
        if self.registry is not None and span.last_token_t:
            itl = max(t - span.last_token_t, 0.0) / n
            h = self.registry.histogram("itl_s", span.model)
            for _ in range(n):
                h.observe(itl)
        span.decode_tokens += n
        span.last_token_t = t
        if self.keep_events:
            span.events.append(("decode", t, float(n)))

    def on_finish(self, uid: int, t: float, outcome: str) -> Optional[Span]:
        """Close ``uid``'s span with its terminal resolution and move it
        to the finished ring. Returns the span (None if unknown)."""
        span = self._live.pop(uid, None)
        if span is None:
            return None
        span.finish_t = t
        span.outcome = outcome
        if self.keep_events:
            span.events.append(("finish", t, 0.0))
        if self.registry is not None:
            self.registry.histogram("e2e_s", span.model).observe(span.e2e_s)
        self.finished.append(span)
        return span

    # -- export ----------------------------------------------------------
    def drain(self) -> List[Span]:
        out = list(self.finished)
        self.finished.clear()
        return out

    def records(self) -> List[dict]:
        return [s.to_dict() for s in self.finished]
