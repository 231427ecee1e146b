"""What one run measured, and the arithmetic the metric readers share.

A ``Run`` holds the harness's records of every request, the window, the
program's step counter read at the window's edges, and, in a traced
run, the reduced device trace. The readers under ``metrics/``
each compute one number from it and return None where the run holds
nothing for them to read.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

from perfbench import workcount
from perfbench.loadgen import Record
from perfbench.peaks import Peaks
from perfbench.stats import percentile
from perfbench.tracereduce import TraceSummary


@dataclass
class Run:
    cfg: dict                          # the configuration file
    records: List[Record]
    t0: float                          # window start (harness clock)
    window_s: float                    # nominal window length
    end_t: float                       # when measurement stopped
    open_loop: bool                    # requests due over time, not a backlog
    setup_s: float
    peaks: Optional[Peaks] = None
    engine_steps: int = 0              # program's step count in the window
    trace: Optional[TraceSummary] = None

    # -- which requests count ---------------------------------------------
    @property
    def window_end(self) -> float:
        return self.t0 + self.window_s

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if r.due_t < self.window_end]

    def attempted(self) -> int:
        return len(self.due_in_window())

    def failed(self) -> int:
        """Requests due in the window that were refused or failed; in an
        open loop also those with no first token when measurement
        stopped (a backlog's queued requests are not failures)."""
        n = 0
        for r in self.due_in_window():
            if r.finish in ("shed", "failed", "timeout"):
                n += 1
            elif self.open_loop and r.first_t is None:
                n += 1
        return n

    # -- latency ------------------------------------------------------------
    def itls(self) -> List[float]:
        """Gaps between consecutive tokens delivered to the handles of
        the requests due in the window."""
        gaps = []
        for r in self.due_in_window():
            gaps += [b - a for a, b in zip(r.token_t, r.token_t[1:])]
        return gaps

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.records for t in r.token_t
                   if self.t0 <= t < self.window_end)

    # -- work done in the window -----------------------------------------
    def decode_work(self) -> Tuple[int, workcount.Work]:
        """Decoded tokens delivered in the window (every token after a
        request's first) and their attention work."""
        n, work = 0, workcount.Work()
        for r in self.records:
            plen = r.prompt_tokens or len(r.req.prompt.encode())
            for j, t in enumerate(r.token_t):
                if j and self.t0 <= t < self.window_end:
                    n += 1
                    work = work + workcount.decode_attention(self.cfg, plen + j)
        return n, work

    def prefill_work(self) -> Tuple[int, workcount.Work]:
        """Uncached prompt tokens of the requests whose first token
        arrived in the window, and their attention work."""
        n, work = 0, workcount.Work()
        for r in self.records:
            if r.first_t is not None and self.t0 <= r.first_t < self.window_end:
                plen = r.prompt_tokens or len(r.req.prompt.encode())
                n += plen - r.cached_tokens
                work = work + workcount.prefill_attention(
                    self.cfg, r.cached_tokens, plen)
        return n, work

    def mfu(self) -> Optional[float]:
        """Model FLOPs of every token computed in the window (uncached
        prefill and decode) over the window, as a percentage of the
        chip's bf16 peak."""
        n_dec, w_dec = self.decode_work()
        n_pre, w_pre = self.prefill_work()
        tokens = n_dec + n_pre
        if not tokens or self.peaks is None:
            return None
        flops = workcount.model_flops(self.cfg, tokens, w_dec + w_pre)
        return 100.0 * flops / (self.window_s * self.peaks.bf16_flops)

    def roofline(self, ops: str, work: workcount.Work) -> Optional[float]:
        """Least time for ``work`` over the traced device time of the ops
        whose names match the pattern ``ops``, as a percentage."""
        if self.trace is None or self.peaks is None:
            return None
        secs = self.trace.seconds_matching(ops)
        if secs <= 0 or work.flops <= 0:
            return None
        least = work.least_seconds(self.peaks.bf16_flops,
                                   self.peaks.hbm_bytes_per_s)
        return 100.0 * least / secs

    def pct(self, values: List[float], p: float) -> Optional[float]:
        return percentile(values, p)
