"""Reduces a JAX profiler trace of one window to busy time, op times and
idle gaps.

The harness wraps its own calls in ``TraceAnnotation`` spans
(``harness.window`` around the window, ``harness.step``,
``harness.submit`` and ``harness.wait`` inside it). The device's op
events come from the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.
Busy time is the union of the op intervals inside the window, averaged
over the chips; an idle gap is a stretch of the window in which no op
ran, named after the harness span the host was in at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
WINDOW = "harness.window"
HOST_SPANS = ("harness.step", "harness.submit", "harness.wait")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the chips traced
    op_s: Dict[str, float]              # device seconds by op name
    top_ops: List[list] = field(default_factory=list)
    top_gaps: List[list] = field(default_factory=list)

    def seconds_matching(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]]) -> TraceSummary:
    """``device_ops``: per chip, (op name, start, end) in seconds;
    ``host``: the harness's (span name, start, end) on the same clock."""
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = windows[0]
    spans = sorted((s, e, n) for n, s, e in host if n in HOST_SPANS)
    op_s: Dict[str, float] = {}
    busy, gaps = [], []
    for ops in device_ops.values():
        ivs = []
        for name, s, e in ops:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            if not is_control_flow(name):
                op_s[name] = op_s.get(name, 0.0) + (c[1] - c[0])
        busy.append(union_length(ivs))
        gaps += idle_gaps(ivs, lo, hi)
    chips = max(len(device_ops), 1)
    for k in op_s:
        op_s[k] /= chips
    named = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inside = [n for a, b, n in spans if a <= mid < b]
        named.append([inside[-1] if inside else "harness.loop", e - s])
    named.sort(key=lambda x: -x[1])
    top = sorted(([n, s] for n, s in op_s.items()), key=lambda x: -x[1])
    return TraceSummary(window_s=hi - lo, busy_s=sum(busy) / chips, op_s=op_s,
                        top_ops=top[:10], top_gaps=named[:10])


def op_name(text: str) -> str:
    """An op event is named by its HLO text (``%name.N = shape op(...)``);
    keep the name."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_control_flow(name: str) -> bool:
    """Loops and branches enclose the ops they run: they count towards
    busy time but not as ops of their own."""
    return name.startswith(("while", "conditional", "call"))


def read_xplane(path: str):
    """(device ops per chip, harness host spans), in seconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = device.setdefault(plane.name, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((op_name(ev.name), s,
                                s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("harness."):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return device, host


def reduce_dir(trace_dir: str) -> TraceSummary:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_events(*read_xplane(paths[0]))
