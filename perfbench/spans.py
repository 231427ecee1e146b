"""Reads the program's phase spans and named scopes out of a profiler
trace, beside what ``tracereduce`` reads.

``tracereduce`` names device ops by their HLO numbering and idle gaps by
the harness's spans. This module reads the trace's ``.xplane.pb``
itself (``XSpace`` decoded by a small hand-built protobuf descriptor;
no generated ``xplane_pb2`` is needed), so it also sees

* each device op's name stack, the ``tf_op`` stat of its event metadata
  (``jit(_fused)/while/body/closed_call/mlp/weight_cast/...``), and the
  program it ran in (``program_id`` -> the ``/host:metadata`` plane);
* the program's own host spans (``gateway.*``, ``scheduler.*``,
  ``engine.*``, ``kv.*``, ``pool.*``, from ``repro.obs.phase``) beside
  the harness's.

From these it gives idle time by the innermost host span
(``idle_by_span``) and device time by named scope (``device_by_scope``:
an op counts under every scope in its name stack; an op outside all of
them counts under its program, ``jit__fused``,
``jit_convert_element_type``, ...). Busy time, op times and the window
are computed as ``tracereduce`` computes them.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from perfbench.tracereduce import (HOST_SPANS, OPS_LINE, WINDOW,
                                   idle_gaps, is_control_flow, op_name,
                                   union_length)

PROGRAM_SPANS = ("gateway.", "scheduler.", "engine.", "kv.", "pool.")
# the model step's named scopes (``jax.named_scope`` in the program)
SCOPES = ("embed", "attn", "mlp", "unembed", "weight_cast", "sample",
          "kv_write", "kv_gather", "kv_scatter")
MODULES_LINE = "XLA Modules"
UNCOVERED = "harness.loop"       # no host span: the harness's own loop

Span = Tuple[str, float, float]                  # name, start, end (s)
Op = Tuple[str, float, float, str]               # name, start, end, stack


# ---------------------------------------------------------------------------
# decoding XSpace


def _xspace_class():
    """Message class of ``XSpace`` (tsl/profiler/protobuf/xplane.proto)
    with the fields read here, under their published numbers; the
    parser skips the others."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="perfbench_xplane.proto",
                                             package="perfbench_xplane",
                                             syntax="proto3")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".perfbench_xplane." + tname

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64 = F.TYPE_INT64, F.TYPE_UINT64
    s, m = F.TYPE_STRING, F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, i64, one, None),
        ("uint64_value", 3, u64, one, None), ("str_value", 5, s, one, None))
    msg("XEvent", ("metadata_id", 1, i64, one, None),
        ("offset_ps", 2, i64, one, None), ("duration_ps", 3, i64, one, None))
    msg("XLine", ("name", 2, s, one, None),
        ("timestamp_ns", 3, i64, one, None), ("events", 4, m, rep, "XEvent"))
    msg("XEventMetadata", ("name", 2, s, one, None),
        ("stats", 5, m, rep, "XStat"))
    msg("XStatMetadata", ("name", 2, s, one, None))
    # map<int64, ...> fields are repeated (key = 1, value = 2) entries
    msg("EventMetadataEntry", ("key", 1, i64, one, None),
        ("value", 2, m, one, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, i64, one, None),
        ("value", 2, m, one, "XStatMetadata"))
    msg("XPlane", ("name", 2, s, one, None), ("lines", 3, m, rep, "XLine"),
        ("event_metadata", 4, m, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, m, rep, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, m, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def _stat(md, stat_names: Dict[int, str], name: str, kind: str):
    """The ``kind`` value (``str_value``, ``uint64_value``) of the stat
    ``name`` of an event's metadata, or None."""
    for st in md.stats:
        if stat_names.get(st.metadata_id) == name:
            return getattr(st, kind)
    return None


def _module_name(text: str) -> str:
    """``jit__fused(5765...)`` -> ``jit__fused``."""
    return text.split("(", 1)[0]


def _program_stack(module: str) -> str:
    """A program's name as a name stack: ``jit__fused`` -> ``jit(_fused)``
    (JAX names the module of ``jit(f)`` ``jit_`` + f's name)."""
    return f"jit({module[4:]})" if module.startswith("jit_") else module


def name_stack(tf_op: Optional[str], module: str) -> str:
    """An op's name stack: its ``tf_op`` stat without the trailing
    ``:<type>``, or, where the compiler left it none (or only an
    argument's name), the program's."""
    if tf_op:
        stack = tf_op.rsplit(":", 1)[0]
        if stack.startswith("jit("):
            return stack
    return _program_stack(module)


def _interval(line, ev) -> Tuple[float, float]:
    """(start, end) in seconds, from whole nanoseconds as
    ``jax.profiler.ProfileData`` gives them (and ``tracereduce`` reads)."""
    s = (line.timestamp_ns + ev.offset_ps // 1000) * 1e-9
    return s, s + (ev.duration_ps // 1000) * 1e-9


@dataclass
class Trace:
    """What one ``.xplane.pb`` holds for this reduction, in seconds."""
    ops: Dict[str, List[Op]]          # per chip: op events with name stacks
    host: List[Span]                  # harness and program spans
    modules: Dict[str, List[Span]]    # per chip: program executions


def read(path: str) -> Trace:
    XSpace = _xspace_class()
    with open(path, "rb") as f:
        space = XSpace.FromString(f.read())
    programs: Dict[int, str] = {}
    for plane in space.planes:
        if plane.name == "/host:metadata":
            for e in plane.event_metadata:       # uint64 ids, int64 keys
                programs[e.key % (1 << 64)] = _module_name(e.value.name)
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU:"):
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            stacks: Dict[int, str] = {}
            for mid, md in meta.items():
                prog = _stat(md, stat_names, "program_id", "uint64_value")
                stacks[mid] = name_stack(
                    _stat(md, stat_names, "tf_op", "str_value"),
                    programs.get(prog, ""))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out = ops.setdefault(plane.name, [])
                    for ev in line.events:
                        s, e = _interval(line, ev)
                        out.append((op_name(meta[ev.metadata_id].name), s, e,
                                    stacks[ev.metadata_id]))
                elif line.name == MODULES_LINE:
                    out = modules.setdefault(plane.name, [])
                    for ev in line.events:
                        out.append((_module_name(meta[ev.metadata_id].name),)
                                   + _interval(line, ev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith(("harness.",) + PROGRAM_SPANS):
                        host.append((name,) + _interval(line, ev))
    return Trace(ops=ops, host=host, modules=modules)


# ---------------------------------------------------------------------------
# reduction


def scopes_of(stack: str) -> List[str]:
    """The named scopes in a name stack, each once."""
    parts = set(stack.replace(";", "/").split("/"))
    return [s for s in SCOPES if s in parts]


def program_of(stack: str) -> str:
    """``jit(_fused)/...`` -> ``jit__fused``."""
    head = stack.split("/", 1)[0]
    if head.startswith("jit(") and head.endswith(")"):
        return "jit_" + head[4:-1]
    return head


def innermost(spans: List[Span]) -> List[Span]:
    """The timeline of the innermost span: disjoint (name, start, end)
    pieces, each named by the deepest span over it. Spans nest (one
    thread), so a stack suffices."""
    edges = []
    for name, s, e in spans:
        edges.append((s, 1, -e, name))
        edges.append((e, 0, 0.0, name))
    edges.sort()
    out: List[Span] = []
    stack: List[str] = []
    at = None
    for t, opening, _, name in edges:
        if at is not None and stack and t > at:
            out.append((stack[-1], at, t))
        if opening:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        at = t
    return out


def names_at(pieces: List[Span], times: List[float]) -> List[Optional[str]]:
    """Name of the piece over each time (``pieces`` disjoint, sorted)."""
    starts = [p[1] for p in pieces]
    out = []
    for t in times:
        i = bisect.bisect_right(starts, t) - 1
        out.append(pieces[i][0] if i >= 0 and t < pieces[i][2] else None)
    return out


def split_by(gaps: List[Tuple[float, float]], pieces: List[Span],
             default: str = UNCOVERED) -> Dict[str, float]:
    """Seconds of ``gaps`` under each piece's name (``pieces`` disjoint
    and sorted); parts no piece covers go to ``default``."""
    starts = [p[1] for p in pieces]
    out: Dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while j < len(pieces) and pieces[j][1] < b:
            name, s, e = pieces[j]
            o = min(b, e) - max(a, s)
            if o > 0:
                out[name] = out.get(name, 0.0) + o
                covered += o
            j += 1
        if b - a - covered > 0:
            out[default] = out.get(default, 0.0) + (b - a - covered)
    return out


@dataclass
class SpanSummary:
    window_s: float
    busy_s: float                       # mean over the chips traced
    idle_by_span: Dict[str, float]      # idle seconds by innermost span
    idle_in: Dict[str, float]           # idle seconds inside each span name
    device_by_scope: Dict[str, float]   # device seconds by scope / program
    engine_steps: int                   # engine.step spans begun in window
    op_s: float = 0.0                   # device seconds of all ops
    top_gaps: List[list] = field(default_factory=list)
    top_ops: List[list] = field(default_factory=list)   # op, stack, s
    launches: Dict[str, list] = field(default_factory=dict)


def reduce_trace(tr: Trace) -> SpanSummary:
    windows = [(s, e) for n, s, e in tr.host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    lo, hi = windows[0]
    named = [(n, s, e) for n, s, e in tr.host
             if n in HOST_SPANS or n.startswith(PROGRAM_SPANS)]
    pieces = [(n, max(s, lo), min(e, hi)) for n, s, e in innermost(named)
              if min(e, hi) > max(s, lo)]
    chips = max(len(tr.ops), 1)
    busy, gaps = [], []
    by_scope: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str], float] = {}
    for ops in tr.ops.values():
        ivs = []
        for name, s, e, stack in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            ivs.append((s, e))
            if is_control_flow(name):
                continue
            for key in scopes_of(stack) or [program_of(stack)]:
                by_scope[key] = by_scope.get(key, 0.0) + (e - s) / chips
            k = (name, stack)
            by_op[k] = by_op.get(k, 0.0) + (e - s) / chips
        busy.append(union_length(ivs))
        gaps += idle_gaps(ivs, lo, hi)
    gaps.sort()
    idle_in: Dict[str, float] = {}
    for name in {n for n, _, _ in named}:
        own = innermost([x for x in named if x[0] == name])
        idle_in[name] = split_by(gaps, own).get(name, 0.0) / chips
    names = names_at(pieces, [0.5 * (a + b) for a, b in gaps])
    top = sorted(([n or UNCOVERED, b - a] for n, (a, b) in zip(names, gaps)),
                 key=lambda x: -x[1])
    launches: Dict[str, list] = {}
    for mods in tr.modules.values():
        for name, s, e in mods:
            if lo <= s < hi:
                c = launches.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += (min(e, hi) - s) / chips
    ops_sorted = sorted(([n, st, v] for (n, st), v in by_op.items()),
                        key=lambda x: -x[2])
    steps = sum(1 for n, s, _ in tr.host if n == "engine.step" and lo <= s < hi)
    idle_by = {k: v / chips for k, v in split_by(gaps, pieces).items()}
    return SpanSummary(window_s=hi - lo, busy_s=sum(busy) / chips,
                       idle_by_span=idle_by, idle_in=idle_in,
                       device_by_scope=by_scope, engine_steps=steps,
                       op_s=sum(by_op.values()),
                       top_gaps=top[:10], top_ops=ops_sorted[:20],
                       launches=launches)


def read_dir(trace_dir: str) -> Trace:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read(paths[0])


# ---------------------------------------------------------------------------
# the per-step readings (a per-layer metric's reader returns one of these)


def host_idle_ms(s: Optional[SpanSummary], steps: int) -> Optional[float]:
    """Device idle time inside ``gateway.step`` spans in the window, per
    engine step, ms."""
    if s is None or not steps:
        return None
    return 1e3 * s.idle_in.get("gateway.step", 0.0) / steps


def scope_ms(s: Optional[SpanSummary], scope: str,
             steps: int) -> Optional[float]:
    """Device time of the ops whose name stack holds ``scope``, per
    engine step, ms (0.0 where no op does)."""
    if s is None or not steps:
        return None
    return 1e3 * s.device_by_scope.get(scope, 0.0) / steps
