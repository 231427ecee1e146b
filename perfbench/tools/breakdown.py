"""Reduces a kept profiler trace by the program's spans and scopes.

    python perfbench/tools/breakdown.py <trace dir> [--ops REGEX]

``<trace dir>`` is what ``tools/keep_trace.py --out`` wrote. Prints one
JSON object: the window, busy time, engine steps (``engine.step`` spans
begun in the window), idle seconds by innermost span and inside each
span, device seconds by named scope (unscoped ops under their program),
the per-step readings ``host_idle_ms``, ``weight_cast_ms`` and
``unembed_ms``, the longest idle gaps, the ops that took the most device
time with their name stacks, and each program's launches. ``--ops``
adds the device seconds (whole trace) of the ops whose names match, by
name stack.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import spans  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("--ops", default=None,
                   help="also list every op whose name matches this regex")
    a = p.parse_args()
    tr = spans.read_dir(a.trace_dir)
    s = spans.reduce_trace(tr)
    steps = s.engine_steps
    idle = s.window_s - s.busy_s
    out = {
        "window_s": s.window_s, "busy_s": s.busy_s, "idle_s": idle,
        "engine_steps": steps,
        "per_step_ms": {
            "host_idle_ms": spans.host_idle_ms(s, steps),
            "weight_cast_ms": spans.scope_ms(s, "weight_cast", steps),
            "unembed_ms": spans.scope_ms(s, "unembed", steps)},
        "idle_by_span": dict(sorted(s.idle_by_span.items(),
                                    key=lambda x: -x[1])),
        "idle_in": dict(sorted(s.idle_in.items(), key=lambda x: -x[1])),
        "device_by_scope": dict(sorted(s.device_by_scope.items(),
                                       key=lambda x: -x[1])),
        "op_s": s.op_s,
        "unscoped_s": sum(v for k, v in s.device_by_scope.items()
                          if k not in spans.SCOPES),
        "top_gaps": s.top_gaps,
        "top_ops": s.top_ops,
        "launches": s.launches,
    }
    if a.ops:
        rx = re.compile(a.ops)
        found = {}
        for ops in tr.ops.values():
            for name, b, e, stack in ops:
                if rx.search(name):
                    key = f"{name} @ {stack}"
                    found[key] = found.get(key, 0.0) + (e - b)
        out["ops_matching"] = found
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
