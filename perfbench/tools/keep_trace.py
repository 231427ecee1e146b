"""Runs one traced run of a cell and keeps its profiler trace.

    python perfbench/tools/keep_trace.py --workload <cell> --seed <n> --seconds <s> --out <dir>

Prints the run's result line; the trace (``*.xplane.pb``) is copied under
``--out``. Used to record the small trace the trace-reduction test reads.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import harness  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    a = p.parse_args()
    args = harness.parse(["--workload", a.workload, "--seed", str(a.seed),
                          "--seconds", str(a.seconds), "--trace", "1"])
    out = harness.run(args, time.perf_counter(), keep_trace=a.out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
