"""Runs a cell several times, each run a process of its own as the
benchmark's check runs it, and records each run's result line.

    python perfbench/tools/runs.py --workload <cell> --seconds <s> --seeds 1,2,3 [--trace 1] --out runs.jsonl

Appends one JSON object per run to ``--out`` (seed, exit code, wall
seconds, the result line, the end of standard error) and prints a short
summary of each.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--trace", default="0")
    p.add_argument("--out", required=True)
    a = p.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    for seed in a.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
               "--workload", a.workload, "--seed", seed,
               "--seconds", a.seconds, "--trace", a.trace]
        t = time.perf_counter()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        rec = {"workload": a.workload, "seed": int(seed), "trace": a.trace,
               "rc": proc.returncode, "wall_s": wall, "result": result,
               "stderr_tail": proc.stderr[-3000:]}
        with open(a.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        short = None
        if result:
            short = {"correct": result["correct"],
                     "attempted": result["attempted"],
                     "failed": result["failed"],
                     "metrics": {k: round(v["value"], 5)
                                 for k, v in result["metrics"].items()},
                     "window": result.get("window"),
                     "gap": result["compared"]["mean_logit_gap"]["value"],
                     "check": result.get("check"),
                     "mem": result["device"].get("memory_peak_bytes")}
        else:
            short = proc.stderr[-1500:]
        print(json.dumps({"seed": int(seed), "rc": proc.returncode,
                          "wall_s": round(wall, 1), "run": short}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
