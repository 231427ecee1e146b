"""The ``correct`` check's controls on the chip: for each seed, one run of
a cell at its own load (the benchmark's own set-up, window and sample),
then the check with each int8 control of the reference put in the
program's place on the same prompts and served tokens, all seeds in one
process.

    python perfbench/tools/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed: the program's compared numbers and
``correct``, and each control's, judged by the harness's own
``is_correct`` against the configuration's limit. A control has to
come out not correct. The benchmark's own runs never run the controls.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import harness  # noqa: E402

CONTROLS = ("w8", "w8a8")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    a = p.parse_args()
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = harness.parse(["--workload", a.workload, "--seed", str(seed),
                              "--seconds", str(a.seconds)])
        t = time.perf_counter()
        out = harness.run(args, t, controls=CONTROLS)
        check = out["check"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "compared": out["compared"],
            "controls": {c: {"correct": check["control." + c]["correct"],
                             "compared": check["control." + c]["compared"]}
                         for c in CONTROLS},
            "attempted": out["attempted"], "failed": out["failed"],
            "check": check,
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
