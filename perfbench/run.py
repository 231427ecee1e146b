"""Entry point of the chip benchmark; see ``perfbench/harness.py``.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs.
"""
import time

_PROCESS_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(process_t0=_PROCESS_T0))
