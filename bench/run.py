#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result's JSON object; the numbers
the correctness check compared, each with its limit, are the last lines of
standard error.  Exits non-zero, printing no result, without a TPU.
"""

import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout root (for ``bench``) and the program, not this directory
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    main(t_start=T0)
