#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits non-zero, with no result line,
unless JAX's devices are TPUs enough for the cell. See bench/harness.py.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here, before any import

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(root=ROOT, t_start=T_START))
