#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout on a machine that holds the cell's
chips.  The last line of standard output is the result, one JSON object;
the numbers compared for ``correct`` are printed beside their limits as
the last lines of standard error.  Without a TPU, or with fewer chips
than the cell needs, it prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
