"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload xl2-256.batch --seed 7 --seconds 30 --trace 0

Exits non-zero, printing no result, without a TPU (or with fewer chips than
the cell asks for) and without the system under test beside it.
"""
import time

PROCESS_T0 = time.monotonic()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(process_t0=PROCESS_T0))
