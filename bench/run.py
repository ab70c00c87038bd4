"""Run one benchmark cell once:

    python3 bench/run.py --workload appc.fit --seed 7 --seconds 30 --trace 0

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root
of the checkout; ``bench/harness.py`` says what a run does. The last line
of standard output is one JSON object with the result. Without a TPU, or
with fewer chips than the cell needs, it exits with code 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
