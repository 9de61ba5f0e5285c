"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the name in ``BENCHMARK.json`` (see
``manifest.py``), refuses to run without the TPUs the cell asks for,
and prints one JSON object as the last line of its output:
``--trace 0`` the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics with the device's busy seconds and a breakdown.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

# run as a script, sys.path[0] is this directory: make it the checkout,
# so that `benchmark` and the program import as packages
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
