"""Run one benchmark cell once.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints the device, then the result as the last line of standard output.
Exits 2, printing no result, where JAX finds no accelerator listed in
``bench/peaks.json`` or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
