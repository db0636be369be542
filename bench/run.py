"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output,
and each compared number beside its limit as the last lines of standard
error.  Exits 2, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  Set-up is timed from the start of this file.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.core import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
