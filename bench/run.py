"""Benchmark entry point: one run of one cell, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the run
exits 2 without it when no TPU is attached or a cell cannot run.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], t_start=T_START))
