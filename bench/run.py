#!/usr/bin/env python3
"""Benchmark entry: run one cell of ``BENCHMARK.json`` on the chip(s).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's result as one JSON line, last on standard output, and
each number that decided ``correct`` beside its limit as the last lines
on standard error. Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime's own log files stay off unless the caller asks for them
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
