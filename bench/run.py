#!/usr/bin/env python3
"""Run one benchmark cell once; the last line of standard output is its
result as one JSON object.

    python3 bench/run.py --workload rb11.serve_closed --seed 7 \
        --seconds 30 --trace 0

Run it from the root of a checkout, on a machine that holds the TPU
chips the cell asks for (``BENCHMARK.json``); it exits non-zero and
prints no result anywhere else. See ``bench/harness.py``.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
