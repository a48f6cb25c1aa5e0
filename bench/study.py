#!/usr/bin/env python3
"""Several runs of one cell in one process (one process holds the chip):
the readings that set a cell's limits, and runs with one setting of the
configuration or the traffic mix changed. Not part of a benchmark run.

    python3 bench/study.py --workload rb11.serve_closed --seconds 10 \
        --seeds 1,2,3 --control-seeds 4,5,6
    python3 bench/study.py --workload rb11.serve_closed --seconds 10 \
        --seeds 1 --sweep config.matmul_precision=null,"highest"

Prints one JSON line per run: its seed, whether it was the control (the
reference in the lower precision put in the program's place), the fault
planted (``--fault``, from ``bench/faults.py``), the overrides, and the
run's result line.
"""
import contextlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.faults import plant  # noqa: E402
from bench.harness import DeviceError, run_cell  # noqa: E402


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--sweep", default=None,
                    help="section.key=v1,v2,...: one run per value and seed")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None,
                    help="a fault of bench/faults.py, planted in every run")
    args = ap.parse_args(argv)
    variants = [None]
    if args.sweep:
        key, vals = args.sweep.split("=", 1)
        section, name = key.split(".", 1)
        variants = [{section: {name: json.loads(v)}}
                    for v in vals.split(",")]
    runs = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    with plant(args.fault) if args.fault else contextlib.nullcontext():
        for over in variants:
            for seed, control in runs:
                try:
                    out = run_cell(args.workload, seed, args.seconds,
                                   bool(args.trace),
                                   t_start=time.perf_counter(),
                                   overrides=over, control=control)
                except DeviceError as e:
                    print(f"study: {e}", file=sys.stderr)
                    return 2
                print(json.dumps({"seed": seed, "control": control,
                                  "fault": args.fault, "overrides": over,
                                  "result": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
