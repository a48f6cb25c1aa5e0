"""Benchmark driver — one section per paper table/figure plus the roofline
table. Prints ``name,us_per_call,derived``-style CSV per section.

  python -m benchmarks.run            # all (cached artifacts reused)
  python -m benchmarks.run --only rewards --refresh
"""
from __future__ import annotations

import argparse

from benchmarks import (
    bench_cost_quality,
    bench_encoders,
    bench_kernels,
    bench_protocol,
    bench_rewards,
    bench_roofline,
    bench_serving,
)
from benchmarks.common import emit_csv
from repro.common.compilation import enable_compile_cache

SECTIONS = {
    "rewards": bench_rewards.run,        # paper Fig. 2
    "encoders": bench_encoders.run,      # paper Fig. 3
    "cost_quality": bench_cost_quality.run,  # paper Fig. 4
    "kernels": bench_kernels.run,
    "roofline": bench_roofline.run,      # deliverable (g)
    "protocol": bench_protocol.run,      # sim engine vs seed host loop
    "serving": bench_serving.run,        # async engine vs sync loop
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=list(SECTIONS), default=None)
    ap.add_argument("--refresh", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(SECTIONS)
    for name in names:
        print(f"# --- {name} ---", flush=True)
        emit_csv(SECTIONS[name](refresh=args.refresh))


if __name__ == "__main__":
    main()
