"""The on-chip benchmark of this repository (see BENCHMARK.json and
bench/run.py). Everything it measures is found by name: configurations
under configs/, traffic mixes under traffic/, per-layer metric readers
under metrics/."""
