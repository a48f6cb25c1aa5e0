"""Small pieces the harness and its drivers share."""
from __future__ import annotations

import contextlib
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def split_seed(seed: int, names=("data", "router", "traffic", "weights",
                                 "checks")) -> dict:
    """Independent 31-bit sub-seeds for each use, from any whole number
    (the run's seed may exceed 64 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(w) & 0x7FFFFFFF for n, w in zip(names, words)}


class Spans:
    """Host spans in the profiler's trace (only while tracing, so the
    end-to-end runs pay nothing for them)."""

    def __init__(self, on: bool):
        self.on = bool(on)

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class Reservoir:
    """Seeded reservoir sample of at most ``k`` items from a stream whose
    length is not known in advance (Algorithm R). ``offer()`` says, when
    an item arrives, whether to keep it, so only kept items are copied."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = int(k)
        self.rng = rng
        self.seen = 0
        self.items: list = []

    def offer(self):
        """Slot to store the arriving item in, or None to skip it."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(0, self.seen))
        return j if j < self.k else None

    def put(self, slot, item) -> None:
        self.items[slot] = item


def p(values, q: float) -> float:
    """The q-th percentile (0-100) of ``values``; 0 for none."""
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else 0.0


def worst(a: float, b: float) -> float:
    """The larger reading; a NaN reading is the worst there is."""
    if a != a or b != b:
        return float("inf")
    return max(a, b)
