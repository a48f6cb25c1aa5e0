"""Deterministic in-memory router fakes for the async-engine tests.

`FakeRouter` implements the engine's router protocol (id-addressed
``decide`` with a live availability mask, ``update_wave`` feedback) with
pure numpy — no jit, no device state — so the engine/fault tests can
exercise queueing, fallback, and accounting semantics in milliseconds.
`BlindFakeRouter` ignores the availability mask, forcing the engine's
fallback chains to do the remapping.
"""
import numpy as np


class FakeRouter:
    """Always prefers ``prefer``; with an availability mask, falls back
    to the lowest-index healthy arm itself (availability-aware)."""

    def __init__(self, num_arms: int, prefer: int = 0):
        self.num_actions = int(num_arms)
        self.prefer = int(prefer)
        self.update_calls = []          # learned count per update_wave
        self.slices = 0

    def decide(self, *, sample_idx, avail=None):
        ids = np.asarray(sample_idx, np.int64).reshape(-1)
        a = np.full(ids.size, self.prefer, np.int32)
        if avail is not None:
            av = np.asarray(avail)
            if av[self.prefer] <= 0:
                up = np.flatnonzero(av > 0)
                a[:] = up[0] if up.size else self.prefer
        return {"action": a, "ids": ids, "aux": {}, "n": ids.size}

    def update_wave(self, decision, served, rewards, learn_mask=None):
        n = decision["n"]
        learn = (np.ones(n, bool) if learn_mask is None
                 else np.asarray(learn_mask, bool).reshape(-1))
        learn = learn & (np.asarray(served) == decision["action"])
        self.update_calls.append(int(learn.sum()))
        return int(learn.sum())

    def end_slice(self):
        self.slices += 1


class BlindFakeRouter(FakeRouter):
    """Ignores the availability mask — decides onto ``prefer`` even when
    it is down, so the engine's fallback chain must remap."""

    def decide(self, *, sample_idx, avail=None):
        ids = np.asarray(sample_idx, np.int64).reshape(-1)
        return {"action": np.full(ids.size, self.prefer, np.int32),
                "ids": ids, "aux": {}, "n": ids.size}
