"""Request batching: group decided rows per target model, pad to the
engine's batch granularity, preserve submission order within a group.

Flush timeouts: with ``flush_timeout`` set, a queue becomes *ready* when
it holds ``max_batch`` requests OR its oldest request has waited at least
``flush_timeout`` seconds. Deadlines are armed per request from its own
arrival time — never from the last flush. The old epoch-deadline scheme
kept a stale deadline armed across an idle period, so the first request
of a post-idle burst "expired" immediately and was flushed alone in an
undersized batch; deriving readiness from arrival timestamps makes an
empty epoch leave nothing armed (see the regression test in
tests/test_serving.py).

The selection rule lives in :class:`ArmQueues`: per-target FIFO queues
of row chunks, which the async engine fills with a decided microbatch's
row indices, one chunk per arm. A batch's rows are right-padded to one
width (:func:`pad_rows`); ``left_align`` moves each row's prompt to the
right end for the decode engine, so that every prompt ends at the same
step and no row replays pads as tokens."""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import defaultdict, deque
from typing import Callable, Dict, Optional, Sequence

import numpy as np

_counter = itertools.count()


@dataclasses.dataclass(slots=True)
class Request:
    tokens: np.ndarray                      # (S,) prompt token ids
    sample_idx: int = -1                    # replay-table row (quality/cost)
    rid: int = dataclasses.field(default_factory=_counter.__next__)


def left_align(tokens: np.ndarray, lengths, pad_token: int = 0):
    """Right-padded rows (B, S) with each row's prompt length ->
    (the rows left-padded, each row's first prompt column (B,) int32).
    Lengths over S are cut to S."""
    tokens = np.asarray(tokens)
    B, S = tokens.shape
    lengths = np.broadcast_to(np.asarray(lengths, np.int64), (B,))
    start = S - np.minimum(lengths, S)
    src = np.arange(S)[None, :] - start[:, None]
    out = np.where(src >= 0,
                   np.take_along_axis(tokens, np.maximum(src, 0), axis=1),
                   pad_token).astype(tokens.dtype)
    return out, start.astype(np.int32)


def pad_rows(prompts: Sequence[np.ndarray], pad_to_multiple: int = 4
             ) -> np.ndarray:
    """Prompts -> one right-padded (B, S) int32 matrix (pads 0), S the
    longest prompt rounded up to ``pad_to_multiple``."""
    max_len = max(len(t) for t in prompts)
    max_len = -(-max_len // pad_to_multiple) * pad_to_multiple
    toks = np.zeros((len(prompts), max_len), np.int32)
    for i, t in enumerate(prompts):
        toks[i, :len(t)] = t
    return toks


class ArmQueues:
    """Per-target FIFO queues of row chunks and the rule that picks the
    next batch. A chunk is ``rows`` (anything sliceable: a list of
    requests, an index array) that arrived together at ``arrival`` on
    ``clock``, with a ``tag`` handed back with each slice of it."""

    def __init__(self, max_batch: int, max_starve: int = 4,
                 flush_timeout: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.max_batch = max_batch
        self.max_starve = max_starve
        self.flush_timeout = flush_timeout
        self.clock = clock
        # target -> chunks [rows, first unserved row, arrival, tag]
        self._chunks: Dict[int, deque] = {}
        self._rows: Dict[int, int] = {}         # target -> rows queued
        self._pending = 0
        # rounds a non-empty queue has been passed over (aging)
        self._age: Dict[int, int] = defaultdict(int)

    def push(self, target: int, rows, arrival: float, tag=None) -> None:
        q = self._chunks.get(target)
        if q is None:
            q = self._chunks[target] = deque()
            self._rows[target] = 0
        q.append([rows, 0, arrival, tag])
        self._rows[target] += len(rows)
        self._pending += len(rows)

    def pending(self) -> int:
        return self._pending

    def _ready(self, target: int, now: float, force: bool) -> bool:
        if force or self.flush_timeout is None \
                or self._rows[target] >= self.max_batch:
            return True
        return now - self._chunks[target][0][2] >= self.flush_timeout

    def pop(self, force: bool = False):
        """Take up to ``max_batch`` rows from the highest-priority READY
        queue. Returns (target, [(tag, rows slice), ...] in arrival
        order) or None — None either because nothing is pending or
        because no queue is ready yet (partial fills still inside their
        flush window). ``force=True`` treats every non-empty queue as
        ready (drain).

        Pure fullest-first starved minority targets indefinitely: a
        queue that refills above a small queue's length every round is
        served forever while the small one waits. Round-robin aging
        fixes this in two tiers — a queue passed over ``max_starve``
        times is served unconditionally (oldest first, one starving
        queue per round: with m queues starving simultaneously the worst
        wait is ``max_starve + m - 1`` rounds), even when a majority
        backlog GROWS every round; otherwise priority is queue length
        plus age (throughput-first with drift toward fairness). Ties
        break to the lowest target id (deterministic). With a flush
        timeout, both tiers select among ready queues only — a queue
        inside its window is waiting, not passed over."""
        if not self._pending:
            return None
        now = self.clock()
        ready = [t for t in self._chunks if self._ready(t, now, force)]
        if not ready:
            return None
        age = self._age
        starving = [t for t in ready if age[t] >= self.max_starve]
        if starving:
            target = max(starving, key=lambda t: (age[t], -t))
        else:
            target = max(ready, key=lambda t: (self._rows[t] + age[t], -t))
        q = self._chunks[target]
        take = min(self.max_batch, self._rows[target])
        parts, need = [], take
        while need:
            chunk = q[0]
            rows, lo = chunk[0], chunk[1]
            if len(rows) - lo <= need:
                q.popleft()
                parts.append((chunk[3], rows[lo:] if lo else rows))
                need -= len(rows) - lo
            else:
                parts.append((chunk[3], rows[lo:lo + need]))
                chunk[1] = lo + need
                need = 0
        self._pending -= take
        if q:
            self._rows[target] -= take
        else:
            del self._chunks[target], self._rows[target]
        age.pop(target, None)
        for t in self._chunks:
            if t != target:
                age[t] += 1
        return target, parts
