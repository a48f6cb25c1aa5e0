"""Request batching: group pending requests per target model, pad to the
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

A batch's rows are right-padded to one width; ``left_align`` moves each
row's prompt to the right end for the decode engine, so that every
prompt ends at the same step and no row replays pads as tokens."""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional

import numpy as np

_counter = itertools.count()


@dataclasses.dataclass
class Request:
    tokens: np.ndarray                      # (S,) prompt token ids
    x_emb: Optional[np.ndarray] = None      # router features
    x_feat: Optional[np.ndarray] = None
    domain: int = 0
    sample_idx: int = -1                    # replay-table row (quality/cost)
    rid: int = dataclasses.field(default_factory=lambda: next(_counter))


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


class RequestBatcher:
    def __init__(self, max_batch: int = 8, pad_to_multiple: int = 4,
                 pad_token: int = 0, max_starve: int = 4,
                 flush_timeout: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.max_batch = max_batch
        self.pad_to_multiple = pad_to_multiple
        self.pad_token = pad_token
        self.max_starve = max_starve
        self.flush_timeout = flush_timeout
        self.clock = clock
        self.queues: Dict[int, List[Request]] = defaultdict(list)
        # arrival clock() per queued request, parallel to ``queues``
        self._arrivals: Dict[int, List[float]] = defaultdict(list)
        # rounds a non-empty queue has been passed over (aging)
        self._age: Dict[int, int] = defaultdict(int)

    def submit(self, target: int, req: Request) -> None:
        self.queues[target].append(req)
        self._arrivals[target].append(self.clock())

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def _ready(self, target: int, now: float, force: bool) -> bool:
        q = self.queues[target]
        if force or self.flush_timeout is None or len(q) >= self.max_batch:
            return True
        return now - self._arrivals[target][0] >= self.flush_timeout

    def next_batch(self, force: bool = False):
        """Pop up to max_batch requests for the highest-priority READY
        queue. Returns (target, requests, padded_tokens (B, S)) or None
        — None either because nothing is pending or because no queue is
        ready yet (partial fills still inside their flush window).
        ``force=True`` treats every non-empty queue as ready (drain).

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
        if not self.pending():
            return None
        now = self.clock()
        ready = [t for t in self.queues if self._ready(t, now, force)]
        if not ready:
            return None
        starving = [t for t in ready if self._age[t] >= self.max_starve]
        if starving:
            target = max(starving, key=lambda t: (self._age[t], -t))
        else:
            target = max(ready,
                         key=lambda t: (len(self.queues[t]) + self._age[t],
                                        -t))
        q = self.queues[target]
        reqs, self.queues[target] = q[:self.max_batch], q[self.max_batch:]
        self._arrivals[target] = self._arrivals[target][len(reqs):]
        if not self.queues[target]:
            del self.queues[target]
            self._arrivals.pop(target, None)
        self._age.pop(target, None)
        for t in self.queues:
            if t != target:
                self._age[t] += 1
        max_len = max(len(r.tokens) for r in reqs)
        max_len = -(-max_len // self.pad_to_multiple) * self.pad_to_multiple
        toks = np.full((len(reqs), max_len), self.pad_token, np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.tokens)] = r.tokens
        return target, reqs, toks
