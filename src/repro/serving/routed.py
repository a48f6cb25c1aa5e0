"""The end-to-end system the paper presumes: NeuralUCB router in front of a
pool of candidate LLMs.

The pool members are (reduced variants of) the 10 assigned architectures,
each behind a ServingEngine. A request flows:

  encode -> router.decide -> batcher -> per-arch engine generate
        -> (quality, cost) feedback -> router.update / train / rebuild

Quality feedback comes from the offline-replay table (as in the paper's
protocol — live grading is out of scope); cost feedback is REAL: it is
derived from each architecture's roofline terms (chip-seconds per token x
a $/chip-hour price), so the router is optimizing a cost model grounded in
the actual serving pool rather than the benchmark's API prices.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from repro.core.reward import utility_reward
from repro.serving.batcher import Request, RequestBatcher, left_align
from repro.serving.engine import ServingEngine


class RoutedServingPool:
    """Router-fronted serving pool. ``router`` is any bandit router
    implementing the ``decide(x_emb, x_feat, domain) -> {"action", ...}``
    / ``update(x_emb, x_feat, domain, decision, rewards)`` /
    ``end_slice(epochs)`` interface — the paper's
    :class:`repro.core.policy.NeuralUCBRouter` (including its ``ts`` /
    ``eps`` / ``boltzmann`` exploration variants, the serving face of the
    DESIGN.md §10 policy zoo) or any compatible policy object.

    The default ``c_max`` (the Eq.-1 reward normalizer) is derived from
    the pool's ACTUAL maximum sequence length: the engines cap sequences
    at ``engine.max_seq``, so normalizing by a fixed 4096-token horizon
    (the old default) compressed every realizable cost toward 0 and
    collapsed the reward's cost discrimination between arms.

    ``log`` keeps the most recent ``log_capacity`` per-request records
    (a bounded deque — under sustained traffic an unbounded list grew
    without limit and eventually OOM'd the serving process). Pass
    ``log_capacity=None`` to opt out of the bound; ``dropped_log_records``
    counts records evicted by the cap so monitoring can tell a short log
    from a trimmed one.
    """

    def __init__(self, router,
                 engines: Sequence[ServingEngine],
                 cost_per_token: Sequence[float],
                 quality_table: Optional[np.ndarray] = None,
                 c_max: Optional[float] = None,
                 cost_lambda: float = 1.0,
                 max_batch: int = 8,
                 log_capacity: Optional[int] = 10_000):
        assert len(engines) == len(cost_per_token)
        if log_capacity is not None and log_capacity <= 0:
            raise ValueError("log_capacity must be positive or None "
                             f"(unbounded), got {log_capacity}")
        self.router = router
        self.engines = list(engines)
        self.cost_per_token = np.asarray(cost_per_token, np.float64)
        self.quality_table = quality_table
        if c_max is None:
            max_seq = max(getattr(e, "max_seq", 4096) for e in engines)
            c_max = float(self.cost_per_token.max() * max_seq)
        self.c_max = c_max
        self.cost_lambda = cost_lambda
        self.batcher = RequestBatcher(max_batch=max_batch)
        self.log: Deque[Dict] = deque(maxlen=log_capacity)
        self.dropped_log_records = 0

    def submit(self, requests: Sequence[Request]) -> List[Dict]:
        """Route + serve a wave of requests; returns per-request records."""
        x_emb = np.stack([r.x_emb for r in requests])
        x_feat = np.stack([r.x_feat for r in requests])
        domain = np.array([r.domain for r in requests], np.int32)
        decision = self.router.decide(x_emb, x_feat, domain)
        for r, a in zip(requests, decision["action"]):
            self.batcher.submit(int(a), r)

        records: Dict[int, Dict] = {}
        while True:
            nb = self.batcher.next_batch()
            if nb is None:
                break
            target, reqs, toks = nb
            eng = self.engines[target]
            toks, start = left_align(toks, [len(r.tokens) for r in reqs])
            t0 = time.time()
            new_tokens, _ = eng.generate(toks, max_new=8, start=start)
            wall = time.time() - t0
            for i, r in enumerate(reqs):
                n_tok = len(r.tokens) + new_tokens.shape[1]
                cost = float(self.cost_per_token[target] * n_tok)
                q = 0.5
                if self.quality_table is not None and r.sample_idx >= 0:
                    q = float(self.quality_table[r.sample_idx, target])
                records[r.rid] = {
                    "rid": r.rid, "action": target, "cost": cost,
                    "quality": q, "wall_s": wall / len(reqs),
                    "tokens": np.asarray(new_tokens[i]),
                }

        # feedback to the bandit
        rewards = np.array([
            float(utility_reward(records[r.rid]["quality"],
                                 records[r.rid]["cost"], self.c_max,
                                 self.cost_lambda))
            for r in requests], np.float32)
        self.router.update(x_emb, x_feat, domain, decision, rewards)
        out = [dict(records[r.rid], reward=float(rw))
               for r, rw in zip(requests, rewards)]
        if self.log.maxlen is not None:
            self.dropped_log_records += max(
                0, len(self.log) + len(out) - self.log.maxlen)
        self.log.extend(out)
        return out

    def end_slice(self, epochs: int = 5):
        return self.router.end_slice(epochs)
