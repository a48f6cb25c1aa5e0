"""High-throughput async router serving engine (DESIGN.md §12).

Continuous-batching front end for any bandit router:

    submit -> [bounded admission queue] -> microbatched DECIDE (one
    batched jit call per microbatch) -> per-arm fallback chain ->
    [per-arm queues] -> serve -> reward feedback -> router UPDATE

The loop is cooperative and deterministic: ``pump()`` advances every
stage as far as it can (decide everything due, serve every ready arm
batch, finalize every completed microbatch), and ``drain()`` force-
flushes until nothing is in flight. "Async" here is the continuous-
batching sense — decides and serves interleave across microbatches, and
nothing blocks on a full wave — while keeping single-threaded replayable
semantics (an injectable ``clock`` makes every timeout testable).

A decided microbatch is held as columns (request ids, table rows,
decided and served arm, reward, quality, cost, chain depth), not walked
row by row: the fallback chains resolve through a (K,) table of
(served arm, depth) rebuilt when an arm's health changes, one stable
sort groups the rows by served arm, and each arm's queue
(:class:`~repro.serving.batcher.ArmQueues`, the batcher's selection
rule) holds (microbatch, row positions) chunks. A serve batch writes its
feedback back with one store per microbatch it touches; a completed
microbatch's records are built from its columns. Only an arm engine
that reads prompts (``real_decode`` not False) gets a padded token
matrix (counted in ``padded_batches``).

Graceful degradation (the CostSavingRouter pattern, SNIPPETS.md §1):

* The admission queue is BOUNDED — a burst beyond ``queue_capacity`` is
  shed at submit with a counted drop, never an unbounded backlog.
* Every arm has a fallback chain (default: every other arm, cheapest
  first). A request decided onto a down arm walks its chain; only a
  fully-down chain sheds (counted). Routers that accept the live
  availability mask (``serving_v2``) never pick a down arm to begin
  with.
* A decide-path exception is caught and counted; the microbatch degrades
  to the cheapest healthy arm and is served WITHOUT a router update (the
  router never learns from decisions it did not make).
* Fallback-remapped rows reach the router with the arm actually served:
  routers exposing ``action_features`` get exact relearning (features
  recomputed for the served arm); ``serving_v2`` routers exclude the
  rows conservatively and count them.

Accounting invariant (asserted by tests/test_serving_faults.py): every
submitted request is exactly one of completed / shed-at-admission /
shed-no-arm / still in flight — nothing is silently dropped.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.common.tracing import span
from repro.core.reward import utility_reward
from repro.serving.batcher import ArmQueues, Request, pad_rows
from repro.serving.snapshot import load_snapshot, save_snapshot, \
    unflatten_state

COUNTERS = ("submitted", "admitted", "completed", "shed_queue_full",
            "shed_no_arm", "fallbacks", "decide_calls", "decide_errors",
            "updates", "learned", "skipped_learn", "dropped_log_records",
            "padded_batches")


class _Group:
    """One decided microbatch awaiting completion, held as columns: row
    ``i`` is ``reqs[i]``; an arm queue holds its rows' positions."""

    __slots__ = ("decision", "reqs", "rid", "ids", "decided", "served",
                 "reward", "quality", "cost", "depth", "remaining",
                 "x_emb", "x_feat", "domain")

    def __init__(self, decision, reqs, rid, ids, decided, depth,
                 x_emb=None, x_feat=None, domain=None):
        n = len(reqs)
        self.decision = decision      # router decision dict, or None
        self.reqs = reqs
        self.rid = rid                # (n,) request ids
        self.ids = ids                # (n,) replay-table rows
        self.decided = decided        # (n,) pre-fallback actions
        self.served = np.full(n, -1, np.int32)
        self.reward = np.zeros(n, np.float32)
        self.quality = np.zeros(n, np.float32)
        self.cost = np.zeros(n, np.float32)
        self.depth = depth            # (n,) fallback-chain depth
        self.remaining = n
        self.x_emb, self.x_feat, self.domain = x_emb, x_feat, domain


class AsyncRouterEngine:
    """See module docstring. ``router`` is either the host
    `NeuralUCBRouter` interface (``decide(x_emb, x_feat, domain)`` /
    ``update(x_emb, x_feat, domain, decision, rewards)``) or a
    ``serving_v2`` router (`DevicePolicyRouter`: id-addressed decide with
    live availability, ``update_wave``). Feedback is table-replay mode
    when ``reward_table`` is given (requests carry ``sample_idx``),
    otherwise the pool's Eq.-1 utility mode from per-token prices."""

    def __init__(self, router, num_arms: int, *,
                 engines: Optional[Sequence] = None,
                 cost_per_token: Optional[Sequence[float]] = None,
                 reward_table: Optional[np.ndarray] = None,
                 quality_table: Optional[np.ndarray] = None,
                 cost_table: Optional[np.ndarray] = None,
                 c_max: Optional[float] = None, cost_lambda: float = 1.0,
                 queue_capacity: int = 2048, decide_batch: int = 256,
                 decide_flush: Optional[float] = None,
                 serve_batch: int = 64,
                 serve_flush: Optional[float] = None,
                 pad_to_multiple: int = 4,
                 fallback_chains: Optional[Dict[int, Sequence[int]]] = None,
                 max_new: int = 8,
                 clock: Callable[[], float] = time.monotonic,
                 fault_hook: Optional[Callable[[int], None]] = None,
                 log_capacity: Optional[int] = 10_000):
        if queue_capacity < decide_batch:
            raise ValueError("queue_capacity must be >= decide_batch "
                             f"({queue_capacity} < {decide_batch})")
        self.router = router
        self.K = int(num_arms)
        self.engines = list(engines) if engines is not None else None
        self.cost_per_token = (None if cost_per_token is None
                               else np.asarray(cost_per_token, np.float64))
        self.reward_table = reward_table
        self.quality_table = quality_table
        self.cost_table = cost_table
        if reward_table is None:
            if self.cost_per_token is None:
                raise ValueError("utility feedback needs cost_per_token "
                                 "(or pass reward_table for replay mode)")
            if c_max is None:
                max_seq = max((getattr(e, "max_seq", 4096)
                               for e in self.engines or []), default=4096)
                c_max = float(self.cost_per_token.max() * max_seq)
        self.c_max = c_max
        self.cost_lambda = cost_lambda
        self.queue_capacity = int(queue_capacity)
        self.decide_batch = int(decide_batch)
        self.decide_flush = decide_flush
        self.max_new = int(max_new)
        self.clock = clock
        self.fault_hook = fault_hook
        self.pad_to_multiple = int(pad_to_multiple)
        # per-arm queues of (gid, row positions) chunks
        self._queues = ArmQueues(serve_batch, flush_timeout=serve_flush,
                                 clock=clock)
        self.arm_up = np.ones(self.K, bool)
        self.chains = self._default_chains() if fallback_chains is None \
            else {int(a): [int(x) for x in c]
                  for a, c in fallback_chains.items()}
        self._resolve_table()
        self._admit: List[Request] = []       # the admission queue
        self._arrive: List[float] = []        # its rows' arrival clocks
        self._groups: Dict[int, _Group] = {}
        self._next_gid = 0
        self.counters = {k: 0 for k in COUNTERS}
        self.decide_wall_s: List[float] = []
        # the latest swallowed decide exception, so a caller that treats
        # any decide error as fatal can say what it was
        self.last_decide_error: Optional[str] = None
        self.log = deque(maxlen=log_capacity)
        self._serving_v2 = bool(getattr(router, "serving_v2", False))

    # ------------------------------------------------------------ health --
    def _arm_cost_rank(self) -> np.ndarray:
        if self.cost_per_token is not None:
            return np.argsort(self.cost_per_token, kind="stable")
        if self.cost_table is not None:
            return np.argsort(np.asarray(self.cost_table).mean(axis=0),
                              kind="stable")
        return np.arange(self.K)

    def _default_chains(self) -> Dict[int, List[int]]:
        order = [int(a) for a in self._arm_cost_rank()]
        return {a: [b for b in order if b != a] for a in range(self.K)}

    def set_arm_health(self, arm: int, up: bool) -> None:
        if self.arm_up[int(arm)] != bool(up):
            self.arm_up[int(arm)] = bool(up)
            self._resolve_table()

    def _safe_arm(self) -> int:
        for a in self._arm_cost_rank():
            if self.arm_up[a]:
                return int(a)
        return -1

    def _resolve_table(self) -> None:
        """The fallback chains under the current health mask, as (K,)
        tables: the arm that serves a row decided onto arm ``a``
        (``_served_of[a]``; -1: the whole chain is down) and its depth
        in the chain (``_depth_of[a]``)."""
        self._served_of = np.full(self.K, -1, np.int32)
        self._depth_of = np.zeros(self.K, np.int32)
        for a in range(self.K):
            chain = [a, *self.chains.get(a, ())]
            d = next((d for d, b in enumerate(chain) if self.arm_up[b]),
                     None)
            if d is not None:
                self._served_of[a], self._depth_of[a] = chain[d], d

    # -------------------------------------------------------- admission --
    def submit(self, requests: Sequence[Request]):
        """Admit into the bounded queue; excess (the newest arrivals) is
        shed with a counted drop and a log record each. Returns
        (n_admitted, n_shed)."""
        n = len(requests)
        with span("engine.submit", rows=n):
            now = self.clock()
            keep = min(n, max(0, self.queue_capacity - len(self._admit)))
            self._admit.extend(requests[:keep])
            self._arrive.extend([now] * keep)
            c = self.counters
            c["submitted"] += n
            c["admitted"] += keep
            if keep < n:
                c["shed_queue_full"] += n - keep
                self._log_many([{"rid": r.rid, "status": "shed_queue_full",
                                 "action": -1, "reward": 0.0}
                                for r in requests[keep:]])
        return keep, n - keep

    @property
    def in_flight(self) -> int:
        return len(self._admit) + self._queues.pending()

    # ------------------------------------------------------------- pump --
    def pump(self, force: bool = False) -> List[Dict]:
        """Advance decide -> dispatch -> serve as far as currently due;
        returns the records completed by this call."""
        out: List[Dict] = []
        with span("engine.pump"):
            while self._decide_due(force):
                k = min(self.decide_batch, len(self._admit))
                reqs, arrived = self._admit[:k], self._arrive[:k]
                del self._admit[:k], self._arrive[:k]
                # the rows' summed admission wait, on the engine's clock
                wait_s = k * self.clock() - sum(arrived)
                self._decide_and_dispatch(reqs, out, wait_s)
            while True:
                nb = self._queues.pop(force)
                if nb is None:
                    break
                self._serve_batch(*nb, out)
        return out

    def drain(self, max_rounds: int = 10_000) -> List[Dict]:
        """Force-flush until nothing is in flight. Bounded: a round that
        makes no progress raises with the counter state instead of
        spinning (the no-deadlock guarantee is 'shed or serve, loudly')."""
        out: List[Dict] = []
        for _ in range(max_rounds):
            if self.in_flight == 0:
                return out
            before = self.in_flight
            out.extend(self.pump(force=True))
            if self.in_flight >= before:
                break
        raise RuntimeError(f"drain stalled with {self.in_flight} in flight; "
                           f"counters={self.counters}")

    def end_slice(self, epochs: int = 1):
        with span("engine.end_slice"):
            return self.router.end_slice(epochs)

    # ----------------------------------------------------------- decide --
    def _decide_due(self, force: bool) -> bool:
        n = len(self._admit)
        if n == 0:
            return False
        if force or n >= self.decide_batch or self.decide_flush is None:
            return True
        return self.clock() - self._arrive[0] >= self.decide_flush

    def _decide_and_dispatch(self, reqs: List[Request], out: List[Dict],
                             wait_s: float):
        n = len(reqs)
        if not self.arm_up.any():
            self.counters["shed_no_arm"] += n
            self._shed_no_arm([r.rid for r in reqs], out)
            return
        gid = self._next_gid
        self._next_gid += 1
        with span("engine.decide", gid=gid, rows=n, wait_us=wait_s * 1e6):
            rid = np.array([r.rid for r in reqs], np.int64)
            ids = np.array([r.sample_idx for r in reqs], np.int64)
            x_emb = x_feat = domain = None
            if not self._serving_v2:
                x_emb = np.stack([r.x_emb for r in reqs])
                x_feat = np.stack([r.x_feat for r in reqs])
                domain = np.array([r.domain for r in reqs], np.int32)
            call_idx = self.counters["decide_calls"]
            self.counters["decide_calls"] += 1
            decision = None
            t0 = time.perf_counter()
            try:
                if self.fault_hook is not None:
                    self.fault_hook(call_idx)
                if self._serving_v2:
                    decision = self.router.decide(
                        sample_idx=ids,
                        avail=self.arm_up.astype(np.float32))
                else:
                    decision = self.router.decide(x_emb, x_feat, domain)
                decided = np.asarray(decision["action"], np.int32).copy()
                self.decide_wall_s.append(time.perf_counter() - t0)
            except Exception as e:
                # degrade, don't die: cheapest healthy arm, no router update
                self.counters["decide_errors"] += 1
                self.last_decide_error = f"{type(e).__name__}: {e}"[:2000]
                decision = None
                decided = np.full(n, self._safe_arm(), np.int32)

        with span("engine.dispatch", gid=gid, rows=n):
            served = self._served_of[decided]
            depth = self._depth_of[decided]
            if decided.min() < 0:
                served[decided < 0] = -1     # no such arm: shed
            g = _Group(decision, reqs, rid, ids, decided, depth, x_emb,
                       x_feat, domain)
            self._groups[gid] = g
            # one stable sort groups the rows by served arm, the shed
            # rows (served -1) first, each arm's rows in microbatch order
            order = np.argsort(served, kind="stable")
            bounds = np.cumsum(np.bincount(served + 1,
                                           minlength=self.K + 1)).tolist()
            n_shed = bounds[0]
            self.counters["fallbacks"] += int(
                np.count_nonzero(depth[order[n_shed:]]))
            if n_shed:
                self.counters["shed_no_arm"] += n_shed
                g.remaining -= n_shed
                self._shed_no_arm(rid[order[:n_shed]].tolist(), out)
            now = self.clock()
            for arm in range(self.K):
                lo, hi = bounds[arm], bounds[arm + 1]
                if hi > lo:
                    self._queues.push(arm, order[lo:hi], now, gid)
        if g.remaining == 0:
            self._finalize(gid, out)

    # ------------------------------------------------------------ serve --
    def _serve_batch(self, target: int, parts: List[tuple],
                     out: List[Dict]):
        """Serve one arm batch: ``parts`` is its (gid, row positions)
        slices in arrival order."""
        parts = [(gid, self._groups[gid], pos) for gid, pos in parts]
        n = sum(len(pos) for _, _, pos in parts)
        with span("engine.serve", arm=target, rows=n):
            ids = np.concatenate([g.ids[pos] for _, g, pos in parts])
            n_new = self.max_new
            engine = None if self.engines is None else self.engines[target]
            lengths = None
            if engine is not None or self.reward_table is None:
                reqs = [g.reqs[i] for _, g, pos in parts
                        for i in pos.tolist()]
                lengths = [len(r.tokens) for r in reqs]
            if engine is not None:
                # the matrix only for an engine that reads the prompts
                if getattr(engine, "real_decode", True):
                    toks = pad_rows([r.tokens for r in reqs],
                                    self.pad_to_multiple)
                    self.counters["padded_batches"] += 1
                else:
                    toks = np.zeros((n, 0), np.int32)
                new_tokens, _ = engine.generate(
                    toks, max_new=self.max_new, lengths=lengths)
                n_new = new_tokens.shape[1]
            if self.reward_table is not None:
                rw = np.asarray(self.reward_table[ids, target], np.float32)
                q = rw if self.quality_table is None else \
                    np.asarray(self.quality_table[ids, target], np.float32)
                c = (np.zeros(n, np.float32)
                     if self.cost_table is None else
                     np.asarray(self.cost_table[ids, target], np.float32))
            else:
                n_tok = np.asarray(lengths) + n_new
                c = (self.cost_per_token[target] * n_tok).astype(np.float32)
                q = np.full(n, 0.5, np.float32)
                if self.quality_table is not None:
                    sel = ids >= 0
                    q[sel] = self.quality_table[ids[sel], target]
                rw = np.asarray(utility_reward(q, c, self.c_max,
                                               self.cost_lambda), np.float32)
            lo = 0
            for _, g, pos in parts:
                hi = lo + len(pos)
                g.served[pos] = target
                g.reward[pos] = rw[lo:hi]
                g.quality[pos] = q[lo:hi]
                g.cost[pos] = c[lo:hi]
                g.remaining -= hi - lo
                lo = hi
            for gid in sorted({gid for gid, _, _ in parts}):
                if self._groups[gid].remaining == 0:
                    self._finalize(gid, out)

    # --------------------------------------------------------- feedback --
    def _finalize(self, gid: int, out: List[Dict]):
        g = self._groups.pop(gid)
        ok = g.served >= 0
        n_ok = int(np.count_nonzero(ok))
        with span("engine.finalize", gid=gid, rows=n_ok):
            if g.decision is not None and n_ok:
                if self._serving_v2:
                    served = np.where(ok, g.served, g.decided)
                    learned = self.router.update_wave(
                        g.decision, served, g.reward, learn_mask=ok)
                    self.counters["updates"] += 1
                    self.counters["learned"] += int(learned)
                    self.counters["skipped_learn"] += n_ok - int(learned)
                else:
                    self._update_legacy(g, ok)
            elif g.decision is None:
                self.counters["skipped_learn"] += n_ok
            self.counters["completed"] += n_ok   # shed rows: at dispatch
            recs = [{"rid": r, "status": "ok", "action": a, "decided": d,
                     "fallback_depth": f, "reward": w, "quality": q,
                     "cost": c}
                    for r, a, d, f, w, q, c in zip(
                        g.rid[ok].tolist(), g.served[ok].tolist(),
                        g.decided[ok].tolist(), g.depth[ok].tolist(),
                        g.reward[ok].tolist(), g.quality[ok].tolist(),
                        g.cost[ok].tolist())]
            self._log_many(recs)
            out.extend(recs)

    def _update_legacy(self, g: _Group, ok: np.ndarray):
        """Host-router feedback: slice the decision to completed rows;
        remapped rows relearn EXACTLY when the router can recompute
        features for the served arm, otherwise they are excluded."""
        rows = np.flatnonzero(ok)
        served = g.served[rows]
        changed = served != g.decided[rows]
        dec = {k: np.asarray(v)[rows].copy() for k, v in g.decision.items()}
        dec["action"] = served.astype(np.int32)
        if changed.any():
            if hasattr(self.router, "action_features"):
                sub = rows[changed]
                dec["g"][changed] = self.router.action_features(
                    g.x_emb[sub], g.x_feat[sub], g.domain[sub],
                    served[changed])
            else:
                keep = ~changed
                self.counters["skipped_learn"] += int(changed.sum())
                rows, served = rows[keep], served[keep]
                dec = {k: v[keep] for k, v in dec.items()}
                if rows.size == 0:
                    return
        self.router.update(g.x_emb[rows], g.x_feat[rows], g.domain[rows],
                           dec, g.reward[rows])
        self.counters["updates"] += 1
        self.counters["learned"] += int(rows.size)

    # ------------------------------------------------------- accounting --
    def _log_many(self, recs: List[Dict]):
        if self.log.maxlen is not None:
            self.counters["dropped_log_records"] += max(
                0, len(self.log) + len(recs) - self.log.maxlen)
        self.log.extend(recs)

    def _shed_no_arm(self, rids: List[int], out: List[Dict]):
        recs = [{"rid": r, "status": "shed_no_arm", "action": -1,
                 "reward": 0.0} for r in rids]
        self._log_many(recs)
        out.extend(recs)

    def check_accounting(self) -> Dict[str, int]:
        """The no-silent-drop invariant; raises if any request is
        unaccounted for."""
        c = self.counters
        lost = (c["submitted"] - c["completed"] - c["shed_queue_full"]
                - c["shed_no_arm"] - self.in_flight)
        if lost != 0:
            raise AssertionError(f"{lost} requests unaccounted for: {c}")
        return {"lost": 0, **c}

    # --------------------------------------------------------- snapshot --
    def snapshot(self, path) -> None:
        """Persist router state + engine counters (drained engines only —
        a checkpoint between waves, the production pattern)."""
        if self.in_flight:
            raise RuntimeError(
                f"snapshot with {self.in_flight} requests in flight; "
                "drain() first")
        d = self.router.state_dict()
        save_snapshot(path, d["arrays"],
                      {"router": d["meta"],
                       "counters": {k: int(v) for k, v in
                                    self.counters.items()}})

    def restore(self, path) -> None:
        flat, meta = load_snapshot(path)
        like = self.router.state_dict()["arrays"]
        self.router.load_state_dict({"arrays": unflatten_state(flat, like),
                                     "meta": meta["router"]})
        self.counters.update({k: int(v) for k, v in
                              meta["counters"].items()})
