"""The async engine moves a decided microbatch as columns; these tests
hold it to the per-row engine it replaced, kept below as a reference
(`_RowEngine`, with its per-row batcher `_RowBatcher`).

One seeded scenario runs through both: an uneven 11-arm mix (minority
queues age), an arm taken down and brought back (fallbacks at depth 1
and 2), a fully-down chain, every arm down, a queue-full burst, injected
decide errors, a train between waves, with and without flush windows,
table and utility feedback, with and without decode engines — over a
router blind to the availability mask (the fallback chains remap), one
that honours it, and a real `DevicePolicyRouter`. Every record, counter,
log entry, router call or final router state, and serve batch (arm,
rows, tokens, lengths) must match."""
import functools
from collections import defaultdict, deque

import jax
import numpy as np
import pytest

from repro.armpool.serving import RooflineArmEngine
from repro.core.reward import utility_reward
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.serving import AsyncRouterEngine, DevicePolicyRouter, Request
from repro.serving.batcher import pad_rows
from repro.sim import DeviceReplayEnv, make_policy
from repro.sim.engine import _tables

K, N = 11, 6000
MIX = 0.6 ** np.arange(K)
MIX = MIX / MIX.sum()


# ------------------------------------------------- per-row reference --
class _RowBatcher:
    """The per-row batcher: one list entry and one clock reading per
    request."""

    def __init__(self, max_batch, pad_to_multiple, flush_timeout, clock,
                 max_starve=4):
        self.max_batch, self.pad = max_batch, pad_to_multiple
        self.flush_timeout, self.clock = flush_timeout, clock
        self.max_starve = max_starve
        self.queues = defaultdict(list)
        self.arrivals = defaultdict(list)
        self.age = defaultdict(int)

    def submit(self, target, req):
        self.queues[target].append(req)
        self.arrivals[target].append(self.clock())

    def pending(self):
        return sum(len(q) for q in self.queues.values())

    def _ready(self, t, now, force):
        if force or self.flush_timeout is None \
                or len(self.queues[t]) >= self.max_batch:
            return True
        return now - self.arrivals[t][0] >= self.flush_timeout

    def next_batch(self, force=False):
        if not self.pending():
            return None
        now = self.clock()
        ready = [t for t in self.queues if self._ready(t, now, force)]
        if not ready:
            return None
        starving = [t for t in ready if self.age[t] >= self.max_starve]
        if starving:
            target = max(starving, key=lambda t: (self.age[t], -t))
        else:
            target = max(ready, key=lambda t: (len(self.queues[t])
                                               + self.age[t], -t))
        q = self.queues[target]
        reqs, self.queues[target] = q[:self.max_batch], q[self.max_batch:]
        self.arrivals[target] = self.arrivals[target][len(reqs):]
        if not self.queues[target]:
            del self.queues[target]
            self.arrivals.pop(target, None)
        self.age.pop(target, None)
        for t in self.queues:
            if t != target:
                self.age[t] += 1
        width = max(len(r.tokens) for r in reqs)
        width = -(-width // self.pad) * self.pad
        toks = np.zeros((len(reqs), width), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.tokens)] = r.tokens
        return target, reqs, toks


class _RowGroup:
    def __init__(self, decision, reqs, decided):
        n = len(reqs)
        self.decision, self.reqs, self.decided = decision, reqs, decided
        self.served = np.full(n, -1, np.int32)
        self.reward = np.zeros(n, np.float32)
        self.quality = np.zeros(n, np.float32)
        self.cost = np.zeros(n, np.float32)
        self.depth = np.zeros(n, np.int32)
        self.remaining = n


class _RowEngine:
    """The engine's per-row logic: a request at a time through
    admission, fallback resolve, batcher, write-back and records."""

    def __init__(self, router, *, engines=None, cost_per_token=None,
                 reward_table=None, quality_table=None, cost_table=None,
                 c_max=None, queue_capacity, decide_batch, decide_flush,
                 serve_batch, serve_flush, pad_to_multiple=4, chains,
                 max_new=8, clock, fault_hook, log_capacity):
        self.router, self.engines = router, engines
        self.cost_per_token = (None if cost_per_token is None
                               else np.asarray(cost_per_token, np.float64))
        self.reward_table, self.quality_table = reward_table, quality_table
        self.cost_table, self.c_max = cost_table, c_max
        self.queue_capacity, self.decide_batch = queue_capacity, decide_batch
        self.decide_flush, self.max_new = decide_flush, max_new
        self.clock, self.fault_hook = clock, fault_hook
        self.batcher = _RowBatcher(serve_batch, pad_to_multiple,
                                   serve_flush, clock)
        self.arm_up = np.ones(K, bool)
        self.chains = chains
        self.admit = deque()
        self.groups, self.slot, self.next_gid = {}, {}, 0
        self.counters = defaultdict(int)
        self.log = deque(maxlen=log_capacity)

    def set_arm_health(self, arm, up):
        self.arm_up[arm] = up

    def _rank(self):
        if self.cost_per_token is not None:
            return np.argsort(self.cost_per_token, kind="stable")
        return np.argsort(self.cost_table.mean(axis=0), kind="stable")

    def _safe_arm(self):
        for a in self._rank():
            if self.arm_up[a]:
                return int(a)
        return -1

    def _resolve(self, a):
        if self.arm_up[a]:
            return a, 0
        for d, b in enumerate(self.chains.get(a, ()), start=1):
            if self.arm_up[b]:
                return b, d
        return -1, len(self.chains.get(a, ())) + 1

    def _log(self, rec):
        if len(self.log) == self.log.maxlen:
            self.counters["dropped_log_records"] += 1
        self.log.append(rec)

    def submit(self, requests):
        now = self.clock()
        for r in requests:
            self.counters["submitted"] += 1
            if len(self.admit) >= self.queue_capacity:
                self.counters["shed_queue_full"] += 1
                self._log({"rid": r.rid, "status": "shed_queue_full",
                           "action": -1, "reward": 0.0})
            else:
                self.counters["admitted"] += 1
                self.admit.append((r, now))

    @property
    def in_flight(self):
        return len(self.admit) + self.batcher.pending()

    def _decide_due(self, force):
        n = len(self.admit)
        if n == 0:
            return False
        if force or n >= self.decide_batch or self.decide_flush is None:
            return True
        return self.clock() - self.admit[0][1] >= self.decide_flush

    def pump(self, force=False):
        out = []
        while self._decide_due(force):
            k = min(self.decide_batch, len(self.admit))
            reqs = [self.admit.popleft()[0] for _ in range(k)]
            self._decide_and_dispatch(reqs, out)
        while True:
            nb = self.batcher.next_batch(force=force)
            if nb is None:
                break
            self._serve_batch(*nb, out)
        return out

    def drain(self):
        out = []
        while self.in_flight:
            out.extend(self.pump(force=True))
        return out

    def end_slice(self):
        self.router.end_slice()

    def _decide_and_dispatch(self, reqs, out):
        n = len(reqs)
        if not self.arm_up.any():
            for r in reqs:
                self.counters["shed_no_arm"] += 1
                rec = {"rid": r.rid, "status": "shed_no_arm", "action": -1,
                       "reward": 0.0}
                self._log(rec)
                out.append(rec)
            return
        gid = self.next_gid
        self.next_gid += 1
        call_idx = self.counters["decide_calls"]
        self.counters["decide_calls"] += 1
        try:
            self.fault_hook(call_idx)
            decision = self.router.decide(
                sample_idx=np.array([r.sample_idx for r in reqs], np.int64),
                avail=self.arm_up.astype(np.float32))
            decided = np.asarray(decision["action"], np.int32).copy()
        except Exception:
            self.counters["decide_errors"] += 1
            decision = None
            decided = np.full(n, self._safe_arm(), np.int32)
        g = _RowGroup(decision, reqs, decided)
        self.groups[gid] = g
        for i, r in enumerate(reqs):
            served, depth = self._resolve(int(decided[i]))
            if served < 0:
                self.counters["shed_no_arm"] += 1
                g.remaining -= 1
                rec = {"rid": r.rid, "status": "shed_no_arm", "action": -1,
                       "reward": 0.0}
                self._log(rec)
                out.append(rec)
                continue
            if depth > 0:
                self.counters["fallbacks"] += 1
            g.depth[i] = depth
            self.slot[r.rid] = (gid, i)
            self.batcher.submit(served, r)
        if g.remaining == 0:
            self._finalize(gid, out)

    def _serve_batch(self, target, reqs, toks, out):
        n_new = self.max_new
        if self.engines is not None:
            new_tokens, _ = self.engines[target].generate(
                toks, max_new=self.max_new,
                lengths=[len(r.tokens) for r in reqs])
            n_new = new_tokens.shape[1]
        ids = np.array([r.sample_idx for r in reqs], np.int64)
        if self.reward_table is not None:
            rw = np.asarray(self.reward_table[ids, target], np.float32)
            q = np.asarray(self.quality_table[ids, target], np.float32)
            c = np.asarray(self.cost_table[ids, target], np.float32)
        else:
            n_tok = np.array([len(r.tokens) + n_new for r in reqs])
            c = (self.cost_per_token[target] * n_tok).astype(np.float32)
            q = np.full(len(reqs), 0.5, np.float32)
            sel = ids >= 0
            q[sel] = self.quality_table[ids[sel], target]
            rw = np.asarray(utility_reward(q, c, self.c_max, 1.0),
                            np.float32)
        touched = set()
        for i, r in enumerate(reqs):
            gid, pos = self.slot.pop(r.rid)
            g = self.groups[gid]
            g.served[pos] = target
            g.reward[pos], g.quality[pos], g.cost[pos] = rw[i], q[i], c[i]
            g.remaining -= 1
            touched.add(gid)
        for gid in sorted(touched):
            if self.groups[gid].remaining == 0:
                self._finalize(gid, out)

    def _finalize(self, gid, out):
        g = self.groups.pop(gid)
        ok = g.served >= 0
        if g.decision is not None and ok.any():
            served = np.where(ok, g.served, g.decided)
            learned = self.router.update_wave(g.decision, served, g.reward,
                                              learn_mask=ok)
            self.counters["updates"] += 1
            self.counters["learned"] += int(learned)
            self.counters["skipped_learn"] += int(ok.sum()) - learned
        elif g.decision is None:
            self.counters["skipped_learn"] += int(ok.sum())
        for i, r in enumerate(g.reqs):
            if not ok[i]:
                continue
            self.counters["completed"] += 1
            rec = {"rid": r.rid, "status": "ok",
                   "action": int(g.served[i]),
                   "decided": int(g.decided[i]),
                   "fallback_depth": int(g.depth[i]),
                   "reward": float(g.reward[i]),
                   "quality": float(g.quality[i]),
                   "cost": float(g.cost[i])}
            self._log(rec)
            out.append(rec)


# ---------------------------------------------------------- fakes --
class _BlindRouter:
    """Blind to the availability mask, drawing arms from an uneven mix;
    records every call's arguments."""

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)
        self.calls = []

    def _draw(self, n, avail):
        return self.rng.choice(K, size=n, p=MIX)

    def decide(self, *, sample_idx, avail):
        self.calls.append(("decide", sample_idx.copy(), avail.copy()))
        a = self._draw(sample_idx.size, avail)
        return {"action": a.astype(np.int32), "n": len(self.calls)}

    def update_wave(self, decision, served, rewards, learn_mask):
        self.calls.append(("update", decision["n"], decision["action"],
                           served.copy(), rewards.copy(),
                           learn_mask.copy()))
        return int(np.count_nonzero(learn_mask
                                    & (served == decision["action"])))

    def end_slice(self):
        self.calls.append(("end_slice",))


class _AwareRouter(_BlindRouter):
    """Honours the availability mask, as `DevicePolicyRouter`'s
    policies do: draws from the mix over the healthy arms only."""

    def _draw(self, n, avail):
        p = MIX * (avail > 0)
        return self.rng.choice(K, size=n, p=p / p.sum())


@functools.lru_cache(maxsize=None)
def _device_policy():
    """A neuralucb policy at test widths and its N-row resident tables,
    built once so that every router shares the compiles."""
    rng = np.random.default_rng(5)
    plen = rng.integers(4, 14, size=N)
    data = {
        "domain": rng.integers(0, 5, size=N).astype(np.int32),
        "topic": rng.normal(size=(N, 32)).astype(np.float32),
        "difficulty": np.zeros(N, np.float32),
        "prompt_tokens": plen.astype(np.float32),
        "quality": rng.uniform(0.1, 1.0, (N, K)).astype(np.float32),
        "cost": (np.arange(1, K + 1, dtype=np.float32)[None]
                 * plen[:, None] * 1e-4).astype(np.float32),
        "x_feat": rng.normal(size=(N, 4)).astype(np.float32),
        "model_names": np.array([f"m{a}" for a in range(K)]),
    }
    env = DeviceReplayEnv.from_host(
        RouterBenchSim(seed=0, n_slices=4, cost_lambda=1.0, data=data))
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=K,
                           num_domains=5, d_text=16, d_hidden=16, d_last=8)
    pol, hyp = make_policy("neuralucb", env, cfg)
    return pol, hyp, _tables(env)


def _device_router(seed):
    """A `DevicePolicyRouter` at test widths over the N-row table."""
    pol, hyp, tables = _device_policy()
    return DevicePolicyRouter(pol, hyp, tables, seed=seed, slice_width=48,
                              capacity_slices=4, batch_size=16,
                              train_chunks=1)


# "v2" is the mask-blind router (the id its test cases have always had)
ROUTERS = {"v2": _BlindRouter, "aware": _AwareRouter,
           "device": _device_router}


class _Tap:
    """A table whose reads are recorded: (rows, arm) per serve batch."""

    def __init__(self, a, reads):
        self.a, self.reads = a, reads

    def __getitem__(self, key):
        self.reads.append((np.array(key[0]), int(key[1])))
        return self.a[key]


class _StubArm:
    """A decode engine that reads its prompts; records each call."""

    def __init__(self, arm, calls):
        self.arm, self.calls = arm, calls

    def generate(self, tokens, max_new=8, lengths=None):
        self.calls.append((self.arm, np.array(tokens), list(lengths)))
        return np.ones((len(tokens), max_new), np.int32), max_new


def _chains():
    """Every arm falls back over the others, most-used first, except arm
    2, whose chain is arm 3 alone."""
    chains = {a: [b for b in range(K) if b != a] for a in range(K)}
    chains[2] = [3]
    return chains


def _world(router, feedback, flush, now, seed=0):
    """One engine's tables, engines and settings; the reads and calls
    it makes are recorded in ``rec``."""
    rng = np.random.default_rng(42)
    quality = rng.uniform(0.1, 1.0, (N, K)).astype(np.float32)
    cost = (np.arange(1, K + 1, dtype=np.float32)[None]
            * rng.uniform(0.9, 1.1, (N, K))).astype(np.float32)
    reward = (quality - 0.05 * cost).astype(np.float32)
    rec = {"reads": [], "arms": []}
    kw = dict(queue_capacity=160, decide_batch=48, serve_batch=16,
              decide_flush=0.5 if flush else None,
              serve_flush=0.8 if flush else None, max_new=6,
              clock=lambda: now[0], log_capacity=700,
              fault_hook=lambda i: _fault(i))
    if feedback == "utility":
        kw.update(cost_per_token=np.linspace(1e-4, 3e-3, K)[::-1].copy(),
                  quality_table=_Tap(quality, rec["reads"]), c_max=0.05)
    else:
        kw.update(reward_table=_Tap(reward, rec["reads"]),
                  quality_table=quality, cost_table=cost)
    if feedback != "table":
        kw["engines"] = [_StubArm(a, rec["arms"]) for a in range(K)]
    return ROUTERS[router](seed), kw, rec


def _fault(call_idx):
    if call_idx in (5, 17):
        raise RuntimeError(f"injected at decide {call_idx}")


def _requests(rng, start, n):
    out = []
    for i in range(start, start + n):
        toks = rng.integers(1, 1000, size=int(rng.integers(1, 14)))
        out.append(Request(tokens=toks.astype(np.int32), sample_idx=i))
    return out


# the scenario: step -> (arm, up) health changes before its submit
HEALTH = {8: [(0, False)],                   # depth-1 fallbacks
          12: [(1, False)],                  # 0's first fallback: depth 2
          18: [(0, True), (1, True)],
          24: [(2, False), (3, False)],      # a fully-down chain
          30: [(2, True), (3, True)] + [(a, False) for a in range(K)],
          31: [(a, True) for a in range(K)]}
BURST = 22                                   # a queue-full shed
ALL_DOWN = 30                # drained: the whole backlog sheds no-arm


def _typed(recs):
    return [[(k, type(v).__name__, v) for k, v in r.items()] for r in recs]


def _same(a, b, what):
    if isinstance(a, np.ndarray):
        assert isinstance(b, np.ndarray), what
        assert a.dtype == b.dtype and a.shape == b.shape, what
        np.testing.assert_array_equal(a, b, err_msg=what)
    elif isinstance(a, (tuple, list)):
        assert type(a) is type(b) and len(a) == len(b), what
        for i, (x, y) in enumerate(zip(a, b)):
            _same(x, y, f"{what}[{i}]")
    elif isinstance(a, dict):
        assert a.keys() == b.keys(), what
        for k in a:
            _same(a[k], b[k], f"{what}.{k}")
    else:
        assert type(a) is type(b) and a == b, what


def _same_router(a, b):
    """The two engines' routers saw the same calls: a fake's call log,
    or a `DevicePolicyRouter`'s whole state, bit for bit."""
    if not isinstance(a, DevicePolicyRouter):
        _same(a.calls, b.calls, "router calls")
        return
    assert (a.wave, a.slices) == (b.wave, b.slices)
    _same(a._counts, b._counts, "ring counts")
    for what, x, y in (("key", a._key, b._key),
                       ("env_idx", a._env_idx, b._env_idx)):
        _same(np.asarray(x), np.asarray(y), what)
    xs, ys = (jax.tree_util.tree_leaves(r.state) for r in (a, b))
    assert len(xs) == len(ys)
    for i, (x, y) in enumerate(zip(xs, ys)):
        _same(np.asarray(x), np.asarray(y), f"state leaf {i}")


@pytest.mark.parametrize("feedback", ["table", "engines", "utility"])
@pytest.mark.parametrize("flush", [False, True], ids=["no_flush", "flush"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_columnar_engine_matches_per_row_reference(router, feedback,
                                                   flush):
    now = [0.0]
    r_new, kw, rec_new = _world(router, feedback, flush, now)
    r_ref, kw_ref, rec_ref = _world(router, feedback, flush, now)
    new = AsyncRouterEngine(r_new, K, fallback_chains=_chains(), **kw)
    ref = _RowEngine(r_ref, chains=_chains(), **kw_ref)
    # a router that honours the mask never decides onto a down arm, so
    # only the blind one walks the fallback chains
    blind = router == "v2"

    rng = np.random.default_rng(7)
    start, seen = 0, []
    for step in range(48):
        for arm, up in HEALTH.get(step, ()):
            new.set_arm_health(arm, up)
            ref.set_arm_health(arm, up)
        n = 400 if step == BURST else int(rng.integers(0, 90))
        reqs = _requests(rng, start, n)
        start += n
        now[0] += float(rng.uniform(0.0, 0.6))
        admitted, shed = new.submit(reqs)
        ref.submit(reqs)
        assert admitted + shed == n
        if step % 7 == 6 or step == ALL_DOWN:
            got, want = new.drain(), ref.drain()
        else:
            got, want = new.pump(), ref.pump()
        assert _typed(got) == _typed(want), f"step {step} records"
        seen += got
        assert new.in_flight == ref.in_flight, f"step {step} in flight"
        assert {k: v for k, v in new.counters.items()
                if v and k != "padded_batches"} \
            == {k: v for k, v in ref.counters.items() if v}, \
            f"step {step} counters"
        if step % 7 == 6:   # drained: train between waves
            new.end_slice()
            ref.end_slice()
        if step == 29:      # before every arm goes down
            assert bool(new.counters["shed_no_arm"]) == blind, \
                "a chain ran out" if not blind else "no chain ran out"
    _same(new.drain(), ref.drain(), "drain")
    assert new.in_flight == 0
    assert new.check_accounting()["lost"] == 0
    assert _typed(new.log) == _typed(ref.log)
    _same_router(r_new, r_ref)
    _same(rec_new, rec_ref, "serve batches")

    # the scenario did reach every branch it names
    c = new.counters
    assert c["shed_queue_full"] and c["shed_no_arm"] and c["learned"]
    assert c["decide_errors"] == 2 and c["dropped_log_records"]
    depths = {x["fallback_depth"] for x in seen if x["status"] == "ok"}
    if blind:
        assert c["fallbacks"] and {1, 2} <= depths
    else:
        assert c["fallbacks"] == 0 and depths == {0}
    assert c["padded_batches"] == (0 if feedback == "table"
                                   else len(rec_new["arms"]))


class _ClockedArm(RooflineArmEngine):
    def __init__(self, calls):
        super().__init__("remote", 0.0, latency_scale=0.0)
        self.calls = calls

    def generate(self, tokens, max_new=8, lengths=None):
        self.calls.append(np.asarray(tokens).shape)
        return super().generate(tokens, max_new, lengths)


@pytest.mark.parametrize("pool", ["none", "clocked", "mixed"])
def test_padded_batches_only_for_engines_that_read_prompts(pool):
    """No engine, or an engine that reads no prompt (``real_decode``
    False), gets no padded matrix and counts nothing; an engine that
    reads prompts gets the batcher's right-padded matrix and each row's
    length, and ``padded_batches`` is its batch count."""
    rng = np.random.default_rng(3)
    reward = rng.uniform(0.1, 0.9, (N, K)).astype(np.float32)
    read, clocked = [], []
    engines = None
    if pool != "none":
        engines = [_ClockedArm(clocked) for _ in range(K)]
    if pool == "mixed":
        engines[0] = _StubArm(0, read)
    eng = AsyncRouterEngine(_BlindRouter(1), K, engines=engines,
                            reward_table=reward, decide_batch=64,
                            serve_batch=16, pad_to_multiple=4)
    reqs = _requests(rng, 0, 500)
    eng.submit(reqs)
    recs = eng.pump() + eng.drain()
    assert sum(r["status"] == "ok" for r in recs) == 500

    assert eng.counters["padded_batches"] == len(read)
    assert all(w == 0 for _, w in clocked)
    if pool == "none":
        assert not read and not clocked
        return
    served = {r["rid"]: r["action"] for r in recs}
    assert sum(b for b, _ in clocked) == sum(a != 0 or pool == "clocked"
                                             for a in served.values())
    if pool == "mixed":
        assert read, "arm 0 served no batch"
        by_prompt = {r.rid: r for r in reqs}
        arm0 = [by_prompt[rid] for rid, a in served.items() if a == 0]
        rows = 0
        for arm, toks, lengths in read:
            batch = arm0[rows:rows + len(toks)]
            rows += len(toks)
            np.testing.assert_array_equal(
                toks, pad_rows([r.tokens for r in batch], 4))
            assert lengths == [len(r.tokens) for r in batch]
            assert toks.shape[1] % 4 == 0
        assert rows == len(arm0)


def test_request_ids_are_fresh_and_increasing():
    """`Request` keeps one id counter: every new request takes the next
    id, whatever else it carries."""
    a = Request(tokens=np.ones(2, np.int32))
    b = Request(tokens=np.ones(3, np.int32), sample_idx=4)
    assert b.rid == a.rid + 1
    assert Request(tokens=a.tokens, rid=-7).rid == -7
