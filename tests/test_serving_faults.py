"""Fault-injection suite for the async serving engine (ISSUE satellite):
arm outages mid-stream, decide-path exceptions, and queue saturation.
Every fault must be absorbed — fallback chains fire, bounded queues shed
with counted drops (never deadlock), the router never learns from
decisions it did not make, and the accounting invariant (submitted ==
completed + shed + in-flight) holds through every storm."""
import types

import numpy as np
import pytest

from repro.serving import (
    AsyncRouterEngine,
    Request,
    ScriptedFaults,
    outages_from_scenario,
    run_storm,
)
from serving_fakes import BlindFakeRouter, FakeRouter

TOK = np.arange(1, 5, dtype=np.int32)
K, N = 4, 200


def _tables(seed=0):
    rng = np.random.default_rng(seed)
    reward = rng.uniform(0.1, 0.9, (N, K)).astype(np.float32)
    quality = rng.uniform(0.2, 1.0, (N, K)).astype(np.float32)
    # arm k costs ~k+1: the cheapest-first fallback order is 0,1,2,3
    cost = (np.arange(1, K + 1, dtype=np.float32)[None]
            * rng.uniform(0.95, 1.05, (N, K))).astype(np.float32)
    return reward, quality, cost


def _engine(router, **kw):
    reward, quality, cost = _tables()
    kw.setdefault("decide_batch", 32)
    kw.setdefault("queue_capacity", 256)
    return AsyncRouterEngine(router, K, reward_table=reward,
                             quality_table=quality, cost_table=cost, **kw)


def _reqs(n, start=0):
    return [Request(tokens=TOK, sample_idx=(start + i) % N)
            for i in range(n)]


# ------------------------------------------------------------- outages --
def test_fallback_chain_fires_on_outage():
    """A mask-blind router keeps deciding onto a down arm; the engine
    walks the arm's fallback chain, serves, counts the remap, and
    EXCLUDES the remapped rows from learning."""
    r = BlindFakeRouter(K, prefer=0)
    eng = _engine(r, fallback_chains={0: [2, 1, 3], 1: [0], 2: [0],
                                      3: [0]})
    eng.set_arm_health(0, False)
    eng.submit(_reqs(20))
    recs = eng.pump() + eng.drain()
    ok = [x for x in recs if x["status"] == "ok"]
    assert len(ok) == 20
    assert all(x["action"] == 2 and x["decided"] == 0
               and x["fallback_depth"] == 1 for x in ok)
    assert eng.counters["fallbacks"] == 20
    # remapped rows never reach the router's learner
    assert eng.counters["learned"] == 0
    assert eng.counters["skipped_learn"] == 20

    eng.set_arm_health(2, False)            # cascading: next link serves
    eng.submit(_reqs(20))
    recs = eng.pump() + eng.drain()
    assert all(x["action"] == 1 and x["fallback_depth"] == 2
               for x in recs if x["status"] == "ok")

    eng.set_arm_health(0, True)             # recovery: chain goes quiet
    before = eng.counters["fallbacks"]
    eng.submit(_reqs(20))
    recs = eng.pump() + eng.drain()
    assert all(x["action"] == 0 and x["fallback_depth"] == 0
               for x in recs if x["status"] == "ok")
    assert eng.counters["fallbacks"] == before
    assert eng.check_accounting()["lost"] == 0


def test_availability_aware_router_never_needs_fallback():
    """The router gets the live mask in decide — an aware one routes
    around the outage itself, so the chain never fires and every row
    learns."""
    eng = _engine(FakeRouter(K, prefer=0))
    eng.set_arm_health(0, False)
    eng.submit(_reqs(40))
    recs = eng.pump() + eng.drain()
    assert all(x["action"] == 1 and x["fallback_depth"] == 0
               for x in recs if x["status"] == "ok")
    assert eng.counters["fallbacks"] == 0
    assert eng.counters["learned"] == 40
    assert eng.check_accounting()["lost"] == 0


def test_whole_chain_down_sheds_counted():
    """Decided arm down and every chain link down: the request is shed
    with a counted drop and a log record — not an exception, not a
    silent loss."""
    r = BlindFakeRouter(K, prefer=0)
    eng = _engine(r, fallback_chains={0: [1]})
    eng.set_arm_health(0, False)
    eng.set_arm_health(1, False)
    eng.submit(_reqs(15))
    recs = eng.pump() + eng.drain()
    assert all(x["status"] == "shed_no_arm" for x in recs)
    assert eng.counters["shed_no_arm"] == 15
    assert eng.counters["completed"] == 0
    assert eng.check_accounting()["lost"] == 0


def test_all_arms_down_never_deadlocks():
    eng = _engine(FakeRouter(K))
    for a in range(K):
        eng.set_arm_health(a, False)
    eng.submit(_reqs(40))
    recs = eng.pump() + eng.drain()      # returns; no stall, no raise
    assert len(recs) == 40
    assert eng.counters["shed_no_arm"] == 40
    assert eng.in_flight == 0
    assert eng.check_accounting()["lost"] == 0


# --------------------------------------------------- decide exceptions --
def test_decide_exception_degrades_without_learning():
    """An injected decide fault degrades the microbatch to the cheapest
    healthy arm, serves it, and skips the router update — the router
    never learns from decisions it did not make."""
    r = FakeRouter(K, prefer=3)
    faults = ScriptedFaults(fail_decide_calls=[0])
    eng = _engine(r, fault_hook=faults.on_decide)
    eng.submit(_reqs(10))
    recs = eng.pump() + eng.drain()
    assert faults.injected_decide_faults == 1
    assert eng.counters["decide_errors"] == 1
    ok = [x for x in recs if x["status"] == "ok"]
    assert len(ok) == 10
    assert all(x["action"] == 0 for x in ok)   # cheapest healthy arm
    assert r.update_calls == []                # no update for the batch
    assert eng.counters["skipped_learn"] == 10

    eng.submit(_reqs(10))                      # call 1: back to normal
    recs = eng.pump() + eng.drain()
    assert eng.counters["decide_errors"] == 1
    assert all(x["action"] == 3 for x in recs if x["status"] == "ok")
    assert r.update_calls == [10]
    assert eng.check_accounting()["lost"] == 0


def test_decide_exception_with_outage_degrades_to_healthy():
    """Fault + outage stacked: the degrade target skips down arms."""
    faults = ScriptedFaults(fail_decide_calls=[0])
    eng = _engine(FakeRouter(K), fault_hook=faults.on_decide)
    eng.set_arm_health(0, False)
    eng.submit(_reqs(8))
    recs = eng.pump() + eng.drain()
    assert all(x["action"] == 1 for x in recs if x["status"] == "ok")
    assert eng.check_accounting()["lost"] == 0


# --------------------------------------------------- queue saturation --
def test_bounded_queue_sheds_burst_with_counted_drops():
    eng = _engine(FakeRouter(K), queue_capacity=32, decide_batch=32)
    admitted, shed = eng.submit(_reqs(100))
    assert (admitted, shed) == (32, 68)
    assert eng.counters["shed_queue_full"] == 68
    recs = eng.pump() + eng.drain()
    assert eng.counters["completed"] == 32
    assert eng.check_accounting()["lost"] == 0
    # shed records carry the drop reason
    sheds = [x for x in eng.log if x["status"] == "shed_queue_full"]
    assert len(sheds) == 68


def test_queue_saturation_mid_stream_recovers():
    """Saturate, drain, saturate again: capacity is per-moment, not a
    lifetime budget; later waves are admitted once the queue empties."""
    eng = _engine(FakeRouter(K), queue_capacity=32, decide_batch=32)
    total_ok = 0
    for w in range(5):
        eng.submit(_reqs(50, start=w * 50))
        recs = eng.pump() + eng.drain()
        total_ok += sum(1 for x in recs if x["status"] == "ok")
    assert total_ok == eng.counters["completed"] == 5 * 32
    assert eng.counters["shed_queue_full"] == 5 * 18
    assert eng.check_accounting()["lost"] == 0


def test_queue_capacity_must_fit_a_microbatch():
    with pytest.raises(ValueError, match="queue_capacity"):
        _engine(FakeRouter(K), queue_capacity=8, decide_batch=32)


# ------------------------------------------------- reward accounting --
def test_learning_accounting_consistent_under_chaos():
    """Messy run — outages toggling, injected decide faults, queue
    pressure — the learning ledger still balances: every completed
    request was either learned from or counted as skipped."""
    r = BlindFakeRouter(K, prefer=0)
    faults = ScriptedFaults(fail_decide_calls=[1, 4],
                            outages=[(0, 2, 5), (1, 3, 6), (2, 4, 6)])
    eng = _engine(r, fault_hook=faults.on_decide, queue_capacity=64,
                  decide_batch=16)
    for w in range(8):
        faults.apply_wave(eng, w)
        eng.submit(_reqs(40, start=w * 40))
        eng.pump()
        eng.drain()
    c = eng.check_accounting()
    assert c["lost"] == 0
    assert c["learned"] + c["skipped_learn"] == c["completed"]
    assert c["learned"] == sum(r.update_calls)
    assert c["decide_errors"] == 2
    assert c["completed"] + c["shed_queue_full"] + c["shed_no_arm"] \
        == c["submitted"]


# ------------------------------------------------------------- storms --
def test_storm_absorbs_everything_zero_lost():
    """run_storm end-to-end with cascading outages, an injected decide
    fault, and flash-crowd pressure on a tiny queue: every outage
    absorbed, zero unhandled exceptions, zero lost requests."""
    reward, quality, cost = _tables()
    env = types.SimpleNamespace(reward=reward, quality=quality, cost=cost)
    m = run_storm(env, FakeRouter(K), requests=2_000, waves=20,
                  pattern="flash_crowd",
                  outages=[(0, 4, 12), (1, 8, 14)],
                  fail_decide_calls=[3], queue_capacity=64,
                  decide_batch=32, serve_batch=32, seed=0)
    assert m["lost_requests"] == 0
    assert m["decide_errors"] == 1
    assert m["completed"] + m["shed"] == m["requests"]
    assert m["decide_calls"] > 0 and m["decide_p99_us"] >= m["decide_p50_us"]
    # the tiny queue under a 10x crowd must shed — and must count it
    assert m["shed"] == m["shed_queue_full"] + m["shed_no_arm"]


def test_scenario_engine_drives_outage_windows():
    """The sim scenario engine doubles as the outage generator: the
    `arm_outage` cascades map onto well-formed per-arm windows, and a
    storm driven by them loses nothing."""
    from repro.data.routerbench import RouterBenchSim
    from repro.sim import DeviceReplayEnv

    henv = RouterBenchSim(seed=0, n_samples=600, n_slices=4)
    env = DeviceReplayEnv.from_host(henv)
    waves = 12
    wins = outages_from_scenario("arm_outage", env, waves)
    assert wins, "arm_outage produced no outage windows"
    for arm, s, e in wins:
        assert 0 <= arm < env.K and 0 <= s < e <= waves
    m = run_storm(env, FakeRouter(env.K), requests=600, waves=waves,
                  pattern="steady", scenario="arm_outage",
                  queue_capacity=128, decide_batch=32, seed=0)
    assert m["lost_requests"] == 0
    assert m["completed"] + m["shed"] == 600
    assert m["outages"] == [list(w) for w in wins]
