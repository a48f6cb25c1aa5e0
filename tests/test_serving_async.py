"""Async serving engine correctness: microbatched continuous batching,
admission control, the engine's construction rules (router protocol,
default ``c_max``, bounded log), and the two parity pins —

* `DevicePolicyRouter` driven one wave per slice reproduces
  `run_policy_device` BIT-EXACTLY (same PRNG discipline, same jitted
  policy callbacks, state device-resident throughout), and
* the same holds with `AsyncRouterEngine` in front of the router, for
  four members of the policy zoo.

Plus snapshot/restore round-trips: serve N, snapshot, kill, restore,
serve N more — identical to the uninterrupted run."""
import collections
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import NeuralUCBRouter
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.serving import (
    AsyncRouterEngine,
    DevicePolicyRouter,
    Request,
)
from repro.serving import policy_router as pr
from repro.sim import DeviceReplayEnv, make_policy, run_policy_device
from repro.sim.engine import _tables
from repro.sim.policies import POLICIES
from serving_fakes import FakeRouter

TOK = np.arange(1, 5, dtype=np.int32)


def _replay_env(K=2, n=48, T=3):
    """Tiny custom replay stream (same recipe as the PR-3 pool-parity
    test): deterministic tables, T slices of n/T samples."""
    rng = np.random.default_rng(0)
    plen = rng.integers(4, 9, size=n)
    cpt = np.array([2e-4, 1e-5])
    data = {
        "domain": rng.integers(0, 3, size=n).astype(np.int32),
        "topic": rng.normal(size=(n, 32)).astype(np.float32),
        "difficulty": np.zeros(n, np.float32),
        "prompt_tokens": plen.astype(np.float32),
        "quality": rng.uniform(0.2, 0.95, size=(n, K)).astype(np.float32),
        "cost": (cpt[None] * (plen[:, None] + 8)).astype(np.float32),
        "x_feat": rng.normal(size=(n, 4)).astype(np.float32),
        "model_names": np.array(["a", "b"]),
    }
    henv = RouterBenchSim(seed=0, n_slices=T, cost_lambda=1.0, data=data)
    return henv, DeviceReplayEnv.from_host(henv)


# ----------------------------------------------------- engine mechanics --
def _fake_engine(**kw):
    rng = np.random.default_rng(0)
    rw = rng.uniform(0.1, 0.9, (100, 3)).astype(np.float32)
    kw.setdefault("decide_batch", 32)
    return rw, AsyncRouterEngine(FakeRouter(3), 3, reward_table=rw, **kw)


def test_engine_microbatches_greedily():
    rw, eng = _fake_engine()
    reqs = [Request(tokens=TOK, sample_idx=i % 100) for i in range(100)]
    sample_of = {r.rid: r.sample_idx for r in reqs}
    eng.submit(reqs)
    recs = eng.pump() + eng.drain()
    assert eng.counters["decide_calls"] == 4     # 32 + 32 + 32 + 4
    assert eng.counters["completed"] == 100
    ok = [r for r in recs if r["status"] == "ok"]
    assert len(ok) == 100
    for r in ok:        # table feedback wired through exactly
        assert r["reward"] == pytest.approx(
            rw[sample_of[r["rid"]], r["action"]])
    assert eng.check_accounting()["lost"] == 0


def test_decide_flush_holds_partial_microbatches():
    """Admission control: with ``decide_flush`` set, an undersized
    microbatch waits for its window instead of dispatching a tiny decide
    per pump; ``force``/drain still flushes immediately."""
    now = [0.0]
    _, eng = _fake_engine(decide_flush=1.0, clock=lambda: now[0])
    eng.submit([Request(tokens=TOK, sample_idx=i) for i in range(5)])
    eng.pump()
    assert eng.counters["decide_calls"] == 0 and eng.in_flight == 5
    now[0] = 0.5
    eng.pump()
    assert eng.counters["decide_calls"] == 0     # still inside the window
    now[0] = 1.25
    recs = eng.pump()
    assert eng.counters["decide_calls"] == 1
    assert sum(1 for r in recs if r["status"] == "ok") == 5
    # a full microbatch never waits on the window
    eng.submit([Request(tokens=TOK, sample_idx=i % 100) for i in range(32)])
    eng.pump()
    assert eng.counters["decide_calls"] == 2
    assert eng.check_accounting()["lost"] == 0


def test_engine_refuses_router_without_update_wave():
    """The engine speaks one router protocol; a host router (``decide``
    over features, ``update``) is refused at construction, naming the
    missing method, not mid-pump."""
    ucfg = UtilityNetConfig(emb_dim=16, num_actions=2, num_domains=3)
    with pytest.raises(TypeError, match="update_wave"):
        AsyncRouterEngine(NeuralUCBRouter(ucfg, seed=0), 2,
                          reward_table=np.zeros((4, 2), np.float32))


def test_engine_default_c_max_uses_actual_max_seq():
    """Regression: the default c_max normalized by a fixed 4096-token
    horizon while the engines cap sequences at max_seq — every
    realizable cost then normalized to < max_seq/4096 of the range,
    compressing rewards toward quality-only and erasing cost
    discrimination between arms. The default must derive from the
    engines' actual max_seq (explicit c_max still wins)."""
    engines = [types.SimpleNamespace(max_seq=256),
               types.SimpleNamespace(max_seq=64)]
    cpt = [1e-4, 1e-6]
    eng = AsyncRouterEngine(FakeRouter(2), 2, engines=engines,
                            cost_per_token=cpt)
    assert eng.c_max == pytest.approx(1e-4 * 256)
    explicit = AsyncRouterEngine(FakeRouter(2), 2, engines=engines,
                                 cost_per_token=cpt, c_max=0.05)
    assert explicit.c_max == 0.05
    # realizable cost at the cap reaches the top of the normalized
    # range instead of 256/4096 of it
    assert 1e-4 * max(e.max_seq for e in engines) / eng.c_max == \
        pytest.approx(1.0)


@pytest.mark.parametrize("cap", [8, None], ids=["cap8", "unbounded"])
def test_engine_log_keeps_newest_records(cap):
    """Regression: a serving log grew without bound under sustained
    traffic. ``log`` is a capped deque of the newest records (queue-full
    sheds included), ``dropped_log_records`` counts every eviction
    exactly, and ``log_capacity=None`` keeps everything."""
    _, eng = _fake_engine(log_capacity=cap, queue_capacity=32)
    for n in (40, 20):                  # 60 records; the first wave sheds 8
        admitted, _ = eng.submit(
            [Request(tokens=TOK, sample_idx=i) for i in range(n)])
        last = eng.pump() + eng.drain()
        assert len(last) == admitted
    assert eng.counters["shed_queue_full"] == 8
    assert eng.log.maxlen == cap
    if cap is None:
        assert len(eng.log) == 60
        assert eng.counters["dropped_log_records"] == 0
    else:
        assert list(eng.log) == last[-cap:]
        assert eng.counters["dropped_log_records"] == 60 - cap


# ---------------------------------------------------- sim bit-parity --
def _assert_scan_state(router, state, key):
    """The router's PRNG key and every state leaf equal the scan's, bit
    for bit."""
    np.testing.assert_array_equal(np.asarray(router._key),
                                  np.asarray(key), err_msg="PRNG key")
    ref = jax.tree_util.tree_leaves(state)
    got = jax.tree_util.tree_leaves(router.state)
    assert len(ref) == len(got)
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"state leaf {i}")


def test_device_router_bit_parity_with_sim_scan():
    """One serving wave per slice through `DevicePolicyRouter` ==
    `run_policy_device`: identical per-slice action histograms and
    BIT-IDENTICAL final state (params, optimizer, A^-1, PRNG key). This
    pins the serving adapter to the paper engine — a drifted key split
    or a reordered Woodbury update fails loudly here."""
    henv, env = _replay_env()
    T, S = henv.n_slices, 16
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    res, state, key = run_policy_device(
        env, pol, hyp, seed=0, train_steps=32, batch_size=16,
        return_state=True)

    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=S, capacity_slices=T,
                                batch_size=16, train_chunks=1)
    router.warmup()    # must not perturb state or the PRNG stream
    reward = np.asarray(env.reward)
    for t in range(T):
        ids = henv.slice_batch(t)["idx"]
        dec = router.decide(sample_idx=ids)
        np.testing.assert_array_equal(
            np.bincount(dec["action"], minlength=env.K),
            res["action_hist"][t], err_msg=f"slice {t} actions")
        router.update_wave(dec, dec["action"], reward[ids, dec["action"]])
        router.end_slice()

    _assert_scan_state(router, state, key)


@pytest.mark.parametrize("name", ["neuralucb", "linucb", "neural_ts",
                                  "eps_greedy"])
def test_engine_over_device_router_matches_sim_scan(name):
    """The served path end to end: `AsyncRouterEngine` in front of a
    `DevicePolicyRouter`, one wave per slice (``decide_batch`` = the
    slice width, ``end_slice`` after each wave), reproduces
    `run_policy_device` — identical per-slice action histograms and
    BIT-IDENTICAL final state and PRNG key. The engine's columns, serve
    grouping and table write-back must hand the router exactly the rows,
    arms and rewards the scan feeds itself."""
    henv, env = _replay_env()
    T, S = henv.n_slices, 16
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy(name, env, cfg)
    res, state, key = run_policy_device(
        env, pol, hyp, seed=0, train_steps=32, batch_size=16,
        return_state=True)

    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=S, capacity_slices=T,
                                batch_size=16, train_chunks=1)
    eng = AsyncRouterEngine(router, env.K,
                            reward_table=np.asarray(env.reward),
                            quality_table=np.asarray(env.quality),
                            cost_table=np.asarray(env.cost),
                            decide_batch=S, serve_batch=S)
    for t in range(T):
        ids = henv.slice_batch(t)["idx"]
        assert ids.size == S
        eng.submit([Request(tokens=TOK, sample_idx=int(i)) for i in ids])
        recs = [r for r in eng.pump() + eng.drain() if r["status"] == "ok"]
        assert len(recs) == S and eng.counters["decide_calls"] == t + 1
        np.testing.assert_array_equal(
            np.bincount([r["action"] for r in recs], minlength=env.K),
            res["action_hist"][t], err_msg=f"slice {t} actions")
        eng.end_slice()
    assert eng.counters["learned"] == T * S
    assert eng.check_accounting()["lost"] == 0

    _assert_scan_state(router, state, key)


# ------------------------------------------ one crossing each way --
def _small_router(name, env):
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K,
                           d_text=16, d_hidden=16, d_last=8)
    pol, hyp = make_policy(name, env, cfg)
    return DevicePolicyRouter(pol, hyp, _tables(env), seed=3,
                              slice_width=16, capacity_slices=3,
                              batch_size=8, train_chunks=1)


def _same_bits(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


@pytest.mark.parametrize("down", [None, 1], ids=["all_up", "arm1_down"])
@pytest.mark.parametrize("name", sorted(POLICIES))
def test_packed_router_calls_are_exact(name, down):
    """The packed decide returns the same actions, logp, aux and next
    key, bit for bit, as the decide program run alone on the unpacked
    ids with the key split on the host; an update fed edge values (-0.0,
    a subnormal, 1e30, 0/1 masks, a remap that reorders the rows)
    through the one packed buffer leaves the same state as the unpacked
    arrays."""
    _, env = _replay_env()
    r = _small_router(name, env)
    st = dict(fcfg=r.fcfg, train_chunks=r.train_chunks,
              batch_size=r.batch_size)
    B = 11                                   # < S: the padding is live
    ids = (np.arange(B) * 5) % 48
    avail = None if down is None else np.where(
        np.arange(env.K) == down, 0.0, 1.0)

    key0 = r._key
    dec = r.decide(sample_idx=ids, avail=avail)
    nxt, k = jax.random.split(key0)
    a, logp, aux = pr._srv_decide_rows(
        r.policy, r.state, k, r.tables, r.hyp,
        jnp.asarray(np.pad(ids, (0, r.S - B)).astype(np.int32)),
        None if avail is None else jnp.asarray(avail, jnp.float32),
        jnp.int32(0), **st)
    _same_bits(dec["action"], np.asarray(a)[:B].astype(np.int32), "action")
    _same_bits(dec["logp"], np.broadcast_to(
        np.asarray(logp, np.float32), a.shape)[:B], "logp")
    _same_bits(r._key, nxt, "key")
    for i, (x, y) in enumerate(zip(jax.tree_util.tree_leaves(dec["aux"]),
                                   jax.tree_util.tree_leaves(aux))):
        _same_bits(x, y, f"aux leaf {i}")

    # the update's transport: every value makes an exact round trip
    tiny = np.float32(1e-45)                 # the least subnormal
    rewards = np.resize(np.array([-0.0, tiny, 1e30, 0.5], np.float32), B)
    served = dec["action"].copy()
    served[3] = (served[3] + 1) % env.K      # a fallback remap
    mask = np.resize(np.array([1, 1, 0, 1, 1], bool), B)
    learn = mask & (served == dec["action"])
    perm = np.concatenate([np.argsort(~learn, kind="stable"),
                           np.arange(B, r.S)]).astype(np.int32)
    assert not np.array_equal(perm, np.arange(r.S))
    row = r.wave % r.T
    buf = pr._pack_update(row, ids, served, rewards, learn, perm)
    pad = lambda v, dt: np.pad(np.asarray(v, dt), (0, r.S - B))  # noqa: E731
    args = (np.int32(row), pad(ids, np.int32), pad(served, np.int32),
            pad(rewards, np.float32), pad(learn, np.float32), perm)
    for i, (x, y) in enumerate(zip(jax.jit(pr._unpack_update)(buf), args)):
        _same_bits(x, y, f"unpacked field {i}")

    before = jax.tree_util.tree_map(jnp.copy, (r.state, r._env_idx))
    r.update_wave(dec, served, rewards, learn_mask=mask)
    want = jax.jit(pr._update, static_argnames=pr._STATIC)(
        r.policy, *before, r.tables, r.hyp, *map(jnp.asarray, args),
        dec["aux"], **st)
    got = jax.tree_util.tree_leaves((r.state, r._env_idx))
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for i, (x, y) in enumerate(zip(got, want)):
        _same_bits(x, y, f"state leaf {i}")


def test_router_calls_cross_once_each_way(monkeypatch):
    """After warm-up the fast path makes one put per decide and update,
    one get per decide, and no key split on the host; any other
    host-to-device transfer is refused by the transfer guard. A decide
    with an arm down puts its mask too."""
    henv, env = _replay_env()
    r = _small_router("neuralucb", env)
    reward = np.asarray(env.reward)
    ids = henv.slice_batch(0)["idx"]
    r.warmup()
    dec = r.decide(sample_idx=ids)           # compiles the update
    r.update_wave(dec, dec["action"], reward[ids, dec["action"]])

    calls = collections.Counter()

    def counted(what, fn):
        def shim(*a, **kw):
            calls[what] += 1
            return fn(*a, **kw)
        return shim

    monkeypatch.setattr(jax.random, "split",
                        counted("split", jax.random.split))
    monkeypatch.setattr(jax, "device_put", counted("put", jax.device_put))
    monkeypatch.setattr(jax, "device_get", counted("get", jax.device_get))
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            dec = r.decide(sample_idx=ids)
            assert calls == {"put": 1, "get": 1}
            r.update_wave(dec, dec["action"], reward[ids, dec["action"]])
            assert calls == {"put": 2, "get": 1}
            calls.clear()
        r.decide(sample_idx=ids, avail=np.array([1.0, 0.0]))
        assert calls == {"put": 2, "get": 1}


def test_async_train_overlap_staleness_bounded():
    """Zero-sync train overlap (``max_train_lag > 0``): decide never
    reads state more than ``max_train_lag`` train epochs stale, the
    overlap really defers commits (staleness > 0 is observed), the run
    routes every wave, and `state_dict` is a flush barrier — staleness
    drops to 0 and a restored router resumes synchronously clean."""
    lag = 2
    henv, env = _replay_env(n=96, T=6)
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    reward = np.asarray(env.reward)

    def build():
        return DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                  slice_width=16, capacity_slices=6,
                                  batch_size=16, train_chunks=1,
                                  max_train_lag=lag)

    router = build()
    seen = []
    for t in range(6):
        ids = henv.slice_batch(t)["idx"]
        dec = router.decide(sample_idx=ids)
        assert router.decide_staleness <= lag
        assert dec["action"].shape == ids.shape
        router.update_wave(dec, dec["action"], reward[ids, dec["action"]])
        router.end_slice()
        # dispatch happened, commit deferred to a later decide/flush
        seen.append(router.decide_staleness)
        assert router.decide_staleness <= lag
    assert max(seen) >= 1, "overlap never deferred a commit"
    sd = router.state_dict()                 # flush barrier
    assert router.decide_staleness == 0
    restored = build()
    restored.load_state_dict(sd)
    assert restored.decide_staleness == 0
    ids = henv.slice_batch(0)["idx"]
    dec = restored.decide(sample_idx=ids)    # restored router still serves
    assert dec["action"].shape == ids.shape


# -------------------------------------------------- snapshot/restore --
def _drive(eng, wave_ids):
    recs = []
    for ids in wave_ids:
        eng.submit([Request(tokens=TOK, sample_idx=int(i)) for i in ids])
        recs.extend(r for r in eng.pump() + eng.drain()
                    if r["status"] == "ok")
    return recs


def _wave_ids(n, waves, per, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n, per) for _ in range(waves)]


def test_snapshot_restore_round_trip_device_router(tmp_path):
    """Serve N waves, snapshot, kill, restore into a FRESH engine, serve
    N more: decisions, rewards, and counters match the uninterrupted
    run exactly (the ring buffers, PRNG key, and wave cursor all travel
    through the npz+json snapshot)."""
    henv, env = _replay_env()
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    reward = np.asarray(env.reward)
    q = np.asarray(env.quality)
    c = np.asarray(env.cost)

    def build():
        router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                    slice_width=16, capacity_slices=8,
                                    batch_size=16, train_chunks=1)
        return AsyncRouterEngine(router, env.K, reward_table=reward,
                                 quality_table=q, cost_table=c,
                                 decide_batch=16, serve_batch=16)

    ids = _wave_ids(reward.shape[0], 6, 16)
    path = str(tmp_path / "snap")

    eng_a = build()
    _drive(eng_a, ids[:3])
    eng_a.end_slice()
    eng_a.snapshot(path)
    recs_a = _drive(eng_a, ids[3:])      # uninterrupted continuation

    eng_b = build()                      # "kill": brand-new everything
    eng_b.restore(path)
    recs_b = _drive(eng_b, ids[3:])

    np.testing.assert_array_equal([r["action"] for r in recs_a],
                                  [r["action"] for r in recs_b])
    np.testing.assert_array_equal([r["reward"] for r in recs_a],
                                  [r["reward"] for r in recs_b])
    assert eng_a.counters == eng_b.counters


def test_snapshot_requires_drained_engine(tmp_path):
    henv, env = _replay_env()
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=16, capacity_slices=4,
                                batch_size=16, train_chunks=1)
    eng = AsyncRouterEngine(router, env.K,
                            reward_table=np.asarray(env.reward),
                            decide_batch=16, decide_flush=9e9)
    eng.submit([Request(tokens=TOK, sample_idx=0)])
    with pytest.raises(RuntimeError, match="in flight"):
        eng.snapshot(str(tmp_path / "bad"))
