"""Phased learning lifecycle tests (DESIGN.md §13): the
LoggedInteractions interchange format, the zoo's propensity semantics,
offline pretraining + warm starts (spec compilation, checkpoint cache,
PRNG invariance), the IPS/SNIPS/DM/DR estimators (unbiasedness on a
synthetic bandit with known propensities, DR parity against on-policy
replay), and the ``offline_online`` / ``ope_selection`` presets end to
end."""
import json
import math
import os

import numpy as np
import pytest

from repro.core.protocol import estimate_offline
from repro.core.utilitynet import UtilityNetConfig
from repro.data.logged import (
    LOGGED_SCHEMA_VERSION,
    LoggedInteractions,
    from_run_log,
    replay_corpus,
)
from repro.data.routerbench import RouterBenchSim
from repro.experiments import (
    ExperimentSpec,
    OPESpec,
    PolicySpec,
    PretrainSpec,
    apply_overrides,
    compile_spec,
    make_preset,
    pretrained_states,
    run_plan,
    spec_from_json,
    spec_to_json,
)
from repro.sim import (
    DeviceReplayEnv,
    make_policy,
    pretrain_policy_state,
    run_policy_device,
)

TINY = {"data.n_samples": 600, "data.n_slices": 3,
        "train.train_steps": 8, "train.batch_size": 32}


@pytest.fixture(scope="module")
def envs():
    henv = RouterBenchSim(seed=0, n_samples=600, n_slices=3)
    return henv, DeviceReplayEnv.from_host(henv)


@pytest.fixture(scope="module")
def cfg(envs):
    henv, _ = envs
    return UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)


# ----------------------------------------------------- logged data format --
def test_replay_corpus_exact_uniform_propensities(envs, tmp_path):
    _, env = envs
    corpus = replay_corpus(env, 500, seed=3)
    K = corpus.num_actions
    assert corpus.n == 500 and corpus.has_propensities
    np.testing.assert_allclose(corpus.logp, -math.log(K), rtol=1e-6)
    assert corpus.slice_idx.min() >= 0 and corpus.slice_idx.max() < 3
    # realized rewards read off the env's reward table at (row, arm)
    reward = np.asarray(env.reward)
    np.testing.assert_allclose(
        corpus.reward, reward[corpus.sample_idx, corpus.action], rtol=1e-6)

    path = os.path.join(tmp_path, "corpus.npz")
    corpus.save(path)
    back = LoggedInteractions.load(path)
    assert back.behavior == corpus.behavior
    assert back.num_actions == K
    np.testing.assert_array_equal(back.action, corpus.action)
    np.testing.assert_allclose(back.logp, corpus.logp)
    np.testing.assert_allclose(back.x_emb, corpus.x_emb)


def test_record_log_round_trips_through_sim_scan(envs, cfg):
    _, env = envs
    pol, hyp = make_policy("eps_greedy", env, cfg)
    _, logged = run_policy_device(env, pol, hyp, seed=0, record_log=True)
    # one row per VALID replay sample, propensities are log-probs
    assert logged.n == int((np.asarray(env.mask) > 0).sum())
    assert logged.behavior == pol.name
    assert logged.has_propensities
    assert logged.logp.max() <= 1e-6
    assert np.isfinite(logged.logp).all()
    # recording must not perturb the run itself (zero extra PRNG use)
    res_plain = run_policy_device(env, pol, hyp, seed=0)
    res_rec, _ = run_policy_device(env, pol, hyp, seed=0, record_log=True)
    np.testing.assert_allclose(res_plain["avg_reward"],
                               res_rec["avg_reward"], rtol=1e-6)


def test_logged_validation_errors():
    x = np.zeros((4, 3), np.float32)
    ok = dict(x_emb=x, x_feat=np.zeros((4, 2)), domain=np.zeros(4),
              action=np.zeros(4), reward=np.zeros(4), logp=None,
              slice_idx=np.zeros(4), num_actions=2)
    LoggedInteractions(**ok)
    with pytest.raises(ValueError, match="reward"):
        LoggedInteractions(**{**ok, "reward": np.zeros(3)})
    with pytest.raises(ValueError, match="actions outside"):
        LoggedInteractions(**{**ok, "action": np.full(4, 7)})
    with pytest.raises(ValueError, match="log-probabilities"):
        LoggedInteractions(**{**ok, "logp": np.full(4, 0.5)})


# -------------------------------------------------------- OPE estimators --
def _synthetic_log(n=40_000, seed=0):
    """Context-free bandit with KNOWN behavior propensities: arm means
    mu, behavior dist p — the ground truth any estimator must recover."""
    rng = np.random.default_rng(seed)
    mu = np.array([0.2, 0.5, 0.7, 0.4])
    p = np.array([0.4, 0.3, 0.2, 0.1])
    a = rng.choice(4, size=n, p=p)
    r = mu[a] + rng.uniform(-0.1, 0.1, size=n)
    logged = LoggedInteractions(
        x_emb=rng.normal(size=(n, 8)).astype(np.float32),
        x_feat=np.zeros((n, 2), np.float32), domain=np.zeros(n),
        action=a, reward=r, logp=np.log(p[a]).astype(np.float32),
        slice_idx=np.zeros(n), num_actions=4, behavior="synthetic")
    return logged, mu


def test_ips_snips_dr_unbiased_on_known_bandit():
    logged, mu = _synthetic_log()
    q = np.array([0.1, 0.2, 0.3, 0.4])
    truth = float(q @ mu)
    probs = np.broadcast_to(q, (logged.n, 4))
    qhat = np.broadcast_to(mu, (logged.n, 4))
    est = estimate_offline(logged, probs, qhat=qhat)
    assert abs(est["ips"] - truth) < 0.02
    assert abs(est["snips"] - truth) < 0.02
    assert abs(est["dm"] - truth) < 1e-6       # exact model -> exact DM
    assert abs(est["dr"] - truth) < 0.02
    assert est["n"] == logged.n and est["ess"] > 0
    # identity target (target == behavior): weights are ~1 and every
    # estimator collapses to the log's own mean reward
    own_probs = np.broadcast_to(np.array([0.4, 0.3, 0.2, 0.1]),
                                (logged.n, 4))
    own = estimate_offline(logged, own_probs)
    assert abs(own["snips"] - logged.reward.mean()) < 0.02
    assert abs(own["mean_w"] - 1.0) < 0.02


def test_estimate_offline_clip_bounds_weights():
    logged, mu = _synthetic_log(n=5000, seed=1)
    probs = np.broadcast_to(np.array([0.0, 0.0, 0.0, 1.0]), (logged.n, 4))
    raw = estimate_offline(logged, probs)
    clipped = estimate_offline(logged, probs, clip=1.0)
    # point mass on the rarest arm: w = 1/0.1 on ~10% of rows (E[w]=1);
    # clipping caps those at 1 -> mean weight collapses to ~P(a=3)
    assert abs(raw["mean_w"] - 1.0) < 0.1
    assert clipped["mean_w"] < 0.2
    assert clipped["ips"] < raw["ips"]          # downward clip bias
    assert clipped["ess"] > raw["ess"]          # variance bought with it


def test_estimate_offline_fails_loudly_without_propensities():
    logged, _ = _synthetic_log(n=100)
    logged.logp = None
    logged.behavior = "mystery-run"
    probs = np.full((100, 4), 0.25)
    with pytest.raises(ValueError, match="mystery-run"):
        estimate_offline(logged, probs)


def test_estimate_offline_shape_errors():
    logged, _ = _synthetic_log(n=100)
    with pytest.raises(ValueError):
        estimate_offline(logged, np.full((50, 4), 0.25))
    with pytest.raises(ValueError):
        estimate_offline(logged, np.full((100, 4), 0.25),
                         qhat=np.zeros((100, 3)))


# --------------------------------------------------- offline pretraining --
def test_pretrain_changes_state_and_beats_random(envs, cfg):
    _, env = envs
    corpus = replay_corpus(env, 2000, seed=0)
    pol, hyp = make_policy("sup_winrate", env, cfg)
    state = pretrain_policy_state(env, pol, hyp, corpus, seed=0)
    assert float(np.abs(np.asarray(state["b"])).sum()) > 0  # ridge folded
    res = run_policy_device(env, pol, hyp, seed=0, init_state=state)
    rnd, rh = make_policy("random", env, cfg)
    res_rnd = run_policy_device(env, rnd, rh, seed=0)
    assert (np.mean(res["avg_reward"])
            > np.mean(res_rnd["avg_reward"]) + 0.1)


def test_injected_init_state_preserves_prng_stream(envs, cfg):
    """Injecting a policy's own cold init state must be bit-identical
    to not injecting at all — the warm/cold comparison isolates state,
    never the PRNG stream."""
    _, env = envs
    corpus = replay_corpus(env, 200, seed=0)
    pol, hyp = make_policy("greedy", env, cfg)   # pretrain hook is a no-op
    state = pretrain_policy_state(env, pol, hyp, corpus, seed=0)
    res_inj = run_policy_device(env, pol, hyp, seed=0, init_state=state)
    res_plain = run_policy_device(env, pol, hyp, seed=0)
    np.testing.assert_array_equal(res_inj["avg_reward"],
                                  res_plain["avg_reward"])


def test_pretrain_requires_corpus(envs, cfg):
    _, env = envs
    pol, hyp = make_policy("linucb", env, cfg)
    with pytest.raises(ValueError, match="corpus"):
        pretrain_policy_state(env, pol, hyp, None)


# ------------------------------------------------------------ spec codec --
def test_pretrain_ope_specs_round_trip():
    spec = ExperimentSpec(
        name="lc", policies=(PolicySpec("neuralucb"),
                             PolicySpec("min_cost")),
        pretrain=PretrainSpec(corpus_size=1000, steps=64,
                              warm_start=(True, False)),
        ope=OPESpec(targets=("min_cost", "random"), parity=("min_cost",)))
    doc = json.loads(json.dumps(spec_to_json(spec)))
    assert spec_from_json(doc) == spec
    doc["pretrain"]["bogus"] = 1
    with pytest.raises(ValueError, match="unknown keys"):
        spec_from_json(doc)


def test_pre_lifecycle_specs_emit_no_lifecycle_keys():
    """Specs without pretrain/ope serialize exactly as before the
    lifecycle existed — their hashes are stable across the PR."""
    doc = spec_to_json(make_preset("paper_table1"))
    assert "pretrain" not in doc and "ope" not in doc


def test_lifecycle_spec_validation():
    with pytest.raises(ValueError):
        PretrainSpec(corpus_size=0)
    with pytest.raises(ValueError):
        PretrainSpec(warm_start=())
    with pytest.raises(ValueError):
        OPESpec(targets=())
    with pytest.raises(ValueError):   # parity must be a subset of targets
        OPESpec(targets=("random",), parity=("min_cost",))


def test_policies_filter_override():
    spec = make_preset("offline_online",
                       {"policies": ["neuralucb", "random"]})
    assert [p.label for p in spec.policies] == ["neuralucb", "random"]
    with pytest.raises(KeyError, match="no policy entry"):
        make_preset("offline_online", {"policies": ["nope"]})


# -------------------------------------------------------------- compiler --
def test_compiler_expands_warm_cold_axis(envs, cfg):
    henv, denv = envs
    spec = ExperimentSpec(
        name="wc", policies=(PolicySpec("neuralucb"),
                             PolicySpec("sup_winrate"),
                             PolicySpec("random")),
        pretrain=PretrainSpec(corpus_size=500, steps=8,
                              warm_start=(True, False)))
    plan = compile_spec(spec, env=denv, host_env=henv)
    call = plan.calls[0]
    assert set(call.policies) == {"neuralucb:warm", "neuralucb:cold",
                                  "sup_winrate:warm", "sup_winrate:cold",
                                  "random"}
    assert plan.pretrain_labels == {
        "neuralucb:warm": True, "neuralucb:cold": False,
        "sup_winrate:warm": True, "sup_winrate:cold": False}
    assert call.grids["neuralucb:warm"][0]["warm_start"] is True
    assert call.grids["neuralucb:cold"][0]["warm_start"] is False
    assert "warm_start" not in call.grids["random"][0]

    # a single warm_start value keeps the plain label
    spec1 = ExperimentSpec(
        name="w1", policies=(PolicySpec("linucb"),),
        pretrain=PretrainSpec(corpus_size=500, warm_start=(True,)))
    plan1 = compile_spec(spec1, env=denv, host_env=henv)
    assert plan1.pretrain_labels == {"linucb": True}


def test_compiler_validates_lifecycle_names(envs):
    henv, denv = envs
    bad_bh = ExperimentSpec(
        name="b", policies=(PolicySpec("random"),),
        pretrain=PretrainSpec(behavior="not_a_policy"))
    with pytest.raises(ValueError, match="not_a_policy"):
        compile_spec(bad_bh, env=denv, host_env=henv)
    bad_tgt = ExperimentSpec(
        name="b", policies=(PolicySpec("random"),),
        ope=OPESpec(targets=("no_such_target",)))
    with pytest.raises(ValueError, match="no_such_target"):
        compile_spec(bad_tgt, env=denv, host_env=henv)


def test_pretrain_checkpoint_cache_hits(envs, monkeypatch, tmp_path):
    henv, denv = envs
    monkeypatch.setenv("REPRO_PRETRAIN_CACHE", str(tmp_path))
    spec = ExperimentSpec(
        name="cache", policies=(PolicySpec("sup_winrate"),),
        pretrain=PretrainSpec(corpus_size=500, steps=8,
                              warm_start=(True,)))
    plan = compile_spec(spec, env=denv, host_env=henv)
    _, states1, info1 = pretrained_states(plan)
    assert info1["sup_winrate"]["cache_hit"] is False
    assert os.path.exists(info1["sup_winrate"]["path"])
    _, states2, info2 = pretrained_states(plan)
    assert info2["sup_winrate"]["cache_hit"] is True
    np.testing.assert_allclose(np.asarray(states1["sup_winrate"]["b"]),
                               np.asarray(states2["sup_winrate"]["b"]),
                               rtol=1e-6)


# ------------------------------------------------------------ end to end --
def test_offline_online_preset_end_to_end(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PRETRAIN_CACHE", str(tmp_path))
    spec = make_preset("offline_online", {
        **TINY, "policies": ["sup_winrate", "linucb", "random"],
        "pretrain.corpus_size": 1500, "pretrain.steps": 32,
        "seeds": [0]})
    res = run_plan(compile_spec(spec))
    assert res.ok
    pols = {c["policy"] for c in res.cells}
    assert {"sup_winrate:warm", "sup_winrate:cold", "linucb:warm",
            "linucb:cold", "random"} <= pols
    # the warm supervised router must beat its cold (= untrained) self
    warm = res.cell("sup_winrate:warm", warm_start=True)
    cold = res.cell("sup_winrate:cold", warm_start=False)
    assert warm["avg_reward_mean"] > cold["avg_reward_mean"]
    assert res.manifest["pretrain"]["corpus_size"] == 1500
    assert set(res.manifest["pretrain"]["labels"]) == {
        "sup_winrate:warm", "linucb:warm"}


def test_ope_selection_preset_end_to_end():
    # The preset's parity_tol (0.05) is sized for full-length logs. At
    # TINY's n=600 the eps-greedy log covers the min-cost arm thinly
    # (ESS ~7 on seed 0), so the DR estimate's standard error is 0.032
    # and the on-policy mean's 0.013: the gate here is 3 sigma of their
    # difference, not a tolerance fitted to one PRNG stream.
    spec = make_preset("ope_selection", {
        **TINY, "ope.parity_tol": 3 * math.hypot(0.032, 0.013)})
    res = run_plan(compile_spec(spec))
    assert res.ok
    offline = res.cells_for("offline")
    assert {c["policy"] for c in offline} == {"min_cost", "greedy",
                                              "sup_winrate", "random"}
    for c in offline:
        for k in ("ips", "snips", "dm", "dr", "ess"):
            assert np.isfinite(c["ope"][k])
    pinned = res.cell("min_cost", scenario="offline")
    assert pinned["ope_ok"] and np.isfinite(pinned["onpolicy_value"])
    # random's uniform target is the easy sanity anchor: its estimate
    # must sit near the behavior env's uniform value, far below min_cost
    rnd = res.cell("random", scenario="offline")
    assert rnd["ope"]["snips"] < pinned["ope"]["snips"]
    assert res.manifest["ope"]["parity_ok"]


def test_ope_and_serving_cannot_share_a_spec():
    spec = make_preset("serving_storm")
    with pytest.raises(ValueError, match="serving"):
        ExperimentSpec(
            name="bad", policies=spec.policies, serving=spec.serving,
            ope=OPESpec(targets=("random",)))


# ----------------------------------------------------------- serving log --
def test_serving_router_log_round_trip(envs, cfg):
    from repro.serving.policy_router import DevicePolicyRouter
    from repro.sim.engine import _tables

    henv, env = envs
    pol, hyp = make_policy("eps_greedy", env, cfg)
    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=32, capacity_slices=8,
                                batch_size=16, train_chunks=1,
                                log_capacity=64)
    reward = np.asarray(env.reward)
    for start in (0, 32, 64):
        ids = np.arange(start, start + 32)
        d = router.decide(sample_idx=ids)
        assert d["logp"].shape == (32,) and d["logp"].max() <= 1e-6
        router.update_wave(d, d["action"], reward[ids, d["action"]])
    logged = router.to_logged()
    assert logged.behavior == f"serving:{pol.name}"
    assert logged.has_propensities and logged.n == 64  # capacity-trimmed
    np.testing.assert_allclose(
        logged.reward, reward[logged.sample_idx, logged.action], rtol=1e-6)
    # a log-disabled router refuses loudly
    router_off = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                    slice_width=32, capacity_slices=8,
                                    batch_size=16, train_chunks=1)
    with pytest.raises(ValueError, match="log_capacity"):
        router_off.to_logged()


def test_serving_router_accepts_pretrained_state(envs, cfg):
    from repro.serving.policy_router import DevicePolicyRouter
    from repro.sim.engine import _tables

    _, env = envs
    corpus = replay_corpus(env, 1500, seed=0)
    pol, hyp = make_policy("sup_winrate", env, cfg)
    state = pretrain_policy_state(env, pol, hyp, corpus, seed=0)
    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=32, capacity_slices=4,
                                batch_size=16, train_chunks=1,
                                pretrained_state=state)
    cold = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                              slice_width=32, capacity_slices=4,
                              batch_size=16, train_chunks=1)
    ids = np.arange(32)
    reward = np.asarray(env.reward)
    r_warm = reward[ids, router.decide(sample_idx=ids)["action"]].mean()
    r_cold = reward[ids, cold.decide(sample_idx=ids)["action"]].mean()
    assert r_warm > r_cold   # pretrained scores route better than zeros
