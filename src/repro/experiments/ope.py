"""Off-policy evaluation stage of the learning lifecycle (DESIGN.md
§13.4).

One propensity-aware behavior log, scored against every target policy
of the spec via ``repro.core.protocol.estimate_offline`` (IPS / SNIPS /
DM / DR). Target action distributions reuse the SERVING decide kernel
(``repro.serving.policy_router._srv_decide_rows``) chunked over the logged
contexts at ``t=1`` — the post-warm-up step — so offline scoring runs
the exact routing code the online paths run, not a reimplementation.
Targets with a pretrain hook are first fit offline on the behavior log
(that is the selection story: pick a router from logs alone). For
targets named in ``spec.ope.parity`` the DR estimate is pinned against
an on-policy replay run of the same policy within ``parity_tol`` —
the artifact's ``ope_ok`` gate, wired into ``ExperimentResult.ok``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.protocol import estimate_offline
from repro.data.logged import LoggedInteractions
from repro.experiments.compiler import ExperimentPlan
from repro.sim import make_policy, pretrain_policy_state, run_policy_device
from repro.sim.policies import OPE_SMOOTHING_EPS, _lin_features, _no_pretrain

_CHUNK = 2048


def behavior_log(plan: ExperimentPlan) -> LoggedInteractions:
    """Run the spec's behavior policy over the replay env with
    ``record_log=True`` — the one logged run every target is scored
    from."""
    ope = plan.spec.ope
    pol, hyp = make_policy(ope.behavior, plan.env, plan.cfg,
                           ucb_backend=plan.spec.ucb_backend,
                           **dict(ope.behavior_overrides))
    _, logged = run_policy_device(
        plan.env, pol, hyp, seed=ope.seed, record_log=True,
        train_steps=plan.train_steps, epochs=plan.spec.train.epochs,
        batch_size=plan.spec.train.batch_size)
    return logged


def fit_qhat(logged: LoggedInteractions, *, ridge: float = 1.0
             ) -> np.ndarray:
    """Direct-method reward model for the DR estimator: one ridge
    regression per arm on the LinUCB featurization (L2-normalized
    embedding + bias), fit on the behavior log's observed
    (context, action, reward) triples. Returns ``(n, K)`` predictions
    for every logged context x every arm."""
    phi = np.asarray(_lin_features(jnp.asarray(logged.x_emb)),
                     np.float64)
    n, d = phi.shape
    k_arms = logged.num_actions
    theta = np.zeros((k_arms, d))
    for a in range(k_arms):
        rows = logged.action == a
        if not rows.any():
            continue
        gram = phi[rows].T @ phi[rows] + ridge * np.eye(d)
        theta[a] = np.linalg.solve(
            gram, phi[rows].T @ logged.reward[rows].astype(np.float64))
    return (phi @ theta.T).astype(np.float64)


def _target_actions(plan: ExperimentPlan, name: str,
                    logged: LoggedInteractions) -> np.ndarray:
    """Decide the target's action on every logged context through the
    serving kernel at ``t=1`` (past the neural warm-up slice), with the
    target pretrained on the behavior log when it has an offline
    phase."""
    from repro.serving.policy_router import _srv_decide_rows, _srv_init
    from repro.sim.engine import _tables

    ope = plan.spec.ope
    env = plan.env
    pol, hyp = make_policy(name, env, plan.cfg,
                           ucb_backend=plan.spec.ucb_backend)
    key = jax.random.PRNGKey(ope.seed)
    state, _, ptables = _srv_init(pol, key, _tables(env), hyp, env.idx)
    if pol.pretrain is not _no_pretrain:
        pt = plan.spec.pretrain
        state = pretrain_policy_state(
            env, pol, hyp, logged, seed=ope.seed,
            steps=pt.steps if pt is not None else 512,
            batch_size=pt.batch_size if pt is not None else 256)

    ids = np.asarray(logged.sample_idx, np.int32)
    n = ids.shape[0]
    pad = (-n) % _CHUNK
    ids_p = np.concatenate([ids, np.zeros(pad, np.int32)]) if pad else ids
    avail = jnp.ones((_CHUNK, env.K), jnp.float32)
    t1 = jnp.int32(1)
    acts: List[np.ndarray] = []
    for c0 in range(0, ids_p.shape[0], _CHUNK):
        a, _, _ = _srv_decide_rows(
            pol, state, jax.random.fold_in(key, c0), ptables, hyp,
            jnp.asarray(ids_p[c0:c0 + _CHUNK]), avail, t1)
        acts.append(np.asarray(a))
    return np.concatenate(acts)[:n]


def _target_probs(name: str, actions: np.ndarray, n: int, k_arms: int
                  ) -> np.ndarray:
    """Full per-row action distribution of a target. ``random`` is
    exactly uniform; every other target is the declared epsilon-smoothed
    point mass on its decided action (the same
    :data:`OPE_SMOOTHING_EPS` semantics the zoo's logp contract uses)."""
    if name == "random":
        return np.full((n, k_arms), 1.0 / k_arms)
    eps = OPE_SMOOTHING_EPS
    probs = np.full((n, k_arms), eps / k_arms)
    probs[np.arange(n), actions] += 1.0 - eps
    return probs


def score_policies_offline(plan: ExperimentPlan, *,
                           logged: Optional[LoggedInteractions] = None,
                           verbose: bool = False
                           ) -> Tuple[List[Dict[str, Any]],
                                      Dict[str, Any]]:
    """The full OPE stage: behavior log -> q-hat -> one artifact cell
    per target under scenario ``"offline"``. Returns ``(cells, info)``;
    ``info`` is the manifest block (behavior, log size, parity
    outcomes)."""
    ope = plan.spec.ope
    if logged is None:
        logged = behavior_log(plan)
    qhat = fit_qhat(logged)
    info: Dict[str, Any] = {"behavior": logged.behavior, "n": logged.n,
                            "targets": list(ope.targets)}
    cells: List[Dict[str, Any]] = []
    for name in ope.targets:
        acts = _target_actions(plan, name, logged)
        probs = _target_probs(name, acts, logged.n, logged.num_actions)
        est = estimate_offline(logged, probs, qhat=qhat, clip=ope.clip)
        cell = {"scenario": "offline", "policy": name, "point": {},
                "train_steps": 0,
                "avg_reward_mean": float(est["dr"]),
                "avg_reward_std": 0.0,
                "avg_cost_mean": float("nan"),
                "avg_quality_mean": float("nan"),
                "ope": est}
        if name in ope.parity:
            pol, hyp = make_policy(name, plan.env, plan.cfg,
                                   ucb_backend=plan.spec.ucb_backend)
            _, onlog = run_policy_device(
                plan.env, pol, hyp, seed=ope.seed, record_log=True,
                train_steps=plan.train_steps,
                epochs=plan.spec.train.epochs,
                batch_size=plan.spec.train.batch_size)
            value = float(onlog.reward.mean())
            cell["onpolicy_value"] = value
            cell["ope_ok"] = bool(abs(est["dr"] - value)
                                  <= ope.parity_tol)
        if verbose:
            gate = ""
            if "ope_ok" in cell:
                gate = (f" vs on-policy {cell['onpolicy_value']:.4f} "
                        f"-> {'ok' if cell['ope_ok'] else 'FAIL'}")
            print(f"[{plan.spec.name}] offline/{name}: "
                  f"dr={est['dr']:.4f} snips={est['snips']:.4f} "
                  f"ips={est['ips']:.4f} ess={est['ess']:.0f}{gate}",
                  flush=True)
        cells.append(cell)
    info["parity_ok"] = all(c.get("ope_ok", True) for c in cells)
    return cells, info
