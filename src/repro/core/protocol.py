"""Simulated online protocol (paper Algorithm 1).

20 slices processed sequentially; per slice: DECIDE every sample, UPDATE
the buffer + shared A^-1, TRAIN UtilityNet for E replay epochs, REBUILD
A^-1. Metrics tracked per slice for every policy: average reward,
cumulative reward, cost, selected quality, action rates — everything the
paper's Figures 2-4 plot.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro.core.policy import NeuralUCBRouter
from repro.data.routerbench import RouterBenchSim


def run_protocol(env: RouterBenchSim, policies: Dict[str, object], *,
                 epochs: int = 5, verbose: bool = True,
                 max_slices: Optional[int] = None) -> Dict[str, Dict]:
    """Run every policy over the same slice stream (offline replay gives all
    policies identical queries and per-action feedback tables).

    Returns {policy: {"avg_reward": [...], "cum_reward": [...],
                      "avg_cost": [...], "avg_quality": [...],
                      "action_hist": (T, K), "wall_s": [...]}}.
    Only host runners (this one and the host-stepped
    ``repro.sim.engine`` runner) carry ``wall_s``, each slice's host
    wall; the device scans and sweeps time no slice on its own and leave
    it out.
    """
    T = env.n_slices if max_slices is None else min(env.n_slices, max_slices)
    K = env.K
    results = {
        name: {"avg_reward": [], "cum_reward": [], "avg_cost": [],
               "avg_quality": [], "action_hist": np.zeros((T, K)),
               "wall_s": []}
        for name in policies
    }
    cum = {name: 0.0 for name in policies}

    for t in range(T):
        batch = env.slice_batch(t)
        n = len(batch["idx"])
        for name, pol in policies.items():
            t0 = time.time()
            if isinstance(pol, NeuralUCBRouter):
                dec = pol.decide(batch["x_emb"], batch["x_feat"],
                                 batch["domain"])
                a = dec["action"]
                r = batch["reward"][np.arange(n), a]
                pol.update(batch["x_emb"], batch["x_feat"], batch["domain"],
                           dec, r)
                pol.end_slice(epochs)
            else:
                a = pol.decide(batch["x_emb"], batch["x_feat"],
                               batch["domain"])
                r = batch["reward"][np.arange(n), a]
                if hasattr(pol, "update"):
                    pol.update(batch["x_emb"], batch["x_feat"],
                               batch["domain"], a, r)
                pol.end_slice()
            q = batch["quality"][np.arange(n), a]
            c = batch["cost"][np.arange(n), a]
            cum[name] += float(r.sum())
            res = results[name]
            res["avg_reward"].append(float(r.mean()))
            res["cum_reward"].append(cum[name])
            res["avg_cost"].append(float(c.mean()))
            res["avg_quality"].append(float(q.mean()))
            res["action_hist"][t] = np.bincount(a, minlength=K)
            res["wall_s"].append(time.time() - t0)
        if verbose:
            line = " ".join(
                f"{name}={results[name]['avg_reward'][-1]:.3f}"
                for name in policies)
            print(f"[slice {t + 1:2d}/{T}] avg_reward: {line}", flush=True)
    return results


def summarize(results: Dict[str, Dict], skip_first: bool = True) -> Dict:
    """Paper-style summary: slice-1 is warm-start-affected and excluded
    from formal comparison (paper §4.2).

    When a result carries the engine's per-slice ``oracle_avg_reward``
    (the best AVAILABLE arm under that slice's effective tables —
    DESIGN.md §9.3), the summary adds dynamic-regret accounting:
    ``dynamic_regret`` is the summed per-slice average shortfall against
    the dynamic oracle over the compared slices, so stationary and
    drifting runs report directly comparable numbers. All values are
    plain Python floats (JSON-serializable)."""
    out = {}
    for name, res in results.items():
        s = 1 if skip_first and len(res["avg_reward"]) > 1 else 0
        summ = {
            "avg_reward": float(np.mean(res["avg_reward"][s:])),
            "final_cum_reward": float(res["cum_reward"][-1]),
            "avg_cost": float(np.mean(res["avg_cost"][s:])),
            "avg_quality": float(np.mean(res["avg_quality"][s:])),
        }
        if "oracle_avg_reward" in res:
            o = np.asarray(res["oracle_avg_reward"][s:], np.float64)
            r = np.asarray(res["avg_reward"][s:], np.float64)
            summ["oracle_avg_reward"] = float(o.mean())
            summ["dynamic_regret"] = float(np.sum(o - r))
            summ["dynamic_regret_per_slice"] = float(np.mean(o - r))
        out[name] = summ
    return out


def summarize_sweep(sweep: Dict, skip_first: bool = True) -> List[Dict]:
    """Summarize ONE policy's grid-annotated sweep (the unified
    ``repro.sim.run_policy_sweep`` schema: metric leaves shaped
    (G, n_seeds, T, ...) plus a ``grid`` dict of (G,) hyper arrays).

    Returns a list of G per-grid-point summaries, each with the point's
    hyper values and the seed-aggregated mean ± std of the standard
    paper metrics (slice 1 excluded per §4.2, as in :func:`summarize`).
    Works for every registered policy — baselines have G=1 and an empty
    grid dict. Values are plain Python floats (JSON-serializable)."""
    r = np.asarray(sweep["avg_reward"], np.float64)       # (G, n_seeds, T)
    G, _, T = r.shape
    s0 = 1 if skip_first and T > 1 else 0
    grid = sweep.get("grid", {})
    points = []
    for g in range(G):
        p = {f: float(np.asarray(v).reshape(-1)[g]) for f, v in grid.items()}
        for key in ("avg_reward", "avg_cost", "avg_quality",
                    "oracle_avg_reward", "mean_logp"):
            if key in sweep:
                per_seed = np.asarray(sweep[key], np.float64)[g, :, s0:]
                p[f"{key}_mean"] = float(per_seed.mean(axis=1).mean())
                p[f"{key}_std"] = float(per_seed.mean(axis=1).std())
        if "oracle_avg_reward" in sweep:
            o = np.asarray(sweep["oracle_avg_reward"], np.float64)[g, :, s0:]
            p["dynamic_regret_mean"] = float((o - r[g, :, s0:]).sum(axis=1)
                                             .mean())
        if "sum_reward" in sweep:
            cum = np.asarray(sweep["sum_reward"], np.float64)[g].sum(axis=1)
            p["final_cum_reward_mean"] = float(cum.mean())
        points.append(p)
    return points


# ------------------------------- off-policy evaluation (DESIGN.md §13.4) --
def estimate_offline(logged, target_probs: np.ndarray, *,
                     qhat: Optional[np.ndarray] = None,
                     clip: Optional[float] = None) -> Dict[str, float]:
    """Counterfactual value estimates of a TARGET policy from one logged
    run (Causal LLM Routing, PAPERS.md): score a policy that never ran.

    ``logged`` is a :class:`repro.data.logged.LoggedInteractions` from a
    propensity-aware producer; ``target_probs`` (n, K) is the target
    policy's action distribution per logged context (rows sum to 1);
    ``qhat`` (n, K), when given, is a direct-method reward model enabling
    the doubly-robust estimator. Returns per-request value estimates:

    * ``ips``   — inverse-propensity scoring, mean(w_i * r_i) with
      w_i = pi_t(a_i | x_i) / pi_b(a_i | x_i). Unbiased, high variance.
    * ``snips`` — self-normalized IPS, sum(w r) / sum(w). Biased
      O(1/n), far lower variance; invariant to propensity scale.
    * ``dm``    — direct method, mean_i sum_k pi_t(k|x_i) qhat[i, k]
      (NaN without ``qhat``). Biased by the reward model.
    * ``dr``    — doubly robust, dm + mean(w (r - qhat[i, a_i])).
      Unbiased when EITHER the propensities or qhat are right.
    * ``ess``   — Kish effective sample size of the weights, the
      reliability diagnostic (ess << n means the log barely covers the
      target).

    ``clip`` truncates importance weights at that value (bias-variance
    knob; SNIPS/DR use the clipped weights too). Fails loudly on logs
    without propensities — a producer that cannot state pi_b cannot feed
    counterfactual estimates (satellite b)."""
    if not logged.has_propensities:
        raise ValueError(
            f"estimate_offline: log from {logged.behavior!r} carries no "
            "propensities (logp=None) — only propensity-aware producers "
            "(record_log runs, replay_corpus, serving to_logged) can "
            "feed counterfactual estimates")
    n = logged.n
    tp = np.asarray(target_probs, np.float64)
    if tp.shape != (n, logged.num_actions):
        raise ValueError(
            f"estimate_offline: target_probs shape {tp.shape} != "
            f"(n={n}, K={logged.num_actions})")
    r = np.asarray(logged.reward, np.float64)
    a = np.asarray(logged.action)
    rows = np.arange(n)
    pb = np.exp(np.asarray(logged.logp, np.float64))
    w = tp[rows, a] / np.maximum(pb, 1e-12)
    if clip is not None:
        w = np.minimum(w, float(clip))
    out = {
        "ips": float((w * r).mean()),
        "snips": float((w * r).sum() / np.maximum(w.sum(), 1e-12)),
        "ess": float(w.sum() ** 2 / np.maximum((w ** 2).sum(), 1e-12)),
        "mean_w": float(w.mean()),
        "n": int(n),
    }
    if qhat is None:
        out["dm"] = float("nan")
        out["dr"] = float("nan")
    else:
        q = np.asarray(qhat, np.float64)
        if q.shape != (n, logged.num_actions):
            raise ValueError(
                f"estimate_offline: qhat shape {q.shape} != "
                f"(n={n}, K={logged.num_actions})")
        dm = (tp * q).sum(axis=1).mean()
        out["dm"] = float(dm)
        out["dr"] = float(dm + (w * (r - q[rows, a])).mean())
    return out
