"""Paper Figure 2 (+Fig. 4 data): full-protocol reward comparison over the
complete 36,497-sample stream, 20 slices — NeuralUCB vs random / min-cost /
RouteLLM-BERT (+ LinUCB as a beyond-paper partial-feedback reference, and
the max-quality reference row)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import cached
from repro.core.baselines import (
    FixedActionPolicy,
    LinUCB,
    RandomPolicy,
    RouteLLMBert,
)
from repro.core.policy import NeuralUCBRouter
from repro.core.protocol import run_protocol, summarize
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim


def _run(n_samples=36_497, n_slices=20, epochs=5):
    env = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    s, w = env.strong_weak_actions()
    rl = RouteLLMBert(s, w, env.x_emb.shape[1])
    b0 = env.slice_batch(0)
    rl.fit_offline(b0["x_emb"], b0["quality"][:, s], b0["quality"][:, w])
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K,
                           d_hidden=384, d_action=32)
    pols = {
        "neuralucb": NeuralUCBRouter(cfg, seed=0),
        "random": RandomPolicy(env.K, seed=1),
        "min-cost": FixedActionPolicy(env.min_cost_action()),
        "max-quality-arm": FixedActionPolicy(env.max_quality_action()),
        "routellm-bert": rl,
        "linucb": LinUCB(env.K, env.x_emb.shape[1]),
    }
    res = run_protocol(env, pols, epochs=epochs, verbose=True)
    summ = summarize(res)

    n = env.n
    aq = env.data["quality"].argmax(1)
    maxq = {
        "avg_reward": float(env.reward_table[np.arange(n), aq].mean()),
        "avg_cost": float(env.data["cost"][np.arange(n), aq].mean()),
        "avg_quality": float(env.data["quality"][np.arange(n), aq].mean()),
    }
    oracle = float(env.reward_table.max(1).mean())
    return {
        "summary": summ,
        "per_slice": {k: {kk: vv for kk, vv in v.items()
                          if kk != "action_hist"}
                      for k, v in res.items()},
        "max_quality_reference": maxq,
        "oracle_reward": oracle,
    }


def run(refresh: bool = False):
    out = cached("rewards_full", _run, refresh)
    rows = [("bench_rewards/policy", "avg_reward", "avg_cost", "avg_quality")]
    for name, s in out["summary"].items():
        rows.append((f"fig2_{name}", round(s["avg_reward"], 4),
                     round(s["avg_cost"], 5), round(s["avg_quality"], 4)))
    mq = out["max_quality_reference"]
    rows.append(("fig4_max_quality_ref", round(mq["avg_reward"], 4),
                 round(mq["avg_cost"], 5), round(mq["avg_quality"], 4)))
    rows.append(("oracle", round(out["oracle_reward"], 4), "", ""))
    nucb_cost_frac = out["summary"]["neuralucb"]["avg_cost"] / mq["avg_cost"]
    rows.append(("fig4_neuralucb_cost_fraction", round(nucb_cost_frac, 4),
                 "", ""))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit_csv
    from repro.common.compilation import enable_compile_cache

    enable_compile_cache()
    emit_csv(run())
