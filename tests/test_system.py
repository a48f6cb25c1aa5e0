"""End-to-end behaviour tests for the paper's system (reward ordering on a
reduced stream — the full-scale reproduction is the `paper_table1` and
`fig2_beta_sweep` presets and examples/encoder_ablation.py)."""
import numpy as np
import pytest

from repro.core.baselines import FixedActionPolicy, RandomPolicy, RouteLLMBert
from repro.core.policy import NeuralUCBRouter
from repro.core.protocol import run_protocol, summarize
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim


@pytest.fixture(scope="module")
def results():
    env = RouterBenchSim(seed=0, n_samples=6000, n_slices=5)
    s, w = env.strong_weak_actions()
    rl = RouteLLMBert(s, w, env.x_emb.shape[1])
    b0 = env.slice_batch(0)
    rl.fit_offline(b0["x_emb"], b0["quality"][:, s], b0["quality"][:, w])
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pols = {
        "neuralucb": NeuralUCBRouter(cfg, seed=0, batch_size=128),
        "random": RandomPolicy(env.K, seed=1),
        "min-cost": FixedActionPolicy(env.min_cost_action()),
        "routellm-bert": rl,
    }
    res = run_protocol(env, pols, epochs=4, verbose=False)
    return env, summarize(res), res


def test_reward_ordering_matches_paper(results):
    """Fig. 2 ordering: NeuralUCB > min-cost >(~) RouteLLM-BERT > random."""
    _, summ, _ = results
    assert summ["neuralucb"]["avg_reward"] > summ["routellm-bert"]["avg_reward"]
    assert summ["neuralucb"]["avg_reward"] > summ["random"]["avg_reward"] + 0.15
    assert summ["min-cost"]["avg_reward"] > summ["routellm-bert"]["avg_reward"]
    assert summ["routellm-bert"]["avg_reward"] > summ["random"]["avg_reward"]


def test_cumulative_gap_widens(results):
    """Fig. 2b: the cumulative-reward gap over random grows with slices."""
    _, _, res = results
    gap = (np.asarray(res["neuralucb"]["cum_reward"])
           - np.asarray(res["random"]["cum_reward"]))
    assert gap[-1] > gap[1]


def test_cost_quality_tradeoff(results):
    """Fig. 4: NeuralUCB spends a fraction of max-quality's cost while
    keeping most of its selected quality."""
    env, summ, _ = results
    n = env.n
    aq = env.data["quality"].argmax(1)
    maxq_cost = env.data["cost"][np.arange(n), aq].mean()
    maxq_quality = env.data["quality"][np.arange(n), aq].mean()
    frac = summ["neuralucb"]["avg_cost"] / maxq_cost
    assert frac < 0.7, f"cost fraction {frac}"
    assert summ["neuralucb"]["avg_quality"] > 0.55 * maxq_quality
