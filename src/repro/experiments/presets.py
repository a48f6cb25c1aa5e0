"""Preset experiment specs (DESIGN.md §11.4).

Every named workload the repo runs — the paper's Table 1, the
Figures 2-4 β sweep, the non-stationary scenario suite, the policy-zoo
exploration comparison, the CI smoke, and the protocol-bench sweep
shapes — is a preset here: a function returning an
:class:`ExperimentSpec`. The driver exposes them as
``run_paper_experiments.py --preset NAME [--set key=value ...]``; the
benches and tests build the SAME specs, so a preset edit propagates to
every consumer at once.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.experiments.spec import (
    ArmPoolSpec,
    DataSpec,
    ExperimentSpec,
    ForgettingSpec,
    OPESpec,
    PolicySpec,
    PretrainSpec,
    ServingSpec,
    SummarizeSpec,
    TrainSpec,
    apply_overrides,
)

PRESETS: Dict[str, Callable[[], ExperimentSpec]] = {}


def register_preset(name: str):
    def deco(fn: Callable[[], ExperimentSpec]):
        PRESETS[name] = fn
        return fn
    return deco


def make_preset(name: str,
                overrides: Optional[Dict[str, Any]] = None
                ) -> ExperimentSpec:
    """Build a registered preset, optionally with ``--set``-style
    dotted-path overrides (``repro.experiments.spec.apply_overrides``).
    """
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; registered: "
                       f"{sorted(PRESETS)}")
    spec = PRESETS[name]()
    if overrides:
        spec = apply_overrides(spec, overrides)
    return spec


def preset_table() -> List[Tuple[str, str]]:
    """(name, one-line description) rows for ``--list-presets`` and the
    README table."""
    rows = []
    for name in sorted(PRESETS):
        doc = (PRESETS[name].__doc__ or "").strip().splitlines()
        rows.append((name, doc[0] if doc else ""))
    return rows


_BASELINES = (PolicySpec("random"), PolicySpec("min_cost"),
              PolicySpec("max_quality"), PolicySpec("greedy"))


@register_preset("paper_table1")
def _paper_table1() -> ExperimentSpec:
    """Paper Table 1: NeuralUCB vs. the §4.1 baselines on the full
    replay stream (reward / cost / quality summary)."""
    return ExperimentSpec(
        name="paper_table1",
        policies=(PolicySpec("neuralucb"),) + _BASELINES)


@register_preset("fig2_beta_sweep")
def _fig2_beta_sweep() -> ExperimentSpec:
    """Figures 2-4: the seeds × β exploration grid as ONE vmapped,
    device-sharded scan dispatch."""
    return ExperimentSpec(
        name="fig2_beta_sweep",
        policies=(PolicySpec("neuralucb",
                             axes=(("beta", (0.25, 0.5, 1.0, 2.0)),)),),
        seeds=(0, 1, 2, 3, 4))


@register_preset("scenario_suite")
def _scenario_suite() -> ExperimentSpec:
    """Non-stationary suite (DESIGN.md §9): vanilla + forgetting
    NeuralUCB vs. greedy/random under price shocks and outages, with
    dynamic-oracle regret."""
    return ExperimentSpec(
        name="scenario_suite",
        policies=(PolicySpec("neuralucb"),
                  PolicySpec("neuralucb", name="neuralucb-forget",
                             forgetting=ForgettingSpec(replay_rho=0.4)),
                  PolicySpec("greedy"), PolicySpec("random")),
        scenarios=("price_shock", "arm_outage"))


@register_preset("policy_zoo")
def _policy_zoo() -> ExperimentSpec:
    """Exploration-strategy comparison (DESIGN.md §10): the whole zoo ×
    seeds, stationary and under a price shock, one dispatch per
    scenario."""
    return ExperimentSpec(
        name="policy_zoo",
        policies=(PolicySpec("neuralucb"), PolicySpec("linucb"),
                  PolicySpec("neural_ts"), PolicySpec("eps_greedy"),
                  PolicySpec("boltzmann")),
        scenarios=(None, "price_shock"),
        seeds=(0, 1, 2))


@register_preset("ci_smoke")
def _ci_smoke() -> ExperimentSpec:
    """CI: the sweep + scenario + cross-policy smokes as one tiny spec
    (β grid, forgetting variant, zoo members, three scenarios)."""
    return ExperimentSpec(
        name="ci_smoke",
        data=DataSpec(n_samples=1500, n_slices=3),
        policies=(PolicySpec("neuralucb",
                             axes=(("beta", (0.5, 1.0)),)),
                  PolicySpec("neuralucb", name="neuralucb-forget",
                             forgetting=ForgettingSpec(replay_rho=0.4)),
                  PolicySpec("linucb"), PolicySpec("neural_ts"),
                  PolicySpec("eps_greedy")),
        scenarios=(None, "price_shock", "arm_outage"),
        seeds=(0, 1),
        train=TrainSpec(train_steps=32, batch_size=64),
        summarize=SummarizeSpec(curves=False))


@register_preset("serving_storm")
def _serving_storm() -> ExperimentSpec:
    """Serving storm (DESIGN.md §12): flash-crowd traffic through the
    async engine with two cascading arm outages and an injected decide
    fault — gates on zero lost requests, the p99 decide-latency bound,
    and the shed ceiling. CI shrinks it via --set serving.requests=...
    serving.waves=...; the full size is the acceptance run."""
    return ExperimentSpec(
        name="serving_storm",
        data=DataSpec(n_samples=6000, n_slices=8),
        policies=(PolicySpec("neuralucb"),),
        seeds=(0,),
        train=TrainSpec(train_steps=32, batch_size=64),
        summarize=SummarizeSpec(curves=False),
        serving=ServingSpec(
            requests=20_000, waves=40, pattern="flash_crowd",
            decide_batch=256, queue_capacity=4096,
            outages=((0, 12, 28), (1, 20, 36)),
            fail_decide_calls=(5,),
            train_every=8, p99_decide_ms=250.0,
            max_shed_fraction=0.02, require_zero_lost=True))


@register_preset("physical_pool")
def _physical_pool() -> ExperimentSpec:
    """Physical arm pool (DESIGN.md §16): 8 real model configs costed
    through the roofline on tpu-v5e feed ONE spec that runs BOTH the
    replay policy sweep and a semi-real serving storm — mamba2-130m
    executes real jitted decode steps, the large arms sleep their
    roofline step time. CI shrinks it via --set serving.requests=...
    data.n_samples=...; calibration stays off (calibrate=true times
    real full-size decode steps — the bench's job)."""
    return ExperimentSpec(
        name="physical_pool",
        data=DataSpec(n_samples=6000, n_slices=8),
        policies=(PolicySpec("neuralucb"),),
        seeds=(0,),
        train=TrainSpec(train_steps=32, batch_size=64),
        summarize=SummarizeSpec(curves=False),
        armpool=ArmPoolSpec(
            arms=("mamba2_130m", "llama3_2_3b", "gemma3_4b",
                  "granite_moe_1b_a400m", "mistral_nemo_12b",
                  "qwen3_moe_30b_a3b", "mistral_large_123b",
                  "jamba_1_5_large_398b"),
            hardware="tpu-v5e", decode_batch=8, context=2048,
            calibrate=False, reduced_decode=True, max_new=4),
        serving=ServingSpec(
            requests=4000, waves=16, pattern="flash_crowd",
            decide_batch=128, serve_batch=64, queue_capacity=4096,
            train_every=4, p99_decide_ms=500.0,
            max_shed_fraction=0.05, require_zero_lost=True))


@register_preset("offline_online")
def _offline_online() -> ExperimentSpec:
    """Phased lifecycle (DESIGN.md §13): pretrain on a logged corpus,
    then stream online — warm vs cold start as a sweepable axis for the
    neural + supervised zoo members, cold baselines riding along. CI
    shrinks it via --set data.n_samples=... pretrain.corpus_size=...;
    the full size is the acceptance run."""
    return ExperimentSpec(
        name="offline_online",
        policies=(PolicySpec("neuralucb"), PolicySpec("sup_winrate"),
                  PolicySpec("linucb"), PolicySpec("greedy"),
                  PolicySpec("random")),
        seeds=(0, 1),
        pretrain=PretrainSpec(corpus_size=20_000, behavior="random",
                              steps=512, warm_start=(True, False)))


@register_preset("ope_selection")
def _ope_selection() -> ExperimentSpec:
    """Off-policy router selection (DESIGN.md §13.4): one eps-greedy
    behavior log scored against four targets via IPS/SNIPS/DR — the
    supervised router fit purely from the log — with the deterministic
    min-cost target's DR estimate parity-pinned against its on-policy
    replay run."""
    return ExperimentSpec(
        name="ope_selection",
        policies=(PolicySpec("eps_greedy"),),
        seeds=(0,),
        summarize=SummarizeSpec(curves=False),
        ope=OPESpec(behavior="eps_greedy",
                    targets=("min_cost", "greedy", "sup_winrate",
                             "random"),
                    parity=("min_cost",), parity_tol=0.05))


@register_preset("bench_nucb_sweep")
def _bench_nucb_sweep() -> ExperimentSpec:
    """Unused: nothing runs this preset since its CPU benchmark section
    went. Kept only because ``test_pre_pr10_spec_hashes_unchanged`` pins
    its spec hash (ROADMAP C8)."""
    return ExperimentSpec(
        name="bench_nucb_sweep",
        data=DataSpec(n_samples=1200, n_slices=32),
        policies=(PolicySpec("neuralucb"),),
        seeds=(0, 1, 2, 3),
        train=TrainSpec(train_steps=32, batch_size=32),
        summarize=SummarizeSpec(curves=False))


@register_preset("bench_zoo_sweep")
def _bench_zoo_sweep() -> ExperimentSpec:
    """Unused: nothing runs this preset since its CPU benchmark section
    went. Kept only because ``test_pre_pr10_spec_hashes_unchanged`` pins
    its spec hash (ROADMAP C8)."""
    return ExperimentSpec(
        name="bench_zoo_sweep",
        data=DataSpec(n_samples=1200, n_slices=8),
        policies=(PolicySpec("neuralucb"), PolicySpec("linucb"),
                  PolicySpec("neural_ts"), PolicySpec("eps_greedy"),
                  PolicySpec("boltzmann")),
        seeds=(0, 1, 2, 3),
        train=TrainSpec(train_steps=32, batch_size=32),
        summarize=SummarizeSpec(curves=False))
