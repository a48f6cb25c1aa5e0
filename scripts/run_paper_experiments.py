"""Paper experiment driver — a thin CLI over the declarative
ExperimentSpec API (``repro.experiments``, DESIGN.md §11).

Preset mode (the canonical interface — one spec, one artifact):

  PYTHONPATH=src python scripts/run_paper_experiments.py --list-presets
  PYTHONPATH=src python scripts/run_paper_experiments.py \
      --preset paper_table1                                # Table 1
  PYTHONPATH=src python scripts/run_paper_experiments.py \
      --preset fig2_beta_sweep                             # Fig. 2-4
  PYTHONPATH=src python scripts/run_paper_experiments.py \
      --preset scenario_suite --set seeds=0,1,2            # §9
  PYTHONPATH=src python scripts/run_paper_experiments.py \
      --preset ci_smoke                                    # CI, one call
  PYTHONPATH=src python scripts/run_paper_experiments.py \
      --preset policy_zoo \
      --set scenarios=stationary,price_shock,arm_outage    # §10

``--set key=value`` overrides address the spec's JSON form with dotted
paths (``data.n_samples=1500``, ``seeds=0,1``,
``policies.neuralucb.axes.beta=0.25,0.5,1.0``); unknown paths and
invalid values error loudly.

The pre-PR-5 flags are kept and MAPPED onto the same specs (e.g.
``--sweep-seeds 5 --betas 0.25 0.5 1.0 2.0`` builds the
``fig2_beta_sweep`` spec), so old invocations keep working — but every
run, flag-built or preset-built, compiles through
``repro.experiments.compile_spec`` into the minimal set of
single-dispatch ``run_policy_sweep`` calls and writes the
schema-versioned artifact (``--out``, default
``paper_experiments.json``).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

from repro.common.compilation import enable_compile_cache
from repro.experiments import (
    DataSpec,
    ExperimentResult,
    ExperimentSpec,
    ForgettingSpec,
    PolicySpec,
    TrainSpec,
    build_env,
    compile_spec,
    format_cells,
    make_preset,
    parse_override_value,
    preset_table,
    run_plan,
)

# Legacy flags that SELECT work; meaningless (and silently ignored
# before PR-5) next to --preset, so their presence there is an error.
# They all parse with default=None (explicitly passing a flag at its
# old default value must still be DETECTED, not silently shadowed by
# the preset); the legacy branch fills in the pre-PR-5 defaults.
_LEGACY_SELECTORS = ("sweep_seeds", "betas", "tau_gs", "cost_lambdas",
                     "scenario", "policies", "sweep_only",
                     "scenario_only", "policies_only", "gamma", "window",
                     "replay_rho", "random_seeds", "train_steps",
                     "epochs", "n_samples", "n_slices", "seed",
                     "cost_lambda")

_LEGACY_DEFAULTS = {"n_samples": 36_497, "n_slices": 20, "epochs": 5,
                    "seed": 0, "cost_lambda": 1.0, "sweep_seeds": 0,
                    "betas": [1.0], "tau_gs": [0.5]}


def _parse_sets(ap: argparse.ArgumentParser,
                pairs: List[str]) -> Dict[str, object]:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            ap.error(f"--set takes KEY=VALUE, got {pair!r}")
        key, _, val = pair.partition("=")
        out[key.strip()] = parse_override_value(val.strip())
    return out


def _data_overrides(args) -> Dict[str, object]:
    return {"data.seed": args.seed, "data.n_samples": args.n_samples,
            "data.n_slices": args.n_slices,
            "data.cost_lambda": args.cost_lambda}


def _train(args, batch_size: int = 256) -> TrainSpec:
    return TrainSpec(epochs=args.epochs, train_steps=args.train_steps,
                     batch_size=batch_size)


def _legacy_specs(ap: argparse.ArgumentParser,
                  args) -> List[Tuple[str, ExperimentSpec]]:
    """Map the pre-PR-5 flag surface onto specs — the compat layer.
    Invalid flag combinations (the ones the old driver silently
    ignored) error here."""
    # --gamma/--window/--replay-rho only feed the scenario suite's
    # forgetting variant; before PR-5 they were SILENTLY ignored
    # without --scenario (a sweep "with forgetting" quietly ran vanilla)
    forget_flags = [n for n, v in (("--gamma", args.gamma),
                                   ("--window", args.window),
                                   ("--replay-rho", args.replay_rho))
                    if v is not None]
    if forget_flags and not args.scenario:
        ap.error(f"{'/'.join(forget_flags)}: these flags configure the "
                 f"forgetting variant of the --scenario suite and have "
                 f"no effect without it; pass --scenario NAME... or "
                 f"drop them")
    if args.sweep_only and args.sweep_seeds <= 0:
        ap.error("--sweep-only requires --sweep-seeds > 0")
    if args.scenario_only and not args.scenario:
        ap.error("--scenario-only requires --scenario NAME...")
    if args.policies_only and not args.policies:
        ap.error("--policies-only requires --policies NAME...")
    if args.random_seeds is not None:
        print("note: --random-seeds is folded into the unified spec's "
              "seed axis; use --set seeds=0,1,... with --preset "
              "paper_table1 for a multi-seed table", file=sys.stderr)

    data = DataSpec(seed=args.seed, n_samples=args.n_samples,
                    n_slices=args.n_slices,
                    cost_lambda=args.cost_lambda)
    fg = ForgettingSpec(gamma=1.0 if args.gamma is None else args.gamma,
                        window=0 if args.window is None else args.window,
                        replay_rho=(0.4 if args.replay_rho is None
                                    else args.replay_rho))
    specs: List[Tuple[str, ExperimentSpec]] = []
    only = args.sweep_only or args.scenario_only or args.policies_only
    if not only:
        specs.append(("summary", make_preset(
            "paper_table1",
            {**_data_overrides(args), "seeds": [args.seed],
             "train.epochs": args.epochs,
             "train.train_steps": args.train_steps})))
    if args.sweep_seeds > 0 and not args.policies_only \
            and not args.scenario_only:
        lambdas = tuple(None if l < 0 else l
                        for l in (args.cost_lambdas or [-1.0]))
        specs.append(("sweep", ExperimentSpec(
            name="fig2_beta_sweep", data=data,
            policies=(PolicySpec("neuralucb", axes=(
                ("beta", tuple(args.betas)),
                ("tau_g", tuple(args.tau_gs)),
                ("cost_lambda", lambdas))),),
            seeds=tuple(range(args.sweep_seeds)),
            train=_train(args))))
    if args.scenario and not args.policies_only:
        specs.append(("scenarios", ExperimentSpec(
            name="scenario_suite", data=data,
            policies=(PolicySpec("neuralucb"),
                      PolicySpec("neuralucb", name="neuralucb-forget",
                                 forgetting=fg),
                      PolicySpec("greedy"), PolicySpec("random")),
            scenarios=tuple(args.scenario),
            seeds=(args.seed,), train=_train(args))))
    if args.policies:
        specs.append(("policy_zoo", ExperimentSpec(
            name="policy_zoo", data=data,
            policies=tuple(PolicySpec(p) for p in args.policies),
            scenarios=(tuple(args.scenario) if args.scenario
                       else (None,)),
            seeds=tuple(range(max(1, args.sweep_seeds))),
            train=_train(args))))
    return specs


def _print_result(section: str, result: ExperimentResult,
                  oracle: Optional[float]) -> None:
    spec = result.spec
    m = result.manifest
    print(f"\n== {section} ({spec.name}: {len(spec.seeds)} seed"
          f"{'s' if len(spec.seeds) != 1 else ''}, "
          f"{m['n_dispatches']} dispatch"
          f"{'es' if m['n_dispatches'] != 1 else ''}, "
          f"{m['wall_s']:.1f}s) ==")
    for scen in result.scenario_names():
        if len(result.scenario_names()) > 1:
            print(f"\n-- scenario: {scen} --")
        print(format_cells(result.cells_for(scen)))
    if oracle is not None:
        print(f"{'oracle (ref)':<18}{'':>9}{oracle:>16.4f}")
    pt = m.get("pretrain")
    if pt:
        hits = sum(v["cache_hit"] for v in pt["labels"].values())
        secs = sum(v["pretrain_s"] for v in pt["labels"].values())
        print(f"\npretrain: {len(pt['labels'])} warm label"
              f"{'s' if len(pt['labels']) != 1 else ''} on a "
              f"{pt['corpus_size']}-row {pt['behavior']!r} corpus "
              f"({hits} cache hit{'s' if hits != 1 else ''}, "
              f"{secs:.1f}s)")
    ope = m.get("ope")
    if ope:
        print(f"\nope: {len(ope['targets'])} targets scored from one "
              f"{ope['behavior']!r} log (n={ope['n']}) -> parity "
              f"{'ok' if ope['parity_ok'] else 'FAIL'}")
        for c in result.cells_for("offline"):
            e = c["ope"]
            pin = ""
            if "ope_ok" in c:
                pin = (f"  vs on-policy {c['onpolicy_value']:.4f} "
                       f"[{'ok' if c['ope_ok'] else 'FAIL'}]")
            print(f"  {c['policy']:<18} dr={e['dr']:.4f} "
                  f"snips={e['snips']:.4f} ips={e['ips']:.4f} "
                  f"ess={e['ess']:.0f}{pin}")


def _table_checks(result: ExperimentResult) -> bool:
    """The old summary-table acceptance: NeuralUCB beats random and is
    within 10% of the max-quality arm's reward."""
    try:
        nucb = result.cell("neuralucb")
        rand = result.cell("random")
        maxq = result.cell("max_quality")
    except KeyError:
        return result.ok
    return (result.ok
            and nucb["avg_reward_mean"] > rand["avg_reward_mean"]
            and nucb["avg_reward_mean"]
            > maxq["avg_reward_mean"] * 0.9)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--preset", default=None,
                    help="registered ExperimentSpec preset "
                         "(--list-presets shows all)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE", dest="sets",
                    help="dotted-path spec override, e.g. "
                         "data.n_samples=1500 or "
                         "policies.neuralucb.axes.beta=0.5,1.0")
    ap.add_argument("--list-presets", action="store_true")
    # ---- legacy flags (mapped onto specs; defaults resolved late) ----
    ap.add_argument("--n-samples", type=int, default=None)
    ap.add_argument("--n-slices", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--random-seeds", type=int, default=None,
                    help="deprecated: folded into the spec seed axis")
    ap.add_argument("--cost-lambda", type=float, default=None)
    ap.add_argument("--sweep-seeds", type=int, default=None,
                    help="NeuralUCB sweep seeds; 0 disables the sweep")
    ap.add_argument("--betas", type=float, nargs="+", default=None)
    ap.add_argument("--tau-gs", type=float, nargs="+", default=None)
    ap.add_argument("--cost-lambdas", type=float, nargs="+", default=None,
                    help="cost_lambda grid; negative = env's own table")
    ap.add_argument("--train-steps", type=int, default=None)
    ap.add_argument("--sweep-only", action="store_true")
    ap.add_argument("--scenario", nargs="+", default=None)
    ap.add_argument("--scenario-only", action="store_true")
    ap.add_argument("--policies", nargs="+", default=None)
    ap.add_argument("--policies-only", action="store_true")
    ap.add_argument("--gamma", type=float, default=None,
                    help="A^-1 rebuild discount for the scenario "
                         "suite's forgetting variant (requires "
                         "--scenario)")
    ap.add_argument("--window", type=int, default=None,
                    help="A^-1 sliding window in slices (requires "
                         "--scenario)")
    ap.add_argument("--replay-rho", type=float, default=None,
                    help="recency weight for replay sampling (requires "
                         "--scenario; suite default 0.4)")
    ap.add_argument("--out", default="paper_experiments.json")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.list_presets:
        for name, desc in preset_table():
            print(f"{name:<18} {desc}")
        return 0

    enable_compile_cache()
    if args.preset is not None:
        # --preset takes its configuration from --set alone; a legacy
        # flag next to it would be silently shadowed by the spec (all
        # legacy flags parse with default=None / False, so even one
        # passed at its old default value is detected here)
        stray = [n for n in _LEGACY_SELECTORS
                 if getattr(args, n) not in (None, False)]
        if stray:
            flags = ", ".join("--" + n.replace("_", "-") for n in stray)
            ap.error(f"{flags} cannot be combined with --preset; use "
                     f"--set key=value overrides instead")
        try:
            spec = make_preset(args.preset, _parse_sets(ap, args.sets))
        except (KeyError, ValueError) as e:
            ap.error(str(e))
        sections = [(args.preset, spec)]
    else:
        if args.sets:
            ap.error("--set requires --preset")
        for name, default in _LEGACY_DEFAULTS.items():
            if getattr(args, name) is None:
                setattr(args, name, default)
        sections = _legacy_specs(ap, args)
        if not sections:
            ap.error("nothing to run")

    out: Dict[str, object] = {}
    ok = True
    # legacy multi-section runs share one DataSpec — build the replay
    # env once and inject it into every section's compile
    shared_data = shared_henv = shared_denv = None
    for section, spec in sections:
        try:
            if spec.armpool is not None:
                # a physical pool compiles its own env from the pool
                # tables — never the shared replay env
                plan = compile_spec(spec)
            else:
                if spec.data != shared_data:
                    shared_henv, shared_denv = build_env(spec.data)
                    shared_data = spec.data
                plan = compile_spec(spec, env=shared_denv,
                                    host_env=shared_henv)
        except ValueError as e:
            ap.error(str(e))
        result = run_plan(plan, verbose=not args.quiet)
        oracle = None
        if plan.host_env is not None and spec.scenarios == (None,):
            oracle = float(plan.host_env.reward_table.max(axis=1).mean())
            result.manifest["oracle_reward"] = oracle
        if not args.quiet:
            _print_result(section, result, oracle)
        if spec.name == "paper_table1":
            ok = ok and _table_checks(result)
            try:
                frac = (result.cell("neuralucb")["avg_cost_mean"]
                        / result.cell("max_quality")["avg_cost_mean"])
                result.manifest[
                    "neuralucb_cost_fraction_of_max_quality"] = frac
                if not args.quiet:
                    print(f"\nneuralucb cost = {100 * frac:.1f}% of "
                          f"max-quality-arm (paper: ~33%)")
            except (KeyError, ZeroDivisionError):
                pass
        else:
            ok = ok and result.ok
        out[section] = result.to_json()

    doc = next(iter(out.values())) if len(out) == 1 else out
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, default=float)
    print(f"\nwrote {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
