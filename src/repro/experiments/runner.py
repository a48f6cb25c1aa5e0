"""``run(plan) -> ExperimentResult`` (DESIGN.md §11.3).

Executes a compiled :class:`ExperimentPlan` — one
``repro.sim.run_policy_sweep`` dispatch per :class:`SweepCall` — and
shapes the outputs into a schema-versioned artifact: one CELL per
(scenario × policy × grid point) with the seed-aggregated paper metrics
(``repro.core.protocol.summarize_sweep``), optional per-slice curves
and per-seed values, and a MANIFEST recording the spec hash, backend /
device topology, resolved train schedule, dispatch count, and
compile/run wall time. The artifact is plain JSON: what the driver
writes, what CI uploads, and what the parity tests diff.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

import numpy as np

import jax

from repro.core.protocol import summarize_sweep
from repro.experiments.compiler import ExperimentPlan
from repro.experiments.spec import (
    ExperimentSpec,
    spec_hash,
    spec_to_json,
)
from repro.sim import run_policy_sweep

RESULT_SCHEMA_VERSION = "experiment-result-v1"

_STATIONARY = "stationary"


@dataclasses.dataclass
class ExperimentResult:
    """Schema-versioned run artifact. ``cells`` is the flat list of
    per-(scenario, policy, grid-point) summaries; ``manifest`` the
    provenance block. ``ok`` is the driver's exit-status predicate:
    every cell's headline metrics came back finite."""

    spec: ExperimentSpec
    manifest: Dict[str, Any]
    cells: List[Dict[str, Any]]

    @property
    def ok(self) -> bool:
        return all(np.isfinite(c["avg_reward_mean"])
                   and c.get("serving_ok", True)
                   and c.get("ope_ok", True) for c in self.cells)

    def scenario_names(self) -> List[str]:
        seen: List[str] = []
        for c in self.cells:
            if c["scenario"] not in seen:
                seen.append(c["scenario"])
        return seen

    def cells_for(self, scenario: str) -> List[Dict[str, Any]]:
        return [c for c in self.cells if c["scenario"] == scenario]

    def cell(self, policy: str, scenario: str = _STATIONARY,
             **point) -> Dict[str, Any]:
        """The unique cell for (policy, scenario[, axis values]) —
        raises if the selector is ambiguous or matches nothing."""
        hits = [c for c in self.cells
                if c["policy"] == policy and c["scenario"] == scenario
                and all(c["point"].get(k) == v for k, v in point.items())]
        if len(hits) != 1:
            raise KeyError(f"cell(policy={policy!r}, "
                           f"scenario={scenario!r}, {point}) matched "
                           f"{len(hits)} cells")
        return hits[0]

    def to_json(self) -> Dict[str, Any]:
        return {"schema": RESULT_SCHEMA_VERSION,
                "spec": spec_to_json(self.spec),
                "manifest": self.manifest,
                "cells": self.cells}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, default=float)


def _run_serving_cell(plan: ExperimentPlan, *,
                      pretrained_state: Any = None,
                      verbose: bool = False) -> Dict[str, Any]:
    """Serving-storm mode: drive the plan's single resolved policy
    through the async engine (DESIGN.md §12) and shape the storm
    metrics into one artifact cell. ``serving_ok`` applies the spec's
    gates (zero lost requests, p99 decide-latency bound, shed ceiling)
    — it feeds :attr:`ExperimentResult.ok`, the CI exit status."""
    from repro.serving import DevicePolicyRouter, run_storm
    from repro.sim.engine import _chunks_for, _tables

    spec = plan.spec
    sv = spec.serving
    label, pol, hyp, fcfg = plan.serving_policy
    chunks = _chunks_for(plan.env, pol, plan.train_steps,
                         spec.train.epochs, spec.train.batch_size)
    capacity = min(1024, -(-sv.requests // sv.decide_batch) + sv.waves)
    router = DevicePolicyRouter(
        pol, hyp, _tables(plan.env), seed=spec.seeds[0],
        slice_width=sv.decide_batch, capacity_slices=capacity,
        batch_size=spec.train.batch_size, train_chunks=chunks, fcfg=fcfg,
        pretrained_state=pretrained_state,
        max_train_lag=sv.max_train_lag)
    engines = None
    engine_info: Dict[str, Any] = {}
    max_new = 8
    if plan.pool is not None:
        # semi-real serve stage: small arms run REAL jitted decode
        # steps, large arms sleep their roofline step time
        from repro.armpool import build_arm_engines, engine_decode_steps
        engines, engine_info = build_arm_engines(
            plan.pool, spec.armpool, max_batch=sv.serve_batch)
        max_new = spec.armpool.max_new
    metrics = run_storm(
        plan.env, router, requests=sv.requests, waves=sv.waves,
        pattern=sv.pattern, outages=sv.outages,
        queue_capacity=sv.queue_capacity, decide_batch=sv.decide_batch,
        serve_batch=sv.serve_batch,
        fail_decide_calls=sv.fail_decide_calls,
        train_every=sv.train_every, seed=sv.seed, engines=engines,
        max_new=max_new)
    if engines is not None:
        metrics["decode_steps"] = engine_decode_steps(engines)

    gates: Dict[str, bool] = {}
    if sv.require_zero_lost:
        gates["zero_lost"] = metrics["lost_requests"] == 0
    if sv.p99_decide_ms is not None:
        gates["p99_decide"] = \
            metrics["decide_p99_us"] / 1000.0 <= sv.p99_decide_ms
    gates["shed_fraction"] = \
        metrics["shed"] <= sv.max_shed_fraction * sv.requests
    ok = all(gates.values())
    if verbose:
        print(f"[{spec.name}] serving/{label}: "
              f"{metrics['requests_per_s']:.0f} req/s, "
              f"p99 decide {metrics['decide_p99_us'] / 1000:.2f} ms, "
              f"shed {metrics['shed']}, lost "
              f"{metrics['lost_requests']} -> "
              f"{'ok' if ok else 'FAIL ' + str(gates)}", flush=True)
    cell = {"scenario": f"serving:{sv.pattern}", "policy": label,
            "point": {}, "train_steps": int(plan.train_steps or 0),
            "avg_reward_mean": metrics["avg_reward"],
            "avg_reward_std": 0.0,
            "avg_cost_mean": metrics["avg_cost"],
            "avg_quality_mean": metrics["avg_quality"],
            "serving": metrics, "serving_gates": gates,
            "serving_ok": bool(ok)}
    if engine_info:
        cell["armpool_engines"] = engine_info
    return cell


def run_plan(plan: ExperimentPlan, *, verbose: bool = False
             ) -> ExperimentResult:
    """Execute every planned dispatch and assemble the artifact."""
    import time

    spec = plan.spec
    summ = spec.summarize
    cells: List[Dict[str, Any]] = []
    t0 = time.perf_counter()

    warm_states: Dict[str, Any] = {}
    pretrain_info: Dict[str, Any] = {}
    if spec.pretrain is not None and plan.pretrain_labels:
        from repro.experiments.pretrain import pretrained_states
        corpus, warm_states, pretrain_info = pretrained_states(
            plan, verbose=verbose)
        pretrain_info = {"behavior": spec.pretrain.behavior,
                         "corpus_size": None if corpus is None
                         else corpus.n,
                         "labels": pretrain_info}

    if spec.serving is not None:
        srv_label = plan.serving_policy[0]
        cells.append(_run_serving_cell(
            plan, pretrained_state=warm_states.get(srv_label),
            verbose=verbose))
    for call in plan.calls:
        inits = {lbl: warm_states[lbl] for lbl in call.policies
                 if lbl in warm_states}
        sweeps = run_policy_sweep(
            plan.env, call.policies, seeds=spec.seeds,
            scenario=call.scenario, forgetting=call.forgetting,
            train_steps=plan.train_steps, epochs=spec.train.epochs,
            batch_size=spec.train.batch_size,
            init_states=inits or None)
        scen_label = call.scenario or _STATIONARY
        for label, sweep in sweeps.items():
            points = summarize_sweep(sweep, skip_first=summ.skip_first)
            for g, p in enumerate(points):
                cell = {"scenario": scen_label, "policy": label,
                        "point": call.grids[label][g],
                        "train_steps": int(sweep["train_steps"]), **p}
                if summ.curves:
                    cell["curve_avg_reward"] = np.asarray(
                        sweep["avg_reward"][g], np.float64
                    ).mean(axis=0).tolist()
                if summ.per_seed:
                    s0 = 1 if summ.skip_first \
                        and sweep["avg_reward"].shape[2] > 1 else 0
                    cell["per_seed_avg_reward"] = np.asarray(
                        sweep["avg_reward"][g][:, s0:], np.float64
                    ).mean(axis=1).tolist()
                cells.append(cell)
            if verbose:
                best = max(points, key=lambda p: p["avg_reward_mean"])
                print(f"[{spec.name}] {scen_label}/{label}: "
                      f"avg_reward={best['avg_reward_mean']:.4f} "
                      f"({len(points)} grid point"
                      f"{'s' if len(points) != 1 else ''})", flush=True)

    ope_info: Dict[str, Any] = {}
    if spec.ope is not None:
        from repro.experiments.ope import score_policies_offline
        ope_cells, ope_info = score_policies_offline(plan, verbose=verbose)
        cells.extend(ope_cells)
    wall_s = time.perf_counter() - t0

    dev = jax.local_devices()
    manifest = {
        "schema": RESULT_SCHEMA_VERSION,
        "spec_name": spec.name,
        "spec_hash": spec_hash(spec),
        "backend": jax.default_backend(),
        "n_devices": len(dev),
        "device_kind": dev[0].device_kind if dev else "none",
        "jax_version": jax.__version__,
        "train_steps": plan.train_steps,
        "n_dispatches": plan.n_dispatches,
        "n_cells": len(cells),
        "n_seeds": len(spec.seeds),
        "compile_s": plan.compile_s,
        "wall_s": wall_s,
    }
    if pretrain_info:
        manifest["pretrain"] = pretrain_info
    if ope_info:
        manifest["ope"] = ope_info
    if plan.pool is not None:
        manifest["armpool"] = plan.pool.manifest()
    return ExperimentResult(spec=spec, manifest=manifest, cells=cells)


def run_spec(spec: ExperimentSpec, *, env=None, host_env=None,
             verbose: bool = False) -> ExperimentResult:
    """One-call convenience: compile then run."""
    from repro.experiments.compiler import compile_spec
    plan = compile_spec(spec, env=env, host_env=host_env)
    return run_plan(plan, verbose=verbose)


def format_cells(cells: List[Dict[str, Any]], *,
                 axes: Optional[List[str]] = None) -> str:
    """Fixed-width table of cells (the CLI's human face). ``axes``
    names the grid columns to show (default: every axis present)."""
    if not cells:
        return "(no cells)"
    if axes is None:
        axes = sorted({k for c in cells for k in c["point"]})
    head = f"{'policy':<18}" + "".join(f"{a:>9}" for a in axes) + \
        (f"{'avg_reward':>16}{'oracle':>9}{'dyn_regret':>11}"
         f"{'avg_cost':>10}{'avg_quality':>12}")
    lines = [head, "-" * len(head)]
    for c in sorted(cells, key=lambda c: -c["avg_reward_mean"]):
        ax = ""
        for a in axes:
            if a not in c["point"]:
                ax += f"{'':>9}"
            elif c["point"][a] is None:
                ax += f"{'env':>9}"
            else:
                ax += f"{c['point'][a]:>9.2f}"
        lines.append(
            f"{c['policy']:<18}{ax}"
            f"{c['avg_reward_mean']:>9.4f}±{c['avg_reward_std']:.4f}"
            f"{c.get('oracle_avg_reward_mean', float('nan')):>9.4f}"
            f"{c.get('dynamic_regret_mean', float('nan')):>11.4f}"
            f"{c['avg_cost_mean']:>10.4f}"
            f"{c['avg_quality_mean']:>12.4f}")
    return "\n".join(lines)
