"""Online-protocol engine throughput: seed host loop vs. the device-resident
engine (repro.sim), on the identical replay stream.

Five comparisons, recorded to ``BENCH_protocol.json`` at the repo root
(schema documented in README.md):

  baseline_protocol_single — one 4-policy protocol run: host Python loop
      (T x policies device round-trips) vs. one jitted lax.scan per policy.
  baseline_sweep           — the paper-style multi-seed sweep: host loop
      over seeds vs. one vmap over PRNG keys (the headline speedup; the
      seed path *cannot* amortize seeds).
  neuralucb_slice_step     — Algorithm 1's hot loop for one slice
      (DECIDE -> feedback lookup -> rank-k UPDATE): host decide()/update()
      round-trip vs. the fused jit step.
  neuralucb_scan_vs_stepped — a full Algorithm 1 run on the same fixed
      training schedule: the PR-1-style per-slice runner
      (~ceil(steps/32)+2 dispatches + one sync per slice) vs. the
      single-dispatch lax.scan (DESIGN.md §8.4).
  neuralucb_sweep          — the paper's multi-seed NeuralUCB sweep:
      sequential per-slice runs (the only way the stepped runner can
      sweep) vs. one vmapped scan dispatch sharded over local devices.
  scenario_scan            — the non-stationary scenario engine's cost
      (DESIGN.md §9): the same Algorithm-1 scan with and without the
      price_shock per-slice transforms (acceptance bound <= 1.3x).
  scenario_adaptivity      — what forgetting buys: vanilla vs the
      recency-forgetting variant (replay_rho=0.4) on the price_shock
      and arm_outage scenarios, seed-mean avg reward per config.
  nucb_fused_decide        — the fused DECIDE op (kernels.nucb_decide)
      per backend (jnp / pallas) with an analytic v5e roofline; off-TPU
      the pallas entry records the self-dispatched jnp reference.
  ainv_rebuild             — the streamed blocked-Cholesky A^-1 rebuild
      (kernels.ainv_rebuild) per backend, same schema.
  nucb_fused_update        — the fused rank-k Woodbury A^-1 update
      (kernels.nucb_update, single launch, A^-1 VMEM-resident) per
      backend, same schema.
  policy_zoo_sweep         — the unified runtime's policy axis
      (DESIGN.md §10): a 5-policy × seed sweep as ONE sharded dispatch
      vs per-policy sweeps and sequential per-seed runs, with
      per-policy decisions/s.
  experiment_compile       — the declarative ExperimentSpec layer's
      overhead (DESIGN.md §11): spec→plan compile wall time and the
      planned device-dispatch count vs the minimal hand-wired count
      (must be 0 extra dispatches) for the driver presets.
  physical_pool            — the arm pool's measured-vs-analytic
      calibration (DESIGN.md §16.3): REAL jitted decode steps for the
      two smallest zoo configs vs the host roofline lower bound, the
      measured/analytic ratio recorded per backend, plus the
      physical_pool preset's pool-compile stats and provenance
      (checksum, chips, $/token). ``--pool-tiny`` swaps in the reduced
      configs (CI-sized; the ``reduced`` flag marks the reshape).

The sweep-shaped sections (neuralucb_sweep, policy_zoo_sweep) are
expressed through the same ExperimentSpec presets the driver runs
(``bench_nucb_sweep`` / ``bench_zoo_sweep``), so the bench measures the
exact code path a ``--preset`` invocation takes.

  python -m benchmarks.bench_protocol [--n-samples N] [--n-slices T]
      [--seeds S] [--nucb-samples N] [--nucb-slices T] [--nucb-seeds S]
      [--nucb-train-steps K] [--nucb-batch B] [--scen-samples N]
      [--scen-slices T] [--scen-seeds S] [--zoo-samples N]
      [--zoo-slices T] [--zoo-seeds S] [--pool-only] [--pool-tiny]
      [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import cached
from repro.common.compilation import enable_compile_cache
from repro.core.baselines import (
    EmpiricalGreedy,
    FixedActionPolicy,
    RandomPolicy,
)
from repro.core.policy import NeuralUCBRouter
from repro.core.protocol import run_protocol
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.experiments import (
    compile_spec,
    make_preset,
    run_plan,
    spec_hash,
)
from repro.sim import (
    DeviceNeuralUCB,
    DeviceReplayEnv,
    ForgettingConfig,
    as_bandit_policy,
    fixed_policy,
    greedy_policy,
    make_policy,
    random_policy,
    run_baseline_sweep,
    run_neuralucb_device,
    run_neuralucb_sweep,
    run_policy_device,
    run_policy_sweep,
)
from repro.sim.engine import (
    _cum_valid,
    _nucb_slice_step,
    _policy_scan,
    _tables,
)
from repro.core import neuralucb as NU
from repro.core.utilitynet import init_utilitynet
from repro.kernels.ainv_rebuild import ainv_rebuild
from repro.kernels.nucb_update import nucb_update
from repro.kernels.backend import PALLAS, resolve_backend
from repro.roofline.model import roofline_terms
from repro.sim.policies import _decide_ucb

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))

ROOT_OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_protocol.json")


def _host_policies(env: RouterBenchSim, seed: int):
    return {
        "random": RandomPolicy(env.K, seed=seed),
        "min-cost": FixedActionPolicy(env.min_cost_action()),
        "max-quality": FixedActionPolicy(env.max_quality_action()),
        "greedy": EmpiricalGreedy(env.K),
    }


def _device_policies(env: DeviceReplayEnv):
    return [
        random_policy(env.K),
        fixed_policy(env.min_cost_action(), "min-cost"),
        fixed_policy(env.max_quality_action(), "max-quality"),
        greedy_policy(env.K),
    ]


def _median_wall(fn, reps: int = 3) -> float:
    """Median-of-reps wall time (protocol runs are seconds-long; medians
    absorb scheduler noise better than means)."""
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return sorted(walls)[len(walls) // 2]


def bench_neuralucb_runs(n_samples: int = 1200, n_slices: int = 32,
                         n_seeds: int = 4, train_steps: int = 32,
                         batch_size: int = 32) -> Dict:
    """Full-Algorithm-1 comparisons on one fixed training schedule: the
    per-slice runner vs. the single-dispatch scan, single-run and as a
    multi-seed sweep (DESIGN.md §8.4). The workload is the paper's
    protocol shape at reduced stream size — what's measured here is
    engine structure (dispatch count, sweep amortization, device
    sharding), which the full stream only dilutes with model FLOPs."""
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    denv = DeviceReplayEnv.from_host(henv)
    cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)

    def stepped_run(seed: int):
        return DeviceNeuralUCB(denv, cfg, seed=seed, batch_size=batch_size
                               ).run(train_steps=train_steps, scan=False)

    def scan_run():
        return run_neuralucb_device(denv, cfg, seed=0,
                                    train_steps=train_steps,
                                    batch_size=batch_size)

    # the sweep leg IS the driver's preset path: spec -> plan -> run
    sweep_plan = compile_spec(
        make_preset("bench_nucb_sweep", {
            "data.n_samples": n_samples, "data.n_slices": n_slices,
            "seeds": list(range(n_seeds)),
            "train.train_steps": train_steps,
            "train.batch_size": batch_size}),
        env=denv, host_env=henv)

    def sweep_run():
        return run_plan(sweep_plan)

    stepped_run(0)                      # compile all three paths
    scan_run()
    sweep_run()

    stepped_single = _median_wall(lambda: stepped_run(0))
    scan_single = _median_wall(scan_run)
    stepped_sweep = _median_wall(
        lambda: [stepped_run(s) for s in range(n_seeds)])
    scan_sweep = _median_wall(sweep_run)
    shape = {"n_samples": n_samples, "n_slices": n_slices,
             "train_steps": train_steps, "batch_size": batch_size}
    return {
        "neuralucb_scan_vs_stepped": dict(
            shape, stepped_s=stepped_single, scan_s=scan_single,
            speedup=stepped_single / scan_single),
        "neuralucb_sweep": dict(
            shape, n_seeds=n_seeds, stepped_s=stepped_sweep,
            scan_s=scan_sweep, speedup=stepped_sweep / scan_sweep,
            n_devices=len(jax.local_devices())),
    }


def bench_scenarios(n_samples: int = 6000, n_slices: int = 12,
                    n_seeds: int = 6, train_steps: int = 32,
                    batch_size: int = 32) -> Dict:
    """Non-stationary scenario engine (DESIGN.md §9), two questions:

    * ``scenario_scan`` — what does the declarative per-slice transform
      path COST? The same Algorithm-1 scan with and without the
      price_shock transforms (per-slice quality/cost/reward re-derive +
      availability handling); the ISSUE acceptance bound is <= 1.3x.
    * ``scenario_adaptivity`` — what does forgetting BUY? Seed-mean avg
      reward of vanilla NeuralUCB vs the recency-forgetting variant
      (replay_rho=0.4, §9.2) under the price_shock and arm_outage
      scenarios, each config one vmapped sweep dispatch.
    """
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    denv = DeviceReplayEnv.from_host(henv)
    cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)
    kw = dict(train_steps=train_steps, batch_size=batch_size,
              ucb_backend="jnp")

    def stationary():
        return run_neuralucb_device(denv, cfg, seed=0, **kw)

    def scenario():
        return run_neuralucb_device(denv, cfg, seed=0,
                                    scenario="price_shock", **kw)

    stationary()                        # compile both traces
    scenario()
    stat_s = _median_wall(stationary)
    scen_s = _median_wall(scenario)

    adaptivity = {}
    fg = ForgettingConfig(replay_rho=0.4)
    for scen in ("price_shock", "arm_outage"):
        row = {}
        for name, f in (("vanilla", None), ("forgetting", fg)):
            skw = dict(seeds=range(n_seeds), train_steps=train_steps,
                       batch_size=batch_size, scenario=scen)
            if f is not None:
                skw["forgetting"] = f
            sw = run_neuralucb_sweep(denv, cfg, **skw)
            row[name] = float(sw["avg_reward"][0, :, 1:].mean())
        row["delta"] = row["forgetting"] - row["vanilla"]
        adaptivity[scen] = row

    shape = {"n_samples": n_samples, "n_slices": n_slices,
             "train_steps": train_steps, "batch_size": batch_size}
    return {
        "scenario_scan": dict(
            shape, scenario="price_shock", stationary_s=stat_s,
            scenario_s=scen_s, overhead=scen_s / stat_s),
        "scenario_adaptivity": dict(
            shape, n_seeds=n_seeds, replay_rho=0.4,
            n_devices=len(jax.local_devices()), **adaptivity),
    }


def bench_policy_zoo(n_samples: int = 1200, n_slices: int = 8,
                     n_seeds: int = 4, train_steps: int = 32,
                     batch_size: int = 32) -> Dict:
    """The unified runtime's policy axis (DESIGN.md §10): a 5-policy
    (neuralucb / linucb / neural_ts / eps_greedy / boltzmann) × seed
    sweep as ONE sharded dispatch vs (a) each policy's own one-dispatch
    sweep and (b) sequential per-seed single runs — per-policy
    decisions/s and the sweep speedup recorded per policy."""
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    denv = DeviceReplayEnv.from_host(henv)
    cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)
    names = ("neuralucb", "linucb", "neural_ts", "eps_greedy", "boltzmann")
    policies = {n: make_policy(n, denv, cfg, ucb_backend="jnp")
                for n in names}
    kw = dict(train_steps=train_steps, batch_size=batch_size)

    # the one-dispatch zoo leg IS the driver's preset path
    zoo_plan = compile_spec(
        make_preset("bench_zoo_sweep", {
            "data.n_samples": n_samples, "data.n_slices": n_slices,
            "seeds": list(range(n_seeds)),
            "train.train_steps": train_steps,
            "train.batch_size": batch_size}),
        env=denv, host_env=henv)
    assert zoo_plan.n_dispatches == 1

    def zoo():
        return run_plan(zoo_plan)

    zoo()                               # compile the one-dispatch program
    zoo_s = _median_wall(zoo)

    per_policy = {}
    sum_sweep = 0.0
    sum_seq = 0.0
    decisions = n_seeds * henv.n
    for name in names:
        pol, hyp = policies[name]

        def psweep(name=name, pol=pol, hyp=hyp):
            return run_policy_sweep(denv, {name: (pol, hyp)},
                                    seeds=range(n_seeds), **kw)

        def pseq(pol=pol, hyp=hyp):
            for s in range(n_seeds):
                run_policy_device(denv, pol, hyp, seed=s, **kw)

        psweep()                        # compile both reference paths
        pseq()
        ps = _median_wall(psweep)
        sq = _median_wall(pseq, reps=1)
        per_policy[name] = {
            "sweep_s": ps, "sequential_s": sq, "speedup": sq / ps,
            "decisions_per_s": decisions / ps,
        }
        sum_sweep += ps
        sum_seq += sq

    return {"policy_zoo_sweep": {
        "n_samples": n_samples, "n_slices": n_slices,
        "train_steps": train_steps, "batch_size": batch_size,
        "n_seeds": n_seeds, "n_policies": len(names),
        "n_devices": len(jax.local_devices()),
        "zoo_dispatch_s": zoo_s,
        "sum_single_policy_sweeps_s": sum_sweep,
        "sequential_runs_s": sum_seq,
        "speedup_vs_sequential": sum_seq / zoo_s,
        "per_policy": per_policy,
    }}


def bench_nucb_kernels(batch: int = 4096, buffer_rows: int = 8192,
                       reps: int = 10) -> Dict:
    """Per-backend microbenchmarks of the two fused neural hot-path ops
    (DESIGN.md §14.1): the fused DECIDE (trunk forward → augment →
    g^T A^-1 g bonus → gated masked argmax) and the streamed
    blocked-Cholesky A^-1 REBUILD, each against the plain-XLA path, with
    an analytic roofline per op (TPU v5e constants). Off-TPU the
    "pallas" entries record what the self-dispatch resolves to — the
    jnp reference (``mode: "reference"``); on TPU they are the compiled
    kernels (``mode: "compiled"``). Interpret mode is never timed: it
    measures the interpreter, not the kernel."""
    cfg = UtilityNetConfig()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = init_utilitynet(ks[0], cfg)
    batch_in = {
        "x_emb": jax.random.normal(ks[1], (batch, cfg.emb_dim)),
        "x_feat": jax.random.normal(ks[2], (batch, cfg.feat_dim)),
        "domain": jax.random.randint(ks[3], (batch,), 0,
                                     cfg.num_domains),
    }
    F = cfg.ucb_feature_dim
    ainv = jnp.eye(F) * 0.5
    beta, tau_g = jnp.float32(1.0), jnp.float32(0.5)
    pallas_mode = ("compiled" if resolve_backend(None) == PALLAS
                   else "reference")

    def decide(backend):
        fn = jax.jit(lambda p, ai, b: _decide_ucb(p, ai, b, beta, tau_g,
                                                  cfg, backend))
        jax.block_until_ready(fn(params, ainv, batch_in))   # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(params, ainv, batch_in))
        wall = (time.perf_counter() - t0) / reps
        return {"decisions_per_s": batch / wall, "wall_s": wall}

    dec = {"jnp": dict(decide("jnp"), mode="xla"),
           "pallas": dict(decide("pallas"), mode=pallas_mode)}

    # analytic decide roofline: one context GEMM + per-action trunk2 /
    # u-head / quadratic form (C=d_text+d_feat, H=d_hidden, D=d_last)
    C = cfg.d_text + cfg.d_feat
    H, D, K = cfg.d_hidden, cfg.d_last, cfg.num_actions
    dec_flops = 2.0 * batch * (C * H + K * (H * D + D * D + 4 * D))
    dec_bytes = 4.0 * (batch * (cfg.emb_dim + cfg.feat_dim + C + F + 2)
                       + C * H + K * H + H * D + F * F)

    gs = jax.random.normal(jax.random.PRNGKey(7), (buffer_rows, F)) * 0.3
    w = jnp.ones((buffer_rows,)).at[: buffer_rows // 4].set(0.0)

    def rebuild(backend):
        # gs / w stay jit ARGUMENTS — a zero-arg closure lets XLA
        # constant-fold the whole rebuild at compile time
        if backend == "pallas":
            fn = jax.jit(lambda g, ww: ainv_rebuild(g, 1.0, weights=ww))
        else:
            fn = jax.jit(lambda g, ww: NU.rebuild_ainv(g, 1.0,
                                                       weights=ww))
        jax.block_until_ready(fn(gs, w))                    # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(gs, w))
        wall = (time.perf_counter() - t0) / reps
        return {"rebuilds_per_s": 1.0 / wall,
                "rows_per_s": buffer_rows / wall, "wall_s": wall}

    reb = {"jnp": dict(rebuild("jnp"), mode="xla"),
           "pallas": dict(rebuild("pallas"), mode=pallas_mode)}

    # Gram accumulation + blocked Cholesky + triangular inverse + L^-T L^-1
    reb_flops = 2.0 * buffer_rows * F * F + 2.0 * F ** 3
    reb_bytes = 4.0 * (buffer_rows * (F + 1) + 3 * F * F)

    # the fused rank-k Woodbury UPDATE (kernels.nucb_update): one slice's
    # worth of rows folded into A^-1 in a single launch, A^-1 resident
    gs_upd = gs[:batch]

    def update(backend):
        if backend == "pallas":
            fn = jax.jit(lambda ai, g: nucb_update(ai, g))
        else:
            fn = jax.jit(lambda ai, g: NU.woodbury_update(ai, g))
        jax.block_until_ready(fn(ainv, gs_upd))             # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(ainv, gs_upd))
        wall = (time.perf_counter() - t0) / reps
        return {"updates_per_s": 1.0 / wall,
                "rows_per_s": batch / wall, "wall_s": wall}

    upd = {"jnp": dict(update("jnp"), mode="xla"),
           "pallas": dict(update("pallas"), mode=pallas_mode)}

    # per 128-row block of k rows: u = G A^-1 (2kF^2), S = I + u G^T
    # (2k^2 F), Cholesky k^3/3, x = S^-1 u (2k^2 F), downdate u^T x
    # (2kF^2) -> aggregated over batch rows
    bk = 128.0
    upd_flops = batch * (4.0 * F * F + 4.0 * bk * F + bk * bk / 3.0)
    upd_bytes = 4.0 * (batch * F + 2.0 * F * F)

    return {
        "nucb_fused_decide": {
            "batch": batch, "num_actions": K, "feature_dim": F,
            "d_hidden": H, "d_last": D,
            "backends": dec,
            "speedup_pallas_vs_jnp": (dec["jnp"]["wall_s"]
                                      / dec["pallas"]["wall_s"]),
            "roofline": dict(
                roofline_terms(dec_flops, dec_bytes, 0.0),
                flops=dec_flops, bytes=dec_bytes),
        },
        "ainv_rebuild": {
            "buffer_rows": buffer_rows, "feature_dim": F,
            "backends": reb,
            "speedup_pallas_vs_jnp": (reb["jnp"]["wall_s"]
                                      / reb["pallas"]["wall_s"]),
            "roofline": dict(
                roofline_terms(reb_flops, reb_bytes, 0.0),
                flops=reb_flops, bytes=reb_bytes),
        },
        "nucb_fused_update": {
            "update_rows": batch, "feature_dim": F, "block_k": int(bk),
            "backends": upd,
            "speedup_pallas_vs_jnp": (upd["jnp"]["wall_s"]
                                      / upd["pallas"]["wall_s"]),
            "roofline": dict(
                roofline_terms(upd_flops, upd_bytes, 0.0),
                flops=upd_flops, bytes=upd_bytes),
        },
    }


def bench_physical_pool(configs=("mamba2_130m", "whisper_medium"),
                        batch: int = 4, steps: int = 6,
                        tiny: bool = False) -> Dict:
    """Physical-arm-pool calibration + compile stats (DESIGN.md §16.3).

    For the two smallest real configs, times REAL jitted decode steps
    (the serving engine's own decode program) against the host
    roofline's analytic lower bound; ``measured_over_analytic`` is the
    per-backend efficiency de-rating that ``ArmPoolSpec(calibrate=True)``
    folds into the pool tables. Also compiles the ``physical_pool``
    preset's pool and records its wall time + provenance manifest
    (the crc32 checksum is the cross-process determinism pin)."""
    from repro.armpool import build_pool_env, measured_ratio
    from repro.configs import get_config

    backend = jax.default_backend()
    calibration: Dict[str, Dict] = {}
    for name in configs:
        cfg = get_config(name)
        if tiny:
            cfg = cfg.reduced()
        r = measured_ratio(cfg, batch, steps=steps)
        calibration[name] = {
            "params_b": cfg.param_count() / 1e9,
            "backends": {backend: {
                "measured_step_s": r["step_s"],
                "analytic_step_s": r["analytic_step_s"],
                "measured_over_analytic": r["ratio"],
                "init_s": r["init_s"],
                "compile_s": r["compile_s"],
            }},
        }

    spec = make_preset("physical_pool")
    t0 = time.perf_counter()
    henv, pool = build_pool_env(spec.armpool, spec.data)
    pool_compile_s = time.perf_counter() - t0
    return {"physical_pool": {
        "backend": backend, "batch": batch, "steps": steps,
        "reduced": bool(tiny),
        "calibration": calibration,
        "pool": dict(pool.manifest(), n_samples=int(henv.n),
                     compile_s=pool_compile_s),
    }}


def bench_experiment_compile(n_samples: int = 1500,
                             n_slices: int = 3) -> Dict:
    """The ExperimentSpec layer's cost (DESIGN.md §11): per driver
    preset, the spec→plan compile wall time (registry resolution, axis
    validation, dispatch grouping — the replay env is injected so data
    generation is excluded) and the planned device-dispatch count
    pinned against the MINIMAL hand-wired count (one
    ``run_policy_sweep`` per (scenario × forgetting-variant) group).
    ``extra_dispatches`` must be 0: expressing a study as a spec may
    cost microseconds of host time but never an extra compiled
    program."""
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    denv = DeviceReplayEnv.from_host(henv)
    # LITERAL hand-derived run_policy_sweep call counts per preset —
    # independent of the compiler's grouping code, so a grouping
    # regression shows up as extra_dispatches != 0 here:
    #   fig2_beta_sweep: 1 scenario (stationary) x 1 variant      = 1
    #   scenario_suite:  2 scenarios x (vanilla + forget) variants = 4
    #   ci_smoke:        3 scenarios x (vanilla + forget) variants = 6
    hand_wired_calls = {"fig2_beta_sweep": 1, "scenario_suite": 4,
                        "ci_smoke": 6}
    out: Dict[str, Dict] = {}
    for name, hand_wired in hand_wired_calls.items():
        spec = make_preset(name)
        compile_s = _median_wall(
            lambda: compile_spec(spec, env=denv, host_env=henv), reps=5)
        plan = compile_spec(spec, env=denv, host_env=henv)
        out[name] = {
            "spec_hash": spec_hash(spec),
            "compile_s": compile_s,
            "n_dispatches": plan.n_dispatches,
            "hand_wired_dispatches": hand_wired,
            "extra_dispatches": plan.n_dispatches - hand_wired,
            "n_cells": plan.n_cells,
        }
    return {"experiment_compile": out}


def bench_offline_pretrain(henv: RouterBenchSim, denv: DeviceReplayEnv,
                           corpus_size: int = 20_000,
                           pretrain_steps: int = 512,
                           train_steps: int = 32) -> Dict:
    """Lifecycle bench (DESIGN.md §13.3): offline pretraining wall time
    per hooked policy plus the warm-vs-cold cumulative-reward delta
    over the EARLY window — the first 20% of slices of the
    paper_table1-shaped stream, where a warm start must pay off before
    the cold online learner catches up. Warm and cold runs share the
    seed (identical PRNG streams); warm additionally drops the slice-0
    uniform warm-up (``warm_slice=False``) so the pretrained state
    routes from the first request."""
    from repro.data.logged import replay_corpus
    from repro.sim import pretrain_policy_state

    cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1],
                           num_actions=henv.K)
    corpus = replay_corpus(denv, corpus_size, seed=0)
    n_early = max(1, int(denv.mask.shape[0]) // 5)
    out: Dict = {"corpus_size": corpus.n, "pretrain_steps": pretrain_steps,
                 "early_slices": n_early, "policies": {}}
    for name in ("neuralucb", "sup_winrate", "linucb"):
        pol_c, hyp = make_policy(name, denv, cfg)
        try:
            pol_w, hyp = make_policy(name, denv, cfg, warm_slice=False)
        except TypeError:
            pol_w = pol_c
        t0 = time.perf_counter()
        state = jax.block_until_ready(pretrain_policy_state(
            denv, pol_w, hyp, corpus, seed=0, steps=pretrain_steps))
        pretrain_s = time.perf_counter() - t0
        res_w = run_policy_device(denv, pol_w, hyp, seed=0,
                                  train_steps=train_steps,
                                  init_state=state)
        res_c = run_policy_device(denv, pol_c, hyp, seed=0,
                                  train_steps=train_steps)
        warm = res_w["cum_reward"][n_early - 1]
        cold = res_c["cum_reward"][n_early - 1]
        out["policies"][name] = {
            "pretrain_s": pretrain_s,
            "early_cum_reward_warm": warm,
            "early_cum_reward_cold": cold,
            "early_delta": warm - cold,
            "final_cum_reward_warm": res_w["cum_reward"][-1],
            "final_cum_reward_cold": res_c["cum_reward"][-1],
        }
    return {"offline_pretrain": out}


def _bench_sharded(fn, fn_args, flag_args, n_seeds: int) -> Dict:
    """Run a sweep section with its lane axis sharded over devices.

    On an accelerator this process already sees the real local devices
    (sweeps shard their lanes over them, DESIGN.md §8.4), and it holds
    the chip: a child process could not get it. So the section runs
    here. On CPU it runs in a child with the host's cores exposed as
    XLA host-platform devices (same mechanism as the 512-device dry-run);
    isolating the flag in the child keeps this process, and every other
    section, on the default single device."""
    if jax.default_backend() != "cpu":
        return fn(*fn_args)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # more devices than sweep lanes would be pure startup overhead in
        # the child — shard_sweep_axis only ever uses the first n_seeds
        n_dev = max(1, min(os.cpu_count() or 1, n_seeds))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_protocol", *flag_args],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError("bench subprocess failed:\n"
                           + out.stderr[-2000:])
    return json.loads(out.stdout)


def bench_neuralucb_sharded(n_samples: int, n_slices: int, n_seeds: int,
                            train_steps: int, batch_size: int) -> Dict:
    return _bench_sharded(
        bench_neuralucb_runs,
        (n_samples, n_slices, n_seeds, train_steps, batch_size),
        ["--nucb-only",
         "--nucb-samples", str(n_samples), "--nucb-slices", str(n_slices),
         "--nucb-seeds", str(n_seeds),
         "--nucb-train-steps", str(train_steps),
         "--nucb-batch", str(batch_size)], n_seeds)


def bench_scenarios_sharded(n_samples: int, n_slices: int, n_seeds: int,
                            train_steps: int, batch_size: int) -> Dict:
    return _bench_sharded(
        bench_scenarios,
        (n_samples, n_slices, n_seeds, train_steps, batch_size),
        ["--scen-only",
         "--scen-samples", str(n_samples), "--scen-slices", str(n_slices),
         "--scen-seeds", str(n_seeds),
         "--nucb-train-steps", str(train_steps),
         "--nucb-batch", str(batch_size)], n_seeds)


def bench_policy_zoo_sharded(n_samples: int, n_slices: int, n_seeds: int,
                             train_steps: int, batch_size: int) -> Dict:
    return _bench_sharded(
        bench_policy_zoo,
        (n_samples, n_slices, n_seeds, train_steps, batch_size),
        ["--zoo-only",
         "--zoo-samples", str(n_samples), "--zoo-slices", str(n_slices),
         "--zoo-seeds", str(n_seeds),
         "--nucb-train-steps", str(train_steps),
         "--nucb-batch", str(batch_size)], n_seeds)


def bench_protocol(n_samples: int = 36_497, n_slices: int = 20,
                   n_seeds: int = 32, nucb_samples: int = 1200,
                   nucb_slices: int = 32, nucb_seeds: int = 4,
                   nucb_train_steps: int = 32,
                   nucb_batch: int = 32, scen_samples: int = 6000,
                   scen_slices: int = 12, scen_seeds: int = 6,
                   zoo_samples: int = 1200, zoo_slices: int = 8,
                   zoo_seeds: int = 4, pool_tiny: bool = False) -> Dict:
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=n_slices)
    denv = DeviceReplayEnv.from_host(henv)
    tables, xs = _tables(denv), denv.slice_xs()
    cum0 = _cum_valid(denv)
    dpols = [as_bandit_policy(p) for p in _device_policies(denv)]
    n_policies = len(dpols)

    def _scan_run(p):
        return jax.block_until_ready(_policy_scan(
            tables, xs, denv.idx, cum0, jax.random.PRNGKey(0), (), p)[1])

    # --- single protocol run ---------------------------------------------
    run_protocol(henv, _host_policies(henv, 0), verbose=False)  # warm numpy
    t0 = time.perf_counter()
    run_protocol(henv, _host_policies(henv, 0), verbose=False)
    host_single = time.perf_counter() - t0

    for p in dpols:  # compile the unified scan per policy
        _scan_run(p)
    t0 = time.perf_counter()
    for p in dpols:
        _scan_run(p)
    dev_single = time.perf_counter() - t0

    # --- multi-seed sweep -------------------------------------------------
    t0 = time.perf_counter()
    for s in range(n_seeds):
        run_protocol(henv, _host_policies(henv, s), verbose=False)
    host_sweep = time.perf_counter() - t0

    for p in dpols:  # compile the vmapped scan
        run_baseline_sweep(denv, p, range(n_seeds))
    t0 = time.perf_counter()
    for p in dpols:
        run_baseline_sweep(denv, p, range(n_seeds))
    dev_sweep = time.perf_counter() - t0
    sweep_decisions = n_seeds * n_policies * henv.n

    # --- NeuralUCB slice step (post-warm decide+update, no training) ------
    cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)
    router = NeuralUCBRouter(cfg, seed=0)
    b = henv.slice_batch(0)
    n0 = len(b["idx"])
    dec = router.decide(b["x_emb"], b["x_feat"], b["domain"])
    router.update(b["x_emb"], b["x_feat"], b["domain"], dec,
                  b["reward"][np.arange(n0), dec["action"]])
    router.end_slice(epochs=1)

    def host_step():
        d = router.decide(b["x_emb"], b["x_feat"], b["domain"])
        router.update(b["x_emb"], b["x_feat"], b["domain"], d,
                      b["reward"][np.arange(n0), d["action"]])

    host_step()
    t0 = time.perf_counter()
    for _ in range(5):
        host_step()
    host_step_s = (time.perf_counter() - t0) / 5

    nucb = DeviceNeuralUCB(denv, cfg, seed=0)

    # ainv/bufs are donated by _nucb_slice_step — thread the returned
    # buffers through the timing loop exactly like the stepped runner does
    def dev_step(ainv, bufs):
        ainv, bufs, _ = _nucb_slice_step(
            nucb.params, ainv, tables, bufs, jnp.int32(1),
            denv.idx[1], denv.mask[1], jax.random.PRNGKey(0),
            jnp.float32(1.0), jnp.float32(0.5), jnp.float32(0.05),
            cfg, nucb.ucb_backend, False)
        return ainv, bufs

    ainv, bufs = dev_step(nucb.ainv, nucb.bufs)
    jax.block_until_ready(ainv)
    t0 = time.perf_counter()
    for _ in range(5):
        ainv, bufs = dev_step(ainv, bufs)
        jax.block_until_ready(ainv)
    dev_step_s = (time.perf_counter() - t0) / 5
    nucb.ainv, nucb.bufs = ainv, bufs

    nucb_runs = bench_neuralucb_sharded(
        nucb_samples, nucb_slices, nucb_seeds, nucb_train_steps, nucb_batch)
    scen_runs = bench_scenarios_sharded(
        scen_samples, scen_slices, scen_seeds, nucb_train_steps,
        nucb_batch)
    zoo_runs = bench_policy_zoo_sharded(
        zoo_samples, zoo_slices, zoo_seeds, nucb_train_steps, nucb_batch)
    kernel_runs = bench_nucb_kernels()
    compile_runs = bench_experiment_compile()
    pretrain_runs = bench_offline_pretrain(henv, denv)
    pool_runs = bench_physical_pool(tiny=pool_tiny)

    return {
        # headline: protocol-engine throughput on the paper-style workload
        # (multi-seed baseline sweep) vs. the seed host loop
        "speedup": host_sweep / dev_sweep,
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "n_samples": n_samples,
            "n_slices": n_slices,
            "n_seeds": n_seeds,
            "n_policies": n_policies,
            "n_devices": len(jax.local_devices()),
            "ucb_backend": nucb.ucb_backend,
            "kernel_backends": ["jnp", "pallas"],
        },
        "baseline_protocol_single": {
            "host_s": host_single,
            "device_s": dev_single,
            "speedup": host_single / dev_single,
        },
        "baseline_sweep": {
            "host_s": host_sweep,
            "device_s": dev_sweep,
            "speedup": host_sweep / dev_sweep,
            "host_decisions_per_s": sweep_decisions / host_sweep,
            "device_decisions_per_s": sweep_decisions / dev_sweep,
        },
        "neuralucb_slice_step": {
            "slice_width": int(denv.slice_width),
            "host_s": host_step_s,
            "device_s": dev_step_s,
            "speedup": host_step_s / dev_step_s,
        },
        **nucb_runs,
        **scen_runs,
        **zoo_runs,
        **kernel_runs,
        **compile_runs,
        **pretrain_runs,
        **pool_runs,
    }


def run(refresh: bool = False, **kw):
    out = cached("protocol_engine_v9", lambda: bench_protocol(**kw), refresh)
    with open(ROOT_OUT, "w") as f:
        json.dump(out, f, indent=1, default=float)
    rows = [("bench_protocol/section", "host_s", "device_s", "speedup")]
    for sec in ("baseline_protocol_single", "baseline_sweep",
                "neuralucb_slice_step"):
        s = out[sec]
        rows.append((sec, round(s["host_s"], 4), round(s["device_s"], 5),
                     round(s["speedup"], 2)))
    for sec in ("neuralucb_scan_vs_stepped", "neuralucb_sweep"):
        s = out[sec]
        rows.append((sec, round(s["stepped_s"], 4), round(s["scan_s"], 4),
                     round(s["speedup"], 2)))
    s = out["scenario_scan"]
    rows.append(("scenario_scan(overhead)", round(s["stationary_s"], 4),
                 round(s["scenario_s"], 4), round(s["overhead"], 3)))
    for scen, row in out["scenario_adaptivity"].items():
        if isinstance(row, dict):
            rows.append((f"adaptivity/{scen}", round(row["vanilla"], 4),
                         round(row["forgetting"], 4),
                         f"+{row['delta']:.4f}"))
    z = out["policy_zoo_sweep"]
    rows.append(("policy_zoo(one dispatch)", round(z["sequential_runs_s"], 4),
                 round(z["zoo_dispatch_s"], 4),
                 round(z["speedup_vs_sequential"], 2)))
    for name, p in z["per_policy"].items():
        rows.append((f"zoo/{name}", round(p["sequential_s"], 4),
                     round(p["sweep_s"], 4),
                     f"{p['decisions_per_s']:.0f}/s"))
    for sec in ("nucb_fused_decide", "ainv_rebuild", "nucb_fused_update"):
        s = out[sec]
        for bk, row in s["backends"].items():
            rate = row.get("decisions_per_s", row.get("rows_per_s"))
            rows.append((f"{sec}/{bk}", round(row["wall_s"], 5),
                         f"{rate:.0f}/s", row["mode"]))
    for name, c in out["experiment_compile"].items():
        rows.append((f"spec_compile/{name}", round(c["compile_s"], 5),
                     f"{c['n_dispatches']} disp",
                     f"+{c['extra_dispatches']}"))
    for name, p in out["offline_pretrain"]["policies"].items():
        rows.append((f"pretrain/{name}", round(p["pretrain_s"], 3),
                     f"{p['early_cum_reward_warm']:.0f}w/"
                     f"{p['early_cum_reward_cold']:.0f}c",
                     f"{p['early_delta']:+.0f}"))
    if "physical_pool" in out:
        pp = out["physical_pool"]
        for name, c in pp["calibration"].items():
            for bk, row in c["backends"].items():
                rows.append((f"pool_calib/{name}/{bk}",
                             round(row["measured_step_s"], 5),
                             round(row["analytic_step_s"], 6),
                             f"x{row['measured_over_analytic']:.1f}"))
        rows.append(("pool_compile",
                     round(pp["pool"]["compile_s"], 4),
                     f"{len(pp['pool']['arms'])} arms",
                     f"crc {pp['pool']['checksum']}"))
    rows.append(("sweep_device_decisions_per_s",
                 round(out["baseline_sweep"]["device_decisions_per_s"]),
                 "", ""))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-samples", type=int, default=36_497)
    ap.add_argument("--n-slices", type=int, default=20)
    ap.add_argument("--seeds", type=int, default=32)
    ap.add_argument("--nucb-samples", type=int, default=1200)
    ap.add_argument("--nucb-slices", type=int, default=32)
    ap.add_argument("--nucb-seeds", type=int, default=4)
    ap.add_argument("--nucb-train-steps", type=int, default=32)
    ap.add_argument("--nucb-batch", type=int, default=32)
    ap.add_argument("--scen-samples", type=int, default=6000)
    ap.add_argument("--scen-slices", type=int, default=12)
    ap.add_argument("--scen-seeds", type=int, default=6)
    ap.add_argument("--zoo-samples", type=int, default=1200)
    ap.add_argument("--zoo-slices", type=int, default=8)
    ap.add_argument("--zoo-seeds", type=int, default=4)
    ap.add_argument("--nucb-only", action="store_true",
                    help="internal: run only the NeuralUCB sections and "
                         "print their JSON (the CPU child entry point)")
    ap.add_argument("--scen-only", action="store_true",
                    help="internal: run only the scenario sections and "
                         "print their JSON (the CPU child entry point)")
    ap.add_argument("--zoo-only", action="store_true",
                    help="internal: run only the policy-zoo sweep section "
                         "and print its JSON (the CPU child entry point)")
    ap.add_argument("--pool-only", action="store_true",
                    help="run only the physical_pool calibration section "
                         "and print its JSON")
    ap.add_argument("--pool-tiny", action="store_true",
                    help="calibrate the REDUCED configs (CI-sized; marks "
                         "the section reduced=true so the regression "
                         "guard treats it as a reshape)")
    ap.add_argument("--pool-batch", type=int, default=4)
    ap.add_argument("--pool-steps", type=int, default=6)
    ap.add_argument("--out", default=ROOT_OUT)
    args = ap.parse_args()
    enable_compile_cache()
    if args.pool_only:
        out = bench_physical_pool(batch=args.pool_batch,
                                  steps=args.pool_steps,
                                  tiny=args.pool_tiny)
        print(json.dumps(out, default=float))
        return
    if args.nucb_only:
        out = bench_neuralucb_runs(
            args.nucb_samples, args.nucb_slices, args.nucb_seeds,
            args.nucb_train_steps, args.nucb_batch)
        print(json.dumps(out, default=float))
        return
    if args.scen_only:
        out = bench_scenarios(
            args.scen_samples, args.scen_slices, args.scen_seeds,
            args.nucb_train_steps, args.nucb_batch)
        print(json.dumps(out, default=float))
        return
    if args.zoo_only:
        out = bench_policy_zoo(
            args.zoo_samples, args.zoo_slices, args.zoo_seeds,
            args.nucb_train_steps, args.nucb_batch)
        print(json.dumps(out, default=float))
        return
    out = bench_protocol(args.n_samples, args.n_slices, args.seeds,
                         args.nucb_samples, args.nucb_slices,
                         args.nucb_seeds, args.nucb_train_steps,
                         args.nucb_batch, args.scen_samples,
                         args.scen_slices, args.scen_seeds,
                         args.zoo_samples, args.zoo_slices,
                         args.zoo_seeds, pool_tiny=args.pool_tiny)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(json.dumps(out, indent=1, default=float))


if __name__ == "__main__":
    main()
