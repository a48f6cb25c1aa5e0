#!/usr/bin/env python3
"""Chip smoke test: drive the router's main paths once on a TPU.

    python chip_smoke.py               # one chip: kernels, protocol, serving
    python chip_smoke.py --four-chips  # sharded sweep vs it on one chip

Everything runs in this one process (a process that has touched JAX
holds the chip; a child could not get it), through the entry points a
user calls: ``repro.experiments`` presets with ``--set``-style
overrides, compiled and run exactly as ``scripts/run_paper_experiments.py
--preset`` runs them. Router widths are the paper's (UtilityNet emb 384,
d_hidden 256, d_last 128, so F = 129) on RouterBench-shaped traffic
(36,497 samples, 86 domains); weights are random, made from seeds.

One-chip phases, each printing one JSON line:

* ``kernels`` — the fused ``nucb_decide`` (B = 512, 4096),
  ``nucb_update`` (4096 rows) and ``ainv_rebuild`` (8192 rows) kernels,
  compiled and run on the chip against their jnp references; the
  compiled decide program must hold a ``tpu_custom_call``.
* ``protocol`` — ``paper_table1`` with ``ucb_backend=pallas`` on the full
  stream: decide, Woodbury update and A^-1 rebuild inside the
  single-dispatch scan (per-slice train steps cut, as printed).
* ``serving`` — ``physical_pool`` with ``armpool.reduced_decode=false``:
  a ~2,000-request storm through the async engine, the router deciding
  with the Pallas kernel and ``mamba2_130m`` decoding at its published
  widths (24 layers, d_model 768, vocab 50,280, ssm_state 128).

``--four-chips`` runs only ``fig2_beta_sweep`` (4 beta x 2 seeds = 8
lanes, cut stream) sharded over 4 chips, and the same sweep on one chip,
and checks that every lane agrees bit for bit.

The script exits non-zero, printing no verdict, when JAX finds no TPU,
when the kernel backend does not resolve to ``pallas``, when any phase
fails, or when the serving storm counts a decide error or a lost
request. Otherwise its last line is ``{"ok": true, "device": {...}}``.
Wall and compile seconds are set-up evidence, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_SAMPLES = 36_497
PROTOCOL_TRAIN_STEPS = 64       # per slice; the epochs=5 schedule is longer
SERVING_REQUESTS = 2_000
SWEEP_SAMPLES, SWEEP_SLICES, SWEEP_TRAIN_STEPS = 6_000, 6, 32


class SmokeFailure(RuntimeError):
    """A phase ran but its output is wrong."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(n_chips: int) -> dict:
    """Fail fast unless JAX sees ``n_chips`` TPUs and every kernel op
    resolves to the compiled Pallas kernel on its own."""
    from repro.kernels.backend import PALLAS, resolve_backend

    dev = _device()
    _require(dev["platform"] == "tpu",
             f"no TPU: JAX runs on {dev['platform']!r}")
    _require(dev["count"] == n_chips,
             f"expected {n_chips} chip(s), JAX sees {dev['count']}")
    _require("REPRO_KERNEL_BACKEND" not in os.environ,
             "REPRO_KERNEL_BACKEND is set; the platform must choose the "
             "kernel backend here")
    backend = resolve_backend(None)
    _require(backend == PALLAS, f"kernel backend resolves to {backend!r}")
    return dev


# ------------------------------------------------------------- kernels --
def phase_kernels(decide_batches=(512, 4096), update_rows=4096,
                  rebuild_rows=8192) -> dict:
    """Run the three main-path kernels on the device against their jnp
    references (computed at full f32 matmul precision). Tolerances are
    the repo's bf16 parity tier (tests/test_nucb_kernels.py): argmax
    agreement >= 0.9, features and means within 5e-2; the printed
    numbers say how close the kernels actually came."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.compilation import CompileCount
    from repro.core import neuralucb as NU
    from repro.core import utilitynet as UN
    from repro.kernels.ainv_rebuild import ainv_rebuild, ainv_rebuild_ref
    from repro.kernels.nucb_decide import (nucb_decide, nucb_decide_ref,
                                           prepare_decide_inputs)
    from repro.kernels.nucb_update import nucb_update, nucb_update_ref

    cfg = UN.UtilityNetConfig()
    F = cfg.ucb_feature_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    params = UN.init_utilitynet(ks[0], cfg)
    Lm = jax.random.normal(ks[1], (F, F)) * 0.05
    ainv = Lm @ Lm.T + jnp.eye(F) * 0.5
    beta, tau_g = jnp.float32(1.0), jnp.float32(0.5)
    out = {"widths": {"emb": cfg.emb_dim, "d_hidden": cfg.d_hidden,
                      "d_last": cfg.d_last, "F": F,
                      "K": cfg.num_actions, "domains": cfg.num_domains}}
    t0 = time.perf_counter()
    with CompileCount() as cc:
        decide = jax.jit(lambda p, e, f, d, A: nucb_decide(
            p, cfg, e, f, d, A, beta, tau_g))
        for B in decide_batches:
            x_emb = jax.random.normal(ks[2], (B, cfg.emb_dim))
            x_feat = jax.random.normal(ks[3], (B, cfg.feat_dim))
            domain = jax.random.randint(ks[4], (B,), 0, cfg.num_domains)
            hlo = decide.lower(params, x_emb, x_feat, domain,
                               ainv).compile().as_text()
            _require("tpu_custom_call" in hlo,
                     f"decide program at B={B} holds no tpu_custom_call")
            a_k, g_k, mu_k, _ = decide(params, x_emb, x_feat, domain, ainv)
            with jax.default_matmul_precision("highest"):
                pre = prepare_decide_inputs(params, x_emb, x_feat, domain)
                a_r, g_r, mu_r = nucb_decide_ref(pre[0], *pre[2:], ainv,
                                                 pre[1], None, beta, tau_g)
            a_k, a_r = np.asarray(a_k), np.asarray(a_r)
            agree = a_k == a_r
            g_err = float(np.abs(np.asarray(g_k) - np.asarray(g_r))[agree]
                          .max(initial=0.0))
            mu_err = float(np.abs(np.asarray(mu_k) - np.asarray(mu_r)).max())
            out[f"decide_B{B}"] = {"agreement": float(agree.mean()),
                                   "g_max_err": g_err,
                                   "mu_safe_max_err": mu_err,
                                   "tpu_custom_call": True}
            _require(agree.mean() >= 0.9 and g_err <= 5e-2 and mu_err <= 5e-2,
                     f"decide B={B} disagrees with its reference: "
                     f"{out[f'decide_B{B}']}")

        gs = NU.augment(jax.random.normal(ks[5], (update_rows, cfg.d_last)))
        got = nucb_update(ainv, gs * 0.3)
        with jax.default_matmul_precision("highest"):
            want = nucb_update_ref(ainv, gs * 0.3)
        upd_err = float(jnp.abs(got - want).max())
        out[f"update_N{update_rows}"] = {"max_err": upd_err}
        _require(upd_err <= 5e-2, f"nucb_update error {upd_err}")

        gs = NU.augment(jax.random.normal(ks[6], (rebuild_rows, cfg.d_last)))
        w = jax.random.uniform(ks[7], (rebuild_rows,))
        got = ainv_rebuild(gs, 1.0, weights=w)
        with jax.default_matmul_precision("highest"):
            want = ainv_rebuild_ref(gs, 1.0, weights=w)
        reb_err = float(jnp.abs(got - want).max())
        out[f"rebuild_N{rebuild_rows}"] = {"max_err": reb_err}
        _require(reb_err <= 5e-2, f"ainv_rebuild error {reb_err}")
    out.update(ok=True, wall_s=time.perf_counter() - t0,
               compile_s=cc.seconds, compiles=cc.compiles)
    return out


# ------------------------------------------------------------ protocol --
def phase_protocol(overrides=None) -> dict:
    """``paper_table1`` through the ``--preset`` path, Pallas kernels
    on the decide / update / rebuild legs of the scan."""
    import numpy as np

    from repro.common.compilation import CompileCount
    from repro.experiments import compile_spec, make_preset, run_plan

    sets = {"ucb_backend": "pallas", "data.n_samples": PAPER_SAMPLES,
            "train.train_steps": PROTOCOL_TRAIN_STEPS, **(overrides or {})}
    spec = make_preset("paper_table1", sets)
    t0 = time.perf_counter()
    with CompileCount() as cc:
        plan = compile_spec(spec)
        res = run_plan(plan)
    wall = time.perf_counter() - t0
    cells = {c["policy"]: c for c in res.cells}
    _require(res.ok, "paper_table1 produced non-finite metrics")
    _require(set(cells) == {p.label for p in spec.policies},
             f"paper_table1 cells {sorted(cells)}")
    n = plan.env.n
    for c in cells.values():
        _require(0.0 <= c["avg_cost_mean"] and
                 0.0 <= c["avg_quality_mean"] <= 1.0,
                 f"{c['policy']}: cost/quality out of range")
    return {"ok": True, "wall_s": wall, "compile_s": cc.seconds,
            "compiles": cc.compiles, "kernel_backend": spec.ucb_backend,
            "n_samples": int(n), "n_slices": int(spec.data.n_slices),
            # of the 86 domains, the heavy-tailed mix leaves a few unsampled
            "domains_seen": int(np.unique(np.asarray(plan.env.domain)).size),
            "n_arms": int(plan.env.K), "decisions": int(n) * len(cells),
            "cut": {"train_steps_per_slice": int(plan.train_steps),
                    "schedule_steps_per_slice": _schedule_steps(plan)},
            "avg_reward": {k: c["avg_reward_mean"]
                           for k, c in cells.items()}}


def _schedule_steps(plan) -> int:
    from repro.sim import neuralucb_train_schedule
    return int(neuralucb_train_schedule(plan.env, plan.spec.train.epochs,
                                        plan.spec.train.batch_size))


# ------------------------------------------------------------- serving --
def phase_serving(overrides=None) -> dict:
    """``physical_pool`` at full decode width: storm through the async
    engine; every gate of the preset, plus no decide error, no lost
    request and real decode steps on mamba2_130m."""
    from repro.common.compilation import CompileCount
    from repro.experiments import compile_spec, make_preset, run_plan

    sets = {"ucb_backend": "pallas", "armpool.reduced_decode": False,
            "data.n_samples": PAPER_SAMPLES,
            "serving.requests": SERVING_REQUESTS, **(overrides or {})}
    spec = make_preset("physical_pool", sets)
    t0 = time.perf_counter()
    with CompileCount() as cc:
        plan = compile_spec(spec)
        res = run_plan(plan)
    wall = time.perf_counter() - t0
    srv = next(c for c in res.cells if c["scenario"].startswith("serving"))
    m = srv["serving"]
    widths = srv["armpool_engines"]["real_decode_widths"]
    steps = m["decode_steps"]["real"]
    line = {"ok": bool(res.ok), "wall_s": wall, "compile_s": cc.seconds,
            "compiles": cc.compiles, "storm_compiles": m["compiles"],
            "kernel_backend": spec.ucb_backend,
            "requests": m["requests"], "completed": m["completed"],
            "decide_calls": m["decide_calls"],
            "decide_errors": m["decide_errors"],
            "lost_requests": m["lost_requests"], "shed": m["shed"],
            "real_decode_steps": steps,
            "clocked_decode_steps": m["decode_steps"]["clocked"],
            "real_decode_widths": widths,
            "gates": srv["serving_gates"]}
    mamba = widths.get("mamba2_130m", {})
    for cond, what in (
            (m["decide_errors"] == 0,
             f"decide error(s), last: {m['last_decide_error']}"),
            (m["lost_requests"] == 0, "lost requests"),
            (res.ok, "physical_pool gates failed"),
            (steps.get("mamba2_130m", 0) > 0,
             "mamba2_130m ran no real decode steps"),
            (mamba.get("num_layers") == 24 and mamba.get("d_model") == 768,
             "mamba2_130m decoded at reduced width")):
        _require(cond, f"{what}: {json.dumps(line, default=float)}")
    return line


# ---------------------------------------------------------- four chips --
def phase_four_chips(overrides=None) -> dict:
    """``fig2_beta_sweep``'s 8 lanes sharded over 4 chips, then the same
    sweep on one chip; every lane must agree bit for bit (sharding never
    changes a lane's arithmetic) and the sharded layout must span the 4
    chips. A lane run alone, unbatched, is no reference here: on the
    chip its matmuls round differently from the batched program's, and
    training carries that into later decisions."""
    import jax
    import numpy as np

    from repro.common.compilation import CompileCount
    from repro.experiments import compile_spec, make_preset
    from repro.sim import run_policy_sweep

    sets = {"ucb_backend": "pallas", "seeds": [0, 1],
            "data.n_samples": SWEEP_SAMPLES, "data.n_slices": SWEEP_SLICES,
            "train.train_steps": SWEEP_TRAIN_STEPS, **(overrides or {})}
    spec = make_preset("fig2_beta_sweep", sets)
    plan = compile_spec(spec)
    (call,) = plan.calls
    (label,) = call.policies
    kw = dict(seeds=spec.seeds, train_steps=plan.train_steps,
              epochs=spec.train.epochs, batch_size=spec.train.batch_size)
    walls = {}
    with CompileCount() as cc:
        sweeps = {}
        for name, n_dev in (("sharded", None), ("one_chip", 1)):
            t0 = time.perf_counter()
            sweeps[name] = run_policy_sweep(plan.env, call.policies,
                                            n_devices=n_dev, **kw)[label]
            walls[f"{name}_wall_s"] = time.perf_counter() - t0
    sharded, one = sweeps["sharded"], sweeps["one_chip"]
    worst = max(float(np.abs(np.asarray(sharded[k])
                             - np.asarray(one[k])).max())
                for k in ("avg_reward", "avg_cost", "avg_quality"))
    hist_l1 = int(np.abs(np.asarray(sharded["action_hist"])
                         - np.asarray(one["action_hist"])).sum())
    layout = sharded["layout"]
    line = {"ok": True, **walls, "compile_s": cc.seconds,
            "compiles": cc.compiles, "kernel_backend": spec.ucb_backend,
            "lanes": int(layout["n_lanes"]),
            "betas": np.asarray(call.policies[label][1].beta).tolist(),
            "seeds": list(spec.seeds), "layout": layout,
            "one_chip_layout": one["layout"],
            "decisions": int(plan.env.n) * int(layout["n_lanes"]),
            "lane_max_abs_diff": worst, "lane_action_hist_l1": hist_l1,
            "cut": {k: sets[k] for k in ("data.n_samples", "data.n_slices",
                                         "train.train_steps")}}
    _require(layout["n_devices"] == len(jax.devices()),
             f"sweep layout spans {layout['n_devices']} devices")
    _require(worst == 0.0 and hist_l1 == 0,
             f"sharded lanes disagree with the one-chip sweep: {line}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded sweep and the "
                         "same sweep on one chip")
    args = ap.parse_args(argv)

    from repro.common.compilation import enable_compile_cache
    enable_compile_cache()
    try:
        dev = check_device(4 if args.four_chips else 1)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as tmp:
        # inputs come from committed files and seeds only: a pretrain
        # cache carried along with the tree is never read
        os.environ["REPRO_PRETRAIN_CACHE"] = tmp
        phases = ([("four_chips", phase_four_chips)] if args.four_chips
                  else [("kernels", phase_kernels),
                        ("protocol", phase_protocol),
                        ("serving", phase_serving)])
        for name, fn in phases:
            try:
                line = fn()
            except SmokeFailure as e:
                print(f"chip_smoke: phase {name} failed: {e}",
                      file=sys.stderr)
                return 1
            _emit(name, **line)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
