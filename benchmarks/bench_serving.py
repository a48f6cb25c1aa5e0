"""Async serving engine throughput (DESIGN.md §12.7): the microbatched
continuous-batching engine vs. a synchronous per-request loop, on the
SAME engine code path and the same device-resident NeuralUCB router.

Two measured modes, recorded to ``BENCH_serving.json`` at the repo root
(schema documented in README.md):

  microbatched — ``run_storm`` over a >=1M-request steady trace with
      ``decide_batch`` requests per jitted decide/update call: sustained
      requests/s, p50/p99 decide-call latency, per-request decide cost,
      periodic train pauses included in the wall clock.
  sync_reference — the identical storm driver with ``decide_batch=1``
      (one jitted decide + one update dispatch per request): the
      pre-continuous-batching serving shape. Run at reduced request
      count (it is the slow side) and reported as measured requests/s.

The headline ``speedup`` is microbatched / sync requests-per-second;
the acceptance bound (>= 10x) is asserted by the CI smoke via the
recorded artifact, not silently assumed.

A third section, ``overlap_vs_sync``, isolates the zero-sync train
overlap (DESIGN.md §15.2): the same storm at a train-heavy cadence
(train every 2 waves, ring depth 8) with ``max_train_lag=0`` (end_slice
blocks on the train) vs ``=2`` (SGD and rebuild dispatched as separate
async device programs, bounded staleness). The compared tail is
``decide_path_p99_us`` — decide-call wall plus any slice-boundary train
stall the next decide waits behind — at zero lost/shed on both sides.
Both train programs are warmed before measurement.

  python -m benchmarks.bench_serving [--requests N] [--waves W]
      [--decide-batch B] [--sync-requests N] [--n-samples N] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict

import jax

from benchmarks.common import cached
from repro.common.compilation import enable_compile_cache
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.serving import DevicePolicyRouter, run_storm
from repro.sim import DeviceReplayEnv, make_policy
from repro.sim.engine import _tables

ROOT_OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_serving.json")

BENCH_SCHEMA = "bench-serving-v2"


def _router(env, *, decide_batch: int, train_steps: int = 32,
            batch_size: int = 64, capacity_slices: int = 256,
            seed: int = 0, train_lag: int = 0) -> DevicePolicyRouter:
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    return DevicePolicyRouter(
        pol, hyp, _tables(env), seed=seed, slice_width=decide_batch,
        capacity_slices=capacity_slices, batch_size=batch_size,
        train_chunks=max(1, -(-train_steps // 32)),
        max_train_lag=train_lag)


def bench_serving(requests: int = 1_000_000, waves: int = 200,
                  decide_batch: int = 512, sync_requests: int = 2_000,
                  n_samples: int = 36_497, train_every: int = 25) -> Dict:
    henv = RouterBenchSim(seed=0, n_samples=n_samples, n_slices=20)
    env = DeviceReplayEnv.from_host(henv)

    t0 = time.perf_counter()
    micro = run_storm(
        env, _router(env, decide_batch=decide_batch),
        requests=requests, waves=waves, pattern="steady",
        queue_capacity=max(4096, 2 * (requests // waves)),
        decide_batch=decide_batch, serve_batch=decide_batch,
        train_every=train_every, seed=0, log_capacity=1024)
    micro_wall = time.perf_counter() - t0

    sync_waves = max(1, sync_requests // 100)
    t0 = time.perf_counter()
    sync = run_storm(
        env, _router(env, decide_batch=1, capacity_slices=1024),
        requests=sync_requests, waves=sync_waves, pattern="steady",
        queue_capacity=max(256, 2 * (sync_requests // sync_waves)),
        decide_batch=1, serve_batch=1,
        train_every=max(1, train_every * sync_requests // requests),
        seed=0, log_capacity=1024)
    sync_wall = time.perf_counter() - t0

    # zero-sync train overlap at a train-heavy cadence (every 2 waves,
    # ring depth 8): identical storm, max_train_lag 0 vs 2 — the only
    # knob that moves. Both train programs (fused sync, staged
    # sgd+rebuild) are compiled by throwaway warmup storms first so the
    # measured stalls are execution, not XLA compile.
    ov_req, ov_waves, ov_cap, lag = min(requests, 400_000), 40, 8, 2
    for wlag in (lag, 0):
        wr = _router(env, decide_batch=decide_batch,
                     capacity_slices=ov_cap, train_lag=wlag)
        run_storm(env, wr, requests=4 * decide_batch, waves=2,
                  pattern="steady", queue_capacity=4 * decide_batch,
                  decide_batch=decide_batch, serve_batch=decide_batch,
                  train_every=1, seed=0)
        wr.state_dict()   # flush: forces the staged rebuild compile too
    ov_kw = dict(requests=ov_req, waves=ov_waves, pattern="steady",
                 queue_capacity=max(4096, 2 * (ov_req // ov_waves)),
                 decide_batch=decide_batch, serve_batch=decide_batch,
                 train_every=2, seed=0, log_capacity=1024)
    ov = {}
    for name, tl in (("sync", 0), ("overlap", lag)):
        t0 = time.perf_counter()
        res = run_storm(env, _router(env, decide_batch=decide_batch,
                                     capacity_slices=ov_cap,
                                     train_lag=tl), **ov_kw)
        ov[name] = {**res, "total_wall_s": time.perf_counter() - t0,
                    "max_train_lag": tl}

    dev = jax.local_devices()
    return {
        "schema": BENCH_SCHEMA,
        "env": {"n_samples": int(n_samples), "n_arms": int(env.K),
                "backend": jax.default_backend(),
                "device_kind": dev[0].device_kind if dev else "none"},
        "microbatched": {**micro, "total_wall_s": micro_wall},
        "sync_reference": {**sync, "total_wall_s": sync_wall},
        "speedup": micro["requests_per_s"] / sync["requests_per_s"],
        "overlap_vs_sync": {
            **ov,
            "p99_decide_path_improvement": (
                ov["sync"]["decide_path_p99_us"]
                / max(ov["overlap"]["decide_path_p99_us"], 1e-9)),
            "throughput_improvement": (
                ov["overlap"]["requests_per_s"]
                / max(ov["sync"]["requests_per_s"], 1e-9)),
        },
    }


def run(refresh: bool = False, **kw):
    out = cached("serving_engine_v2", lambda: bench_serving(**kw), refresh)
    with open(ROOT_OUT, "w") as f:
        json.dump(out, f, indent=1, default=float)
    rows = [("bench_serving/mode", "requests", "req_per_s",
             "p99_decide_us")]
    for mode in ("microbatched", "sync_reference"):
        s = out[mode]
        rows.append((mode, s["requests"], round(s["requests_per_s"]),
                     round(s["decide_p99_us"], 1)))
    rows.append(("speedup(micro/sync)", "", round(out["speedup"], 2), ""))
    for mode in ("sync", "overlap"):
        s = out["overlap_vs_sync"][mode]
        rows.append((f"overlap_vs_sync/{mode}(lag={s['max_train_lag']})",
                     s["requests"], round(s["requests_per_s"]),
                     round(s["decide_path_p99_us"], 1)))
    rows.append(("overlap_p99_decide_path_gain", "",
                 round(out["overlap_vs_sync"]
                       ["p99_decide_path_improvement"], 2), ""))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=1_000_000)
    ap.add_argument("--waves", type=int, default=200)
    ap.add_argument("--decide-batch", type=int, default=512)
    ap.add_argument("--sync-requests", type=int, default=2_000)
    ap.add_argument("--n-samples", type=int, default=36_497)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    global ROOT_OUT
    if args.out:
        ROOT_OUT = args.out
    for row in run(refresh=True, requests=args.requests, waves=args.waves,
                   decide_batch=args.decide_batch,
                   sync_requests=args.sync_requests,
                   n_samples=args.n_samples):
        print(",".join(str(x) for x in row), flush=True)


if __name__ == "__main__":
    main()
