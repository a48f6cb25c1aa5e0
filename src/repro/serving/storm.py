"""Traffic-storm driver (DESIGN.md §12.5): replay-table traffic through
the async engine at scale.

One call = one storm: a traffic pattern shapes the request budget into
arrival waves, scripted (or scenario-derived) outage windows toggle arm
health at wave boundaries, and the engine's decide-latency samples and
counters roll up into the `serving_storm` / `physical_pool` presets'
metrics — p50/p99 decide latency, sustained requests/s, shed/fallback
accounting, and the zero-lost-requests invariant.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.common.compilation import CompileCount
from repro.serving.async_engine import AsyncRouterEngine
from repro.serving.batcher import Request
from repro.serving.faults import ScriptedFaults
from repro.serving.traffic import outages_from_scenario, wave_sizes

_TOKENS = np.arange(1, 5, dtype=np.int32)   # shared stub prompt


def run_storm(env, router, *, requests: int, waves: int,
              pattern: str = "flash_crowd",
              outages: Sequence[Tuple[int, int, int]] = (),
              scenario: Optional[str] = None,
              queue_capacity: int = 4096, decide_batch: int = 256,
              serve_batch: int = 256,
              fail_decide_calls: Sequence[int] = (),
              train_every: int = 0, seed: int = 0,
              log_capacity: Optional[int] = 1024,
              engines: Optional[Sequence] = None,
              max_new: int = 8) -> Dict:
    """Drive ``router`` through a storm over ``env``'s replay tables.

    ``env`` is a `DeviceReplayEnv` (feedback = its reward/quality/cost
    tables); ``outages`` are announced ``(arm, start_wave, end_wave)``
    windows, optionally augmented from a sim ``scenario``'s masks;
    ``train_every`` runs `end_slice` every that many waves (0 = never).
    ``engines`` (one per arm, the armpool's semi-real mode) makes the
    serve stage actually execute each request — ``max_new`` generated
    tokens per request — while feedback stays table-replay.
    Returns the metrics dict (the keys below; the presets' artifacts
    carry it under ``serving``).
    """
    reward = np.asarray(env.reward)
    quality = np.asarray(env.quality)
    cost = np.asarray(env.cost)
    n, K = reward.shape
    outages = [(int(a), int(s), int(e)) for a, s, e in outages]
    if scenario is not None:
        outages += outages_from_scenario(scenario, env, waves)
    faults = ScriptedFaults(fail_decide_calls=fail_decide_calls,
                            outages=outages)
    if engines is not None and len(engines) != K:
        raise ValueError(f"run_storm: {len(engines)} engines for "
                         f"{K} arms (one engine per arm)")
    engine = AsyncRouterEngine(
        router, K, engines=engines, reward_table=reward,
        quality_table=quality,
        cost_table=cost, queue_capacity=queue_capacity,
        decide_batch=decide_batch, serve_batch=serve_batch,
        max_new=max_new,
        fault_hook=faults.on_decide, log_capacity=log_capacity)
    sizes = wave_sizes(pattern, requests, waves, seed=seed)
    rng = np.random.default_rng(seed)
    if hasattr(router, "warmup"):
        router.warmup()   # keep jit compiles out of the latency samples

    sum_reward = sum_quality = sum_cost = 0.0
    n_ok = 0
    per_wave_shed = np.zeros(waves, np.int64)
    # slice-boundary train stalls: (index of the first decide call AFTER
    # the stall, stall seconds) — the request-visible decide path waits
    # behind a blocking end_slice, so p99 over walls+stalls is the tail
    # a caller actually sees (the overlap bench compares this)
    stalls: list = []
    t0 = time.perf_counter()
    with CompileCount() as storm_compiles:
        for w in range(waves):
            faults.apply_wave(engine, w)
            ids = rng.integers(0, n, size=int(sizes[w]))
            reqs = [Request(tokens=_TOKENS, sample_idx=int(i)) for i in ids]
            shed0 = (engine.counters["shed_queue_full"]
                     + engine.counters["shed_no_arm"])
            engine.submit(reqs)
            recs = engine.pump()
            recs += engine.drain()
            for r in recs:
                if r["status"] == "ok":
                    n_ok += 1
                    sum_reward += r["reward"]
                    sum_quality += r["quality"]
                    sum_cost += r["cost"]
            per_wave_shed[w] = (engine.counters["shed_queue_full"]
                                + engine.counters["shed_no_arm"]) - shed0
            if train_every and (w + 1) % train_every == 0:
                ts = time.perf_counter()
                engine.end_slice()
                stalls.append((len(engine.decide_wall_s),
                               time.perf_counter() - ts))
    wall = time.perf_counter() - t0
    acct = engine.check_accounting()

    walls_us = np.asarray(engine.decide_wall_s) * 1e6
    path_us = walls_us.copy()
    stall_us = np.asarray([s for _, s in stalls]) * 1e6
    for idx, s in stalls:
        if idx < path_us.size:
            path_us[idx] += s * 1e6
    c = engine.counters
    shed = c["shed_queue_full"] + c["shed_no_arm"]
    return {
        "pattern": pattern, "requests": int(requests), "waves": int(waves),
        "decide_batch": int(decide_batch),
        "outages": [list(o) for o in outages],
        "wall_s": float(wall),
        "requests_per_s": float(c["completed"] / max(wall, 1e-9)),
        "decide_calls": int(c["decide_calls"]),
        "decide_p50_us": float(np.percentile(walls_us, 50))
        if walls_us.size else 0.0,
        "decide_p99_us": float(np.percentile(walls_us, 99))
        if walls_us.size else 0.0,
        "decide_p50_per_req_us": float(
            np.percentile(walls_us, 50) / decide_batch)
        if walls_us.size else 0.0,
        "decide_path_p99_us": float(np.percentile(path_us, 99))
        if path_us.size else 0.0,
        "train_stall_p99_us": float(np.percentile(stall_us, 99))
        if stall_us.size else 0.0,
        "train_stall_total_s": float(stall_us.sum() / 1e6),
        "completed": int(c["completed"]), "shed": int(shed),
        "shed_queue_full": int(c["shed_queue_full"]),
        "shed_no_arm": int(c["shed_no_arm"]),
        "fallbacks": int(c["fallbacks"]),
        "decide_errors": int(c["decide_errors"]),
        "last_decide_error": engine.last_decide_error,
        # XLA compiles inside the storm window (warm-up excluded): a
        # shape the warm-up missed compiles here, on the request path
        "compiles": int(storm_compiles.compiles),
        "learned": int(c["learned"]),
        "skipped_learn": int(c["skipped_learn"]),
        "lost_requests": int(acct["lost"]),
        "max_wave_shed": int(per_wave_shed.max()) if waves else 0,
        "avg_reward": float(sum_reward / max(n_ok, 1)),
        "avg_quality": float(sum_quality / max(n_ok, 1)),
        "avg_cost": float(sum_cost / max(n_ok, 1)),
    }
