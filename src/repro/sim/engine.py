"""Vectorized online protocol engine (DESIGN.md §8, §10).

ONE scan drives every policy: :func:`_policy_scan_impl` runs a full
T-slice protocol — DECIDE → feedback lookup → UPDATE → TRAIN → REBUILD —
for any :class:`repro.sim.policies.BanditPolicy` as a single jitted
``lax.scan`` (one device dispatch for the whole run), with scenarios
(DESIGN.md §9), ``ForgettingConfig`` adaptivity, delayed feedback, and
availability fallback threaded through the shared :class:`PolicyCtx`.

Public runners:

* :func:`run_policy_device` — one policy, all T slices, one dispatch.
* :func:`run_policy_sweep` — a POLICY AXIS of (grid × seed) lane vmaps:
  every policy's lanes are padded to a device-count multiple and
  sharded over a ("grid", "seed") mesh (``launch.mesh.make_sweep_mesh``
  + ``distributed.sharding.sweep_lane_layout``), and ALL policies
  execute inside one jitted dispatch, so a (policy × hypers × seed ×
  scenario) study is one compiled program per scenario.
* :func:`run_baseline_device` / :func:`run_baseline_sweep` — thin
  wrappers lifting legacy :class:`DevicePolicy` triples; the sweep now
  emits the same grid-annotated ``(G, n_seeds, T, ...)`` schema as
  every other policy.
* :func:`run_neuralucb_device` / :func:`run_neuralucb_sweep` — the
  paper's Algorithm 1 through the same runner (NeuralUCB is just the
  richest registered policy); bit-exact with the pre-unification scans
  (tests/test_golden.py).
* :class:`DeviceNeuralUCB` — the host-stepped runner (one fused jit call
  per slice phase), kept as the bit-exact parity reference; its
  ``run()`` delegates to the scanned path when the schedule allows.

Differences vs. the seed host loop (``repro.core.protocol.run_protocol``),
see DESIGN.md §8.3/§8.4: the random baseline and warm-slice exploration
draw from the jax PRNG (numpy's in the seed), and replay training samples
minibatches with replacement (permutation epochs in the seed). Policies
that are deterministic given the reward stream (fixed arms, greedy) are
bit-compatible — asserted by tests/test_sim_engine.py.
"""
from __future__ import annotations

import functools
import itertools
import math
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import neuralucb as NU
from repro.core import utilitynet as UN
from repro.core.policy import default_ucb_backend
from repro.core.reward import normalize_cost
from repro.distributed.sharding import (
    pad_sweep_lanes,
    shard_sweep_axis,  # noqa: F401  (re-export: legacy callers import here)
    shard_sweep_lanes,
    sweep_lane_layout,
)
from repro.kernels.nucb_update import nucb_update
from repro.launch.mesh import make_sweep_mesh
from repro.sim.env import DeviceReplayEnv
from repro.sim.policies import (
    TRAIN_CHUNK,
    VANILLA_FORGETTING,
    BanditPolicy,
    DevicePolicy,
    ForgettingConfig,
    NeuralUCBHypers,
    NeuralUCBState,
    PolicyCtx,
    _decide_ucb,
    _decide_warm,
    _no_train,
    _rebuild_impl,
    _sample_valid,  # noqa: F401  (re-export: tests/benchmarks import here)
    _slice_weights,
    _train_chunk,
    as_bandit_policy,
    neural_init_state,
    neuralucb_policy,
)
from repro.sim.scenarios import ScenarioTables, resolve_scenario


def _tables(env: DeviceReplayEnv) -> Dict[str, jnp.ndarray]:
    """Resident replay tables. ``cnorm`` is the Eq.-1 normalized cost,
    carried so sweep harnesses can re-derive the reward table for any
    ``cost_lambda`` on device (baseline scans simply never read it);
    ``c_max`` / ``env_lambda`` / ``mean_cost`` feed the scenario
    engine's per-slice reward recompute and availability fallback."""
    return {"x_emb": env.x_emb, "x_feat": env.x_feat, "domain": env.domain,
            "quality": env.quality, "cost": env.cost, "reward": env.reward,
            "cnorm": normalize_cost(env.cost, env.cost.max()),
            "c_max": env.cost.max(),
            "env_lambda": jnp.float32(env.cost_lambda),
            "mean_cost": env.cost.mean(axis=0),
            "oracle_max": env.reward.max(axis=1)}


def _context(tables, idx):
    return {"x_emb": tables["x_emb"][idx], "x_feat": tables["x_feat"][idx],
            "domain": tables["domain"][idx]}


def _effective_slice(tables, scn: Optional[ScenarioTables], t, idx, lam):
    """Slice-t effective tables (DESIGN.md §9.1). With no scenario this
    is None — the metrics/feedback paths then use the PR-2 (S,)-gather
    fast path against the resident tables directly (materializing (S, K)
    temporaries per slice measurably regressed the vmapped sweep). With
    a scenario, the declarative per-slice transforms are applied to the
    gathered (S, K) rows and the Eq.-1 reward is re-derived on device
    with the env's stationary C_max (a shocked price may push the
    normalized cost past 1 — that is the point of a shock)."""
    if scn is None:
        return None
    q = jnp.clip(tables["quality"][idx] * scn.quality_mult[t]
                 + scn.quality_add[t], 0.0, 1.0)
    c = tables["cost"][idx] * scn.cost_mult[t]
    r = q * jnp.exp(-lam * normalize_cost(c, tables["c_max"]))
    return {"quality": q, "cost": c, "reward": r, "avail": scn.avail[t]}


def _avail_fallback(a, avail, mean_cost):
    """Engine-level failover for availability-unaware policies: a request
    routed to an unavailable arm falls back to the cheapest available
    arm (deterministic, like production failover to the budget tier)."""
    fb = jnp.argmin(jnp.where(avail > 0, mean_cost, jnp.inf)).astype(
        jnp.int32)
    return jnp.where(avail[a] > 0, a, fb).astype(jnp.int32)


def _pick(tables, eff, key, idx, actions):
    """Chosen-action values (S,): resident-table gather on the
    stationary fast path, effective-table gather under a scenario."""
    if eff is None:
        return tables[key][idx, actions]
    rows = jnp.arange(actions.shape[0], dtype=jnp.int32)
    return eff[key][rows, actions]


def _slice_metrics(tables, eff, idx, mask, actions):
    denom = jnp.maximum(mask.sum(), 1.0)
    r = _pick(tables, eff, "reward", idx, actions) * mask
    q = _pick(tables, eff, "quality", idx, actions) * mask
    c = _pick(tables, eff, "cost", idx, actions) * mask
    K = tables["reward"].shape[1]
    hist = (jax.nn.one_hot(actions, K, dtype=jnp.float32)
            * mask[:, None]).sum(axis=0)
    # dynamic oracle: best AVAILABLE arm per sample under the slice's
    # effective tables (the regret reference, §9.3); precomputed per
    # sample on the stationary path
    if eff is None:
        o = tables["oracle_max"][idx] * mask
    else:
        r_all = eff["reward"]
        if eff["avail"] is not None:
            r_all = jnp.where(eff["avail"] > 0, r_all, -1.0)
        o = r_all.max(axis=1) * mask
    return {"sum_reward": r.sum(), "avg_reward": r.sum() / denom,
            "avg_cost": c.sum() / denom, "avg_quality": q.sum() / denom,
            "action_hist": hist, "oracle_avg_reward": o.sum() / denom}


def _metrics_to_results(ms: Dict[str, np.ndarray]) -> Dict:
    """Convert stacked per-slice device metrics to the
    ``core.protocol.run_protocol`` per-policy result format (a scan
    times no slice on its own, so there is no ``wall_s``)."""
    cum = np.cumsum(np.asarray(ms["sum_reward"], np.float64))
    out = {
        "avg_reward": [float(v) for v in ms["avg_reward"]],
        "cum_reward": [float(v) for v in cum],
        "avg_cost": [float(v) for v in ms["avg_cost"]],
        "avg_quality": [float(v) for v in ms["avg_quality"]],
        "oracle_avg_reward": [float(v) for v in ms["oracle_avg_reward"]],
        "action_hist": np.asarray(ms["action_hist"]),
    }
    if "mean_logp" in ms:
        out["mean_logp"] = [float(v) for v in ms["mean_logp"]]
    return out


def _resolve_lam(tables, hyp):
    """The Eq.-1 lambda driving a scenario's per-slice reward re-derive:
    policies that sweep ``cost_lambda`` (the neural hypers pytrees) use
    it when non-negative; everything else replays the env's own."""
    cl = getattr(hyp, "cost_lambda", None)
    if cl is None:
        return tables["env_lambda"]
    return jnp.where(cl >= 0, jnp.abs(cl), tables["env_lambda"])


# ----------------------------------------------- THE protocol scan (§10) --
def _policy_scan_impl(tables, xs, env_idx, cum0, key, hyp,
                      policy: BanditPolicy,
                      scn: Optional[ScenarioTables] = None, delay: int = 0,
                      fcfg: ForgettingConfig = VANILLA_FORGETTING,
                      train_chunks: int = 1, batch_size: int = 256,
                      init_state: Any = None, record_log: bool = False):
    """The single protocol scan driving every registered policy: one
    whole T-slice run as a pure ``lax.scan`` over (state, key). Key
    discipline is fixed by the runner — one split per slice feeds
    ``decide``; ``train`` splits further from the carried stream — so
    every policy (and the host-stepped NeuralUCB reference) consumes an
    identical PRNG stream for identical schedules.

    ``init_state`` injects a PRETRAINED state pytree (DESIGN.md §13.3):
    ``policy.init`` still runs — its key fold fixes the run stream, so a
    warm and a cold run differ only by state, never by PRNG — and its
    state is then replaced. ``record_log`` (static) additionally stacks
    the per-slice (action, log-propensity, realized reward) into the
    metrics pytree so the runner can shape a
    :class:`repro.data.logged.LoggedInteractions` from the run."""
    if scn is None:
        # stationary: pre-derive the whole reward table once per run;
        # scenario runs re-derive per slice inside _effective_slice
        tables = policy.prepare(tables, hyp)
    lam = _resolve_lam(tables, hyp)
    ctx0 = PolicyCtx(tables=tables, env_idx=env_idx, cum0=cum0, hyp=hyp,
                     eff=None, t=None, idx=None, mask=None, avail=None,
                     delay=delay, fcfg=fcfg, train_chunks=train_chunks,
                     batch_size=batch_size)
    state, key = policy.init(key, ctx0)
    if init_state is not None:
        state = init_state

    def step(carry, x):
        state, key = carry
        key, k_slice = jax.random.split(key)
        t, idx, mask = x["t"], x["idx"], x["mask"]
        eff = _effective_slice(tables, scn, t, idx, lam)
        batch = _context(tables, idx)
        avail = None if eff is None else eff["avail"]
        ctx = ctx0._replace(eff=eff, t=t, idx=idx, mask=mask, avail=avail)
        with jax.named_scope("router.decide"):
            a, logp, aux = policy.decide(state, k_slice, batch, ctx)
        if not policy.availability_aware and avail is not None:
            a = _avail_fallback(a, avail, tables["mean_cost"])
        m = _slice_metrics(tables, eff, idx, mask, a)
        r = _pick(tables, eff, "reward", idx, a)
        m["mean_logp"] = (logp * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        if record_log:
            m["action"], m["logp"], m["reward"] = a, logp, r
        with jax.named_scope("router.update"):
            state = policy.update(state, batch, a, r, ctx, aux)
        with jax.named_scope("router.train_sgd"):
            state, key = policy.train(state, key, ctx)
        with jax.named_scope("router.rebuild"):
            state = policy.rebuild(state, ctx)
        return (state, key), m

    return jax.lax.scan(step, (state, key), xs)


_STATIC = ("policy", "delay", "fcfg", "train_chunks", "batch_size",
           "record_log")

_policy_scan = jax.jit(_policy_scan_impl, static_argnames=_STATIC)


@functools.partial(
    jax.jit, static_argnames=("policies", "delay", "fcfg", "train_chunks",
                              "batch_size", "mesh"))
def _policy_zoo_scan(tables, xs, env_idx, cum0, keys_tup, hyp_tup,
                     policies: Tuple[BanditPolicy, ...], scn=None,
                     delay: int = 0,
                     fcfg: ForgettingConfig = VANILLA_FORGETTING,
                     train_chunks: int = 1, batch_size: int = 256,
                     init_tup: Any = None, mesh=None):
    """The POLICY AXIS: every policy's (grid x seed) lane vmap, compiled
    and executed as ONE jitted dispatch. Per policy, ``keys`` (L, 2) and
    every hyp leaf (L,) are pre-flattened by the caller into one lane
    axis (lane l = (g, s), g = l // n_seeds) — a single batching axis
    compiles to markedly better CPU code than nested grid/seed vmaps and
    gives the device sharding one unambiguous axis. Policies carry
    heterogeneous state/hypers pytrees, so the policy axis is a static
    tuple (each member its own vmapped scan inside the one program)
    rather than one more vmap dimension — what stays uniform is the lane
    schema, the sharding, and the (G, n_seeds, T, ...) result layout.
    Scenario transforms are broadcast, not vmapped: every lane replays
    the same drift (one resident copy of the transform tables).

    With a multi-device ``mesh`` the lane vmap runs under ``shard_map``:
    each device scans its own lane shard. The compiler cannot partition
    a Pallas kernel across devices by itself, and lanes never talk to
    each other, so nothing crosses devices until the results return."""
    out = []
    for i, p in enumerate(policies):
        # a pretrained init state (one per policy) is CLOSED OVER, so it
        # broadcasts across the lane vmap instead of growing a lane axis
        ist = None if init_tup is None else init_tup[i]

        def one(k, h, p=p, ist=ist):
            return _policy_scan_impl(tables, xs, env_idx, cum0, k, h, p,
                                     scn, delay, fcfg, train_chunks,
                                     batch_size, init_state=ist)[1]
        lanes = jax.vmap(one)
        if mesh is not None and mesh.devices.size > 1:
            spec = P(("grid", "seed"))
            lanes = jax.shard_map(lanes, mesh=mesh, in_specs=(spec, spec),
                                  out_specs=spec, check_vma=False)
        out.append(lanes(keys_tup[i], hyp_tup[i]))
    return tuple(out)


def _cum_valid(env: DeviceReplayEnv) -> jnp.ndarray:
    """(T+1,) int32 cumulative VALID sample counts: cum0[t+1] = number of
    real (unpadded) samples in slices 0..t — the searchsorted table for
    ``policies._sample_valid`` and the training-budget base."""
    sizes = np.asarray(env.slice_sizes, np.int64)
    return jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)


def neuralucb_train_schedule(env: DeviceReplayEnv, epochs: int = 5,
                             batch_size: int = 256,
                             max_slices: Optional[int] = None) -> int:
    """Fixed per-slice SGD budget (steps) for the scanned runner.

    The host-stepped growing schedule spends ``epochs * (seen_t //
    batch)`` steps after slice t (rounded up to TRAIN_CHUNK dispatches);
    the scan needs ONE static budget for every slice, so we spread the
    growing schedule's total chunk count evenly (rounded up) — same total
    compute to within T chunks, uniform trace.
    """
    sizes = np.asarray(env.slice_sizes, np.int64)
    if max_slices is not None:
        sizes = sizes[:max_slices]
    seen = np.cumsum(sizes)
    chunks = [-(-int(epochs * (s // batch_size)) // TRAIN_CHUNK)
              for s in seen]
    per_slice = max(1, -(-sum(chunks) // len(chunks)))
    return per_slice * TRAIN_CHUNK


def _chunks_for(env: DeviceReplayEnv, policy: BanditPolicy,
                train_steps: Optional[int], epochs: int,
                batch_size: int) -> int:
    """TRAIN_CHUNK dispatches per slice. Policies without a train hook
    get the canonical 1 (the value is a static jit arg — pinning it
    avoids gratuitous retraces across differently-scheduled calls)."""
    if policy.train is _no_train:
        return 1
    if train_steps is None:
        train_steps = neuralucb_train_schedule(env, epochs, batch_size)
    return -(-int(train_steps) // TRAIN_CHUNK)


def run_policy_device(env: DeviceReplayEnv, policy: BanditPolicy,
                      hypers: Any = (), *, seed: int = 0, scenario=None,
                      forgetting: ForgettingConfig = VANILLA_FORGETTING,
                      train_steps: Optional[int] = None, epochs: int = 5,
                      batch_size: int = 256, return_state: bool = False,
                      init_state: Any = None, record_log: bool = False):
    """Any registered policy, all T slices, ONE device dispatch.

    ``hypers`` is the policy's scalar hypers pytree (see
    ``repro.sim.policies.make_policy``); ``scenario`` (name | Scenario |
    None) applies the DESIGN.md §9 non-stationary transforms inside the
    same single scan; ``forgetting`` selects the §9.2 adaptivity variant;
    ``train_steps`` / ``epochs`` set the per-slice replay-SGD budget for
    policies with a train hook. ``init_state`` injects a pretrained state
    (:func:`pretrain_policy_state`); ``record_log`` also returns the
    run's propensity-annotated :class:`~repro.data.logged
    .LoggedInteractions`. Returns the ``run_protocol`` per-policy result
    dict; with ``record_log=True`` ``(res, logged)``; with
    ``return_state=True`` additionally ``state, key`` appended."""
    from repro.data.logged import from_run_log
    env, scn, delay = resolve_scenario(env, scenario)
    chunks = _chunks_for(env, policy, train_steps, epochs, batch_size)
    (state, key), ms = _policy_scan(
        _tables(env), env.slice_xs(), env.idx, _cum_valid(env),
        jax.random.PRNGKey(seed), hypers, policy, scn, delay, forgetting,
        chunks, batch_size, init_state, record_log)
    jax.block_until_ready(ms)
    ms = {k: np.asarray(v) for k, v in ms.items()}
    log = {k: ms.pop(k) for k in ("action", "logp", "reward")
           if k in ms}
    res = _metrics_to_results(ms)
    extras = []
    if record_log:
        extras.append(from_run_log(env, log, behavior=policy.name))
    if return_state:
        extras.extend([state, key])
    return (res, *extras) if extras else res


# ------------------------------------------- offline pretraining (§13.3) --
@functools.partial(
    jax.jit, static_argnames=("policy", "fcfg", "train_chunks",
                              "batch_size", "pretrain_steps"))
def _pretrain_impl(tables, env_idx, cum0, key, hyp, logged,
                   policy: BanditPolicy,
                   fcfg: ForgettingConfig = VANILLA_FORGETTING,
                   train_chunks: int = 1, batch_size: int = 256,
                   pretrain_steps: int = 0):
    """prepare -> init -> pretrain as one jitted dispatch: the offline
    phase of the lifecycle, producing the state the online scan starts
    from."""
    tables = policy.prepare(tables, hyp)
    ctx = PolicyCtx(tables=tables, env_idx=env_idx, cum0=cum0, hyp=hyp,
                    eff=None, t=None, idx=None, mask=None, avail=None,
                    delay=0, fcfg=fcfg, train_chunks=train_chunks,
                    batch_size=batch_size, pretrain_steps=pretrain_steps)
    state, key = policy.init(key, ctx)
    state, _ = policy.pretrain(state, key, logged, ctx)
    return state


def pretrain_policy_state(env: DeviceReplayEnv, policy: BanditPolicy,
                          hypers: Any = (), logged=None, *, seed: int = 0,
                          steps: int = 512, batch_size: int = 256,
                          forgetting: ForgettingConfig = VANILLA_FORGETTING):
    """Run a policy's OFFLINE phase on a logged corpus (DESIGN.md §13.3).

    ``logged`` is a :class:`repro.data.logged.LoggedInteractions`;
    ``steps`` is the offline SGD budget (``PolicyCtx.pretrain_steps`` —
    the ridge folds ignore it, they consume the whole corpus). Returns
    the pretrained state pytree, injectable into the online scan via
    ``run_policy_device(init_state=...)`` /
    ``run_policy_sweep(init_states={name: ...})`` — warm and cold runs
    then share an identical PRNG stream and differ only by this state."""
    if logged is None:
        raise ValueError("pretrain_policy_state: a LoggedInteractions "
                         "corpus is required")
    return _pretrain_impl(_tables(env), env.idx, _cum_valid(env),
                          jax.random.PRNGKey(seed), hypers,
                          logged.to_device(), policy, forgetting, 1,
                          batch_size, int(steps))


def _grid_size(hypers: Any) -> int:
    leaves = jax.tree.leaves(hypers)
    sizes = [int(l.shape[0]) for l in map(jnp.asarray, leaves)
             if jnp.ndim(l) >= 1]
    if sizes and len(set(sizes)) > 1:
        raise ValueError(f"ragged hypers grid: leaf sizes {sorted(set(sizes))}")
    return sizes[0] if sizes else 1


def _flatten_lanes(hypers: Any, G: int, n_seeds: int):
    """Broadcast scalar leaves to (G,), then repeat each grid point per
    seed — lane l = (g, s) with g = l // n_seeds, s = l % n_seeds."""
    def lane(l):
        l = jnp.asarray(l)
        if jnp.ndim(l) == 0:
            l = jnp.broadcast_to(l, (G,))
        return jnp.repeat(l, n_seeds, axis=0)
    return jax.tree.map(lane, hypers)


def run_policy_sweep(env: DeviceReplayEnv,
                     policies: Dict[str, Tuple[BanditPolicy, Any]], *,
                     seeds: Sequence[int], scenario=None,
                     forgetting: ForgettingConfig = VANILLA_FORGETTING,
                     train_steps: Optional[int] = None, epochs: int = 5,
                     batch_size: int = 256,
                     init_states: Optional[Dict[str, Any]] = None,
                     n_devices: Optional[int] = None
                     ) -> Dict[str, Dict]:
    """(policy × hypers × seed) study as ONE sharded device dispatch.

    ``policies`` maps name -> (BanditPolicy, hypers_grid) where each
    hypers_grid leaf is a scalar (broadcast) or a (G,) array of grid
    points (G may differ per policy). Every policy's (G x n_seeds) lane
    axis is sharded across local devices, and all policies run inside
    one jitted program (``_policy_zoo_scan``). ``init_states`` maps
    name -> pretrained state pytree (:func:`pretrain_policy_state`) —
    one state per policy, broadcast across its lanes. ``n_devices`` caps
    the local devices the lanes shard over (None = all): sharding never
    changes a lane's result, so 1 gives the one-device reference.

    Returns {name: sweep} in the unified annotated schema: metric leaves
    (G, n_seeds, T, ...), plus ``seeds``, ``train_steps``, ``grid``
    (each hypers field as a (G,) array), and ``layout`` (the lane→device
    manifest, :meth:`SweepLaneLayout.manifest`) — every cell feeds
    ``core.protocol.summarize`` via :func:`sweep_point_results`, and the
    whole sweep feeds ``core.protocol.summarize_sweep``.

    Device layout (DESIGN.md §14.3): every policy's lane axis is PADDED
    with dead lanes (broadcast copies of lane 0) up to a device-count
    multiple and sharded over a ("grid", "seed") mesh — all local
    devices always participate, where the legacy ``shard_sweep_axis``
    silently fell back toward 1 device on non-dividing lane counts. Dead
    lanes are sliced off before results leave this function."""
    seeds = list(seeds)
    n_seeds = len(seeds)
    env, scn, delay = resolve_scenario(env, scenario)
    any_train = any(p.train is not _no_train for p, _ in policies.values())
    if train_steps is None and any_train:
        train_steps = neuralucb_train_schedule(env, epochs, batch_size)
    chunks = -(-int(train_steps) // TRAIN_CHUNK) if any_train else 1
    base_keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    gsizes = [_grid_size(grid) for _, grid in policies.values()]
    mesh = make_sweep_mesh(functools.reduce(math.gcd, gsizes, 0) or 1,
                           n_seeds, n_devices)
    names, pols, keys_t, hyp_t, grids, layouts = [], [], [], [], [], []
    for (name, (pol, grid)), G in zip(policies.items(), gsizes):
        hyp = _flatten_lanes(grid, G, n_seeds)
        keys = jnp.tile(base_keys, (G, 1))
        layout = sweep_lane_layout(G * n_seeds, mesh)
        keys, hyp = pad_sweep_lanes((keys, hyp), layout.pad)
        keys, hyp = shard_sweep_lanes((keys, hyp), mesh)
        names.append(name)
        pols.append(pol)
        keys_t.append(keys)
        hyp_t.append(hyp)
        grids.append(grid)
        layouts.append(layout)
    init_tup = None
    if init_states:
        init_tup = tuple(init_states.get(n) for n in names)
    ms_t = _policy_zoo_scan(_tables(env), env.slice_xs(), env.idx,
                            _cum_valid(env), tuple(keys_t), tuple(hyp_t),
                            tuple(pols), scn, delay, forgetting, chunks,
                            batch_size, init_tup=init_tup, mesh=mesh)
    out = {}
    for name, pol, G, grid, layout, ms in zip(names, pols, gsizes, grids,
                                              layouts, ms_t):
        # dead pad lanes are dropped HERE, before any consumer
        # (sweep_point_results / summarize_sweep) can see them
        d = {k: np.asarray(v)[:layout.n_lanes].reshape(
                 (G, n_seeds) + v.shape[1:])
             for k, v in ms.items()}
        d["seeds"] = np.asarray(seeds)
        # annotate the steps that actually RAN: a sweep of train-less
        # policies executes zero SGD steps whatever the caller requested
        d["train_steps"] = np.asarray(
            chunks * TRAIN_CHUNK if pol.train is not _no_train else 0)
        d["grid"] = {
            f: np.asarray(jnp.broadcast_to(jnp.asarray(v), (G,)))
            for f, v in (zip(grid._fields, grid)
                         if hasattr(grid, "_fields") else ())}
        d["layout"] = layout.manifest()
        out[name] = d
    return out


# --------------------------------------------------------------- baselines --
def run_baseline_device(env: DeviceReplayEnv, policy, *, seed: int = 0,
                        scenario=None) -> Dict:
    """One baseline, all T slices, one device dispatch, via the unified
    runner (``policy`` may be a legacy :class:`DevicePolicy` triple or a
    :class:`BanditPolicy`). Returns the ``run_protocol`` per-policy
    result dict (summarize-compatible)."""
    if isinstance(policy, DevicePolicy):
        policy = as_bandit_policy(policy)
    return run_policy_device(env, policy, (), seed=seed, scenario=scenario)


def run_baseline_sweep(env: DeviceReplayEnv, policy, seeds,
                       scenario=None) -> Dict[str, np.ndarray]:
    """Multi-seed baseline sweep through the unified sweep runner.

    Returns the same grid-annotated schema as every policy sweep: metric
    leaves have shape (G=1, n_seeds, T, ...) plus ``seeds`` — a cell
    feeds ``summarize`` via :func:`sweep_point_results`."""
    if isinstance(policy, DevicePolicy):
        policy = as_bandit_policy(policy)
    return run_policy_sweep(env, {policy.name: (policy, ())},
                            seeds=seeds, scenario=scenario)[policy.name]


# --------------------------------------------------------------- neuralucb --
def _hypers(beta, tau_g, gate_margin, lr, ridge_lambda0,
            cost_lambda) -> NeuralUCBHypers:
    f = jnp.float32
    return NeuralUCBHypers(
        beta=f(beta), tau_g=f(tau_g), gate_margin=f(gate_margin), lr=f(lr),
        ridge_lambda0=f(ridge_lambda0),
        cost_lambda=f(-1.0 if cost_lambda is None else cost_lambda))


def run_neuralucb_device(env: DeviceReplayEnv, cfg: UN.UtilityNetConfig, *,
                         seed: int = 0, epochs: int = 5,
                         train_steps: Optional[int] = None,
                         beta: float = 1.0, tau_g: float = 0.5,
                         ridge_lambda0: float = 1.0, lr: float = 1e-3,
                         gate_margin: float = 0.05, batch_size: int = 256,
                         cost_lambda: Optional[float] = None,
                         ucb_backend: Optional[str] = None,
                         scenario=None,
                         forgetting: ForgettingConfig = VANILLA_FORGETTING,
                         return_state: bool = False):
    """Algorithm 1 end to end as ONE device dispatch (DESIGN.md §8.4) —
    the registered ``neuralucb`` policy on the unified runner.

    ``train_steps`` is the fixed per-slice SGD budget (rounded up to a
    TRAIN_CHUNK multiple); when omitted it is derived from ``epochs`` via
    :func:`neuralucb_train_schedule` to match the stepped runner's total
    budget. ``scenario`` (name | Scenario | None) applies the DESIGN.md
    §9 non-stationary transforms inside the same single scan;
    ``forgetting`` selects the adaptivity variant (§9.2). Returns the
    ``run_protocol`` per-policy result dict; with ``return_state=True``
    also the final :class:`NeuralUCBState`.
    """
    backend = ucb_backend or default_ucb_backend()
    policy = neuralucb_policy(cfg, backend)
    hyp = _hypers(beta, tau_g, gate_margin, lr, ridge_lambda0, cost_lambda)
    out = run_policy_device(env, policy, hyp, seed=seed, scenario=scenario,
                            forgetting=forgetting, train_steps=train_steps,
                            epochs=epochs, batch_size=batch_size,
                            return_state=return_state)
    if not return_state:
        return out
    res, state, key = out
    return res, NeuralUCBState(params=state["params"], opt=state["opt"],
                               ainv=state["ainv"], bufs=state["bufs"],
                               key=key)


def run_neuralucb_sweep(env: DeviceReplayEnv, cfg: UN.UtilityNetConfig, *,
                        seeds: Sequence[int], betas=(1.0,), tau_gs=(0.5,),
                        cost_lambdas=(None,), epochs: int = 5,
                        train_steps: Optional[int] = None,
                        ridge_lambda0: float = 1.0, lr: float = 1e-3,
                        gate_margin: float = 0.05, batch_size: int = 256,
                        ucb_backend: str = "jnp", scenario=None,
                        forgetting: ForgettingConfig = VANILLA_FORGETTING
                        ) -> Dict[str, np.ndarray]:
    """Multi-seed, multi-hyper NeuralUCB sweep as one dispatch.

    The hyper grid is the cartesian product ``betas x tau_gs x
    cost_lambdas`` (G points, ``itertools.product`` order, recorded in the
    returned ``beta`` / ``tau_g`` / ``cost_lambda`` arrays); metric leaves
    come back with shape (G, n_seeds, T, ...). The flattened (grid x
    seed) lane axis is padded to a device-count multiple and sharded
    over the ("grid", "seed") sweep mesh. The default UCB backend is the
    portable jnp path; ``ucb_backend="pallas"`` routes DECIDE through
    the fused decide kernel and REBUILD through the blocked-Cholesky
    kernel (`repro.kernels`) — off-TPU these self-dispatch to their jnp
    references, so the option is safe (if slower to trace) under the
    sweep vmap everywhere.
    """
    grid = list(itertools.product(betas, tau_gs, cost_lambdas))
    G = len(grid)
    f = functools.partial(jnp.asarray, dtype=jnp.float32)
    hyp_grid = NeuralUCBHypers(
        beta=f([b for b, _, _ in grid]),
        tau_g=f([t for _, t, _ in grid]),
        gate_margin=jnp.full((G,), gate_margin, jnp.float32),
        lr=jnp.full((G,), lr, jnp.float32),
        ridge_lambda0=jnp.full((G,), ridge_lambda0, jnp.float32),
        cost_lambda=f([-1.0 if l is None else l for _, _, l in grid]))
    out = run_policy_sweep(
        env, {"neuralucb": (neuralucb_policy(cfg, ucb_backend), hyp_grid)},
        seeds=seeds, scenario=scenario, forgetting=forgetting,
        train_steps=train_steps, epochs=epochs,
        batch_size=batch_size)["neuralucb"]
    # legacy flat annotations (the grid subdict carries the same data)
    out["beta"] = np.asarray([b for b, _, _ in grid], np.float32)
    out["tau_g"] = np.asarray([t for _, t, _ in grid], np.float32)
    out["cost_lambda"] = np.asarray(
        [np.nan if l is None else l for _, _, l in grid], np.float32)
    return out


def sweep_point_results(sweep: Dict[str, np.ndarray], g: int,
                        s: int) -> Dict:
    """Extract one (grid point, seed) run from ANY policy's annotated
    sweep as a ``run_protocol`` per-policy result dict, so sweep cells
    feed ``repro.core.protocol.summarize`` unchanged."""
    cum = np.cumsum(np.asarray(sweep["sum_reward"][g, s], np.float64))
    out = {
        "avg_reward": [float(v) for v in sweep["avg_reward"][g, s]],
        "cum_reward": [float(v) for v in cum],
        "avg_cost": [float(v) for v in sweep["avg_cost"][g, s]],
        "avg_quality": [float(v) for v in sweep["avg_quality"][g, s]],
        "oracle_avg_reward": [float(v)
                              for v in sweep["oracle_avg_reward"][g, s]],
        "action_hist": np.asarray(sweep["action_hist"][g, s]),
    }
    if "mean_logp" in sweep:
        out["mean_logp"] = [float(v) for v in sweep["mean_logp"][g, s]]
    return out


# -------------------------------------------- host-stepped parity runner --
@functools.partial(jax.jit, static_argnames=("cfg", "backend", "warm"),
                   donate_argnames=("ainv", "bufs"))
def _nucb_slice_step(params, ainv, tables, bufs, t, idx, mask, key,
                     beta, tau_g, gate_margin,
                     cfg: UN.UtilityNetConfig, backend: str, warm: bool):
    """DECIDE -> feedback lookup -> buffer write -> rank-k UPDATE, fused.
    Host-stepped entry point: ``warm`` is static (one trace per phase).
    Stationary tables only — scenarios are a scanned-runner feature.
    A^-1 and the ring buffers are donated — the caller threads them
    through every slice and never reads the stale copy, so XLA updates
    them in place instead of double-buffering (F, F) + (T, S) state."""
    batch = _context(tables, idx)
    if warm:
        a, _, g, mu_safe, gs = _decide_warm(params, batch, key, cfg)
    else:
        a, _, g, mu_safe, gs = _decide_ucb(params, ainv, batch, beta,
                                           tau_g, cfg, backend)
    r = _pick(tables, None, "reward", idx, a)
    gate_label = (r < mu_safe - gate_margin).astype(jnp.float32)
    bufs = {
        "action": bufs["action"].at[t].set(a),
        "reward": bufs["reward"].at[t].set(r),
        "gate_label": bufs["gate_label"].at[t].set(gate_label),
        "w": bufs["w"].at[t].set(mask),
        "gate_w": bufs["gate_w"].at[t].set(mask * gs),
    }
    # padded rows are zeroed -> contribute nothing to the rank-k update
    if backend == "pallas":
        ainv = nucb_update(ainv, g * mask[:, None])
    else:
        ainv = NU.woodbury_update(ainv, g * mask[:, None])
    return ainv, bufs, _slice_metrics(tables, None, idx, mask, a)


# params/opt are donated: the stepped runner overwrites its references
# with the returned leaves, so the pre-step weights and AdamW moments
# never need to coexist with the post-step ones in HBM.
_nucb_train = jax.jit(
    _train_chunk,
    static_argnames=("cfg", "num_steps", "batch_size", "fcfg", "delayed"),
    donate_argnames=("params", "opt"))

# NOT donated: every input (params, buffers, tables) outlives the call —
# the rebuild reads the replay buffers it does not own (DESIGN.md §15).
_nucb_rebuild = jax.jit(_rebuild_impl, static_argnames=("cfg", "backend"))


class DeviceNeuralUCB:
    """Host-stepped NeuralUCB protocol runner (paper Algorithm 1).

    Same hyperparameters as :class:`repro.core.policy.NeuralUCBRouter`;
    the replay buffer is (T, S) device arrays of outcomes keyed by the
    env's slice-index matrix, so buffered contexts are looked up from the
    resident tables instead of being copied.

    This is the parity reference for the single-dispatch scanned path
    (:func:`run_neuralucb_device`): ~ceil(steps/TRAIN_CHUNK)+2 dispatches
    and one sync per slice, identical math. ``run()`` delegates to the
    scanned path when the schedule allows (fixed ``train_steps``, full
    stream, fresh state); pass ``scan=False`` to force stepping."""

    def __init__(self, env: DeviceReplayEnv, cfg: UN.UtilityNetConfig, *,
                 seed: int = 0, beta: float = 1.0, tau_g: float = 0.5,
                 ridge_lambda0: float = 1.0, lr: float = 1e-3,
                 gate_margin: float = 0.05, batch_size: int = 256,
                 ucb_backend: Optional[str] = None,
                 forgetting: ForgettingConfig = VANILLA_FORGETTING):
        self.env = env
        self.cfg = cfg
        self.seed = seed
        self.beta = beta
        self.tau_g = tau_g
        self.ridge_lambda0 = ridge_lambda0
        self.lr = lr
        self.gate_margin = gate_margin
        self.batch_size = batch_size
        self.forgetting = forgetting
        self.ucb_backend = ucb_backend or default_ucb_backend()
        T, S = env.idx.shape
        # same split discipline as the unified runner's neural init:
        # split[0] -> network init, split[1] -> run stream (the PR-1
        # runner fed PRNGKey(seed) to both, correlating warm-slice
        # exploration with the weight init)
        state, key = neural_init_state(jax.random.PRNGKey(seed), cfg, T, S,
                                       ridge_lambda0)
        self.params, self.opt = state["params"], state["opt"]
        self.ainv, self.bufs, self.key = state["ainv"], state["bufs"], key
        self._cum0 = _cum_valid(env)
        self._stepped = False   # True once run() has mutated state host-side

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def run(self, *, epochs: int = 5, verbose: bool = False,
            max_slices: Optional[int] = None,
            train_steps: Optional[int] = None, scan="auto") -> Dict:
        """Run Algorithm 1 end to end; returns the ``run_protocol``
        per-policy result dict (summarize-compatible).

        ``train_steps`` fixes the per-slice SGD budget (scanned-runner
        schedule); without it the budget grows with the buffer
        (``epochs * (seen // batch)``, the seed-loop schedule), which only
        the stepped path can express. ``scan="auto"`` delegates to the
        single-dispatch scanned runner whenever the schedule allows —
        fixed ``train_steps``, full stream, state untouched by a previous
        stepped run; ``scan=False`` forces stepping (parity reference)."""
        can_scan = (train_steps is not None and max_slices is None
                    and not self._stepped)
        if scan is True and not can_scan:
            raise ValueError(
                "scan=True requires a fixed train_steps schedule, "
                "max_slices=None, and state untouched by a stepped run")
        if scan is not False and can_scan:
            return self._run_scanned(train_steps, verbose)
        return self._run_stepped(epochs=epochs, verbose=verbose,
                                 max_slices=max_slices,
                                 train_steps=train_steps)

    def _run_scanned(self, train_steps: int, verbose: bool) -> Dict:
        res, state = run_neuralucb_device(
            self.env, self.cfg, seed=self.seed, train_steps=train_steps,
            beta=self.beta, tau_g=self.tau_g,
            ridge_lambda0=self.ridge_lambda0, lr=self.lr,
            gate_margin=self.gate_margin, batch_size=self.batch_size,
            ucb_backend=self.ucb_backend, forgetting=self.forgetting,
            return_state=True)
        self.params, self.opt = state.params, state.opt
        self.ainv, self.bufs, self.key = state.ainv, state.bufs, state.key
        self._stepped = True
        if verbose:
            T = len(res["avg_reward"])
            for t, v in enumerate(res["avg_reward"]):
                print(f"[sim slice {t + 1:2d}/{T}] avg_reward={v:.3f}",
                      flush=True)
        return res

    def _run_stepped(self, *, epochs: int, verbose: bool,
                     max_slices: Optional[int],
                     train_steps: Optional[int]) -> Dict:
        env = self.env
        self._stepped = True
        tables = _tables(env)
        T = env.n_slices if max_slices is None else min(env.n_slices,
                                                        max_slices)
        per_slice = []
        wall = []
        for t in range(T):
            t0 = time.perf_counter()
            self.ainv, self.bufs, m = _nucb_slice_step(
                self.params, self.ainv, tables, self.bufs,
                jnp.int32(t), env.idx[t], env.mask[t], self._next_key(),
                jnp.float32(self.beta), jnp.float32(self.tau_g),
                jnp.float32(self.gate_margin),
                self.cfg, self.ucb_backend, t == 0)
            # valid samples observed so far — the sampling range AND the
            # growing-schedule budget base (was the padded (t+1)*S range)
            count = self._cum0[t + 1]
            if train_steps is not None:
                num_steps = int(train_steps)
            else:
                num_steps = epochs * (int(count) // self.batch_size)
            # round the step budget up to TRAIN_CHUNK-sized dispatches:
            # as a static jit arg each distinct value would recompile the
            # whole training scan
            for _ in range(-(-num_steps // TRAIN_CHUNK)):
                self.params, self.opt = _nucb_train(
                    self.params, self.opt, tables, env.idx, self.bufs,
                    self._next_key(), self._cum0, count,
                    jnp.float32(self.lr), self.cfg, TRAIN_CHUNK,
                    self.batch_size, jnp.int32(t), self.forgetting, False)
            row_w = None if self.forgetting.is_vanilla else _slice_weights(
                env.idx.shape[0], jnp.int32(t), 0, self.forgetting)
            self.ainv = _nucb_rebuild(
                self.params, tables, env.idx, self.bufs["action"],
                self.bufs["w"], self.cfg, jnp.float32(self.ridge_lambda0),
                row_w, backend=self.ucb_backend)
            jax.block_until_ready(self.ainv)
            per_slice.append(m)
            wall.append(time.perf_counter() - t0)
            if verbose:
                print(f"[sim slice {t + 1:2d}/{T}] "
                      f"avg_reward={float(m['avg_reward']):.3f}", flush=True)
        ms = {k: np.stack([np.asarray(m[k]) for m in per_slice])
              for k in per_slice[0]}
        out = _metrics_to_results(ms)
        out["wall_s"] = wall
        return out


def run_protocol_device(env: DeviceReplayEnv,
                        policies: Dict[str, Any], *,
                        neuralucb: Optional[DeviceNeuralUCB] = None,
                        epochs: int = 5, seed: int = 0,
                        verbose: bool = False,
                        scenario=None) -> Dict[str, Dict]:
    """Drop-in device-resident counterpart of
    ``repro.core.protocol.run_protocol``: every policy (legacy
    :class:`DevicePolicy` triples and unified :class:`BanditPolicy`
    members alike) replays the same slice stream (and the same scenario
    drift, when one is named); results feed
    ``repro.core.protocol.summarize``.

    Scheduling caveat: with ``scenario=None`` the NeuralUCB leg is
    ``neuralucb.run(epochs=...)`` — the stepped growing schedule (or its
    scan delegation). With a scenario — INCLUDING the named
    ``"stationary"`` — it is the scanned runner with the fixed
    epochs-derived schedule (a scan cannot express a growing budget,
    DESIGN.md §8.4), so the two calls are not sample-identical; the
    byte-identical stationary contract holds at the
    ``run_neuralucb_device`` / ``run_baseline_device`` level."""
    results = {}
    if neuralucb is not None:
        if scenario is not None:
            results["neuralucb"] = run_neuralucb_device(
                env, neuralucb.cfg, seed=neuralucb.seed,
                epochs=epochs, beta=neuralucb.beta, tau_g=neuralucb.tau_g,
                ridge_lambda0=neuralucb.ridge_lambda0, lr=neuralucb.lr,
                gate_margin=neuralucb.gate_margin,
                batch_size=neuralucb.batch_size,
                ucb_backend=neuralucb.ucb_backend,
                forgetting=neuralucb.forgetting, scenario=scenario)
            if verbose:
                r = results["neuralucb"]["avg_reward"]
                name = getattr(scenario, "name", scenario)
                print(f"[sim] neuralucb ({name}): avg_reward="
                      f"{np.mean(r):.3f}", flush=True)
        else:
            results["neuralucb"] = neuralucb.run(epochs=epochs,
                                                 verbose=verbose)
    for name, pol in policies.items():
        results[name] = run_baseline_device(env, pol, seed=seed,
                                            scenario=scenario)
        if verbose:
            print(f"[sim] {name}: avg_reward="
                  f"{np.mean(results[name]['avg_reward']):.3f}", flush=True)
    return results
