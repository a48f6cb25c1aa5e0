"""Declarative experiment specification (DESIGN.md §11).

An :class:`ExperimentSpec` is a frozen, JSON-round-trippable description
of a full (policy × scenario × hyper × seed) study: the replay data
source, the policy list (each with optional hyper-grid axes, builder
overrides, and a per-entry forgetting variant), the scenario list, the
seed list, the train schedule, and the summarize options. It is the ONE
input every consumer shares — the paper driver
(``scripts/run_paper_experiments.py``), the protocol benchmarks, CI
smokes, and the parity tests all express their runs as specs, so a new
scenario / policy / grid axis is a spec edit, not four parallel script
edits.

The spec layer is deliberately dumb: no registry lookups, no jax — just
typed fields, cheap invariant checks, and a strict JSON codec
(:func:`spec_to_json` / :func:`spec_from_json`; unknown keys are
rejected, round-trips are identity). Registry resolution and grouping
into device dispatches live in :mod:`repro.experiments.compiler`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Optional, Tuple

SPEC_SCHEMA_VERSION = "experiment-spec-v1"


@dataclasses.dataclass(frozen=True)
class DataSpec:
    """RouterBench-surrogate replay source (DESIGN.md §5)."""

    seed: int = 0
    n_samples: int = 36_497
    n_slices: int = 20
    cost_lambda: float = 1.0

    def __post_init__(self):
        if self.n_samples <= 0 or self.n_slices <= 0:
            raise ValueError("DataSpec: n_samples and n_slices must be "
                             f"positive, got {self.n_samples}/"
                             f"{self.n_slices}")


@dataclasses.dataclass(frozen=True)
class ForgettingSpec:
    """Adaptivity knobs (DESIGN.md §9.2) as a JSON-friendly spec; maps
    onto :class:`repro.sim.policies.ForgettingConfig` at compile time."""

    gamma: float = 1.0
    window: int = 0
    replay_rho: float = 1.0

    def __post_init__(self):
        if not 0.0 < self.gamma <= 1.0:
            raise ValueError(f"ForgettingSpec: gamma must be in (0, 1], "
                             f"got {self.gamma}")
        if self.window < 0:
            raise ValueError(f"ForgettingSpec: window must be >= 0, "
                             f"got {self.window}")
        if not 0.0 < self.replay_rho <= 1.0:
            raise ValueError(f"ForgettingSpec: replay_rho must be in "
                             f"(0, 1], got {self.replay_rho}")

    def to_config(self):
        from repro.sim.policies import ForgettingConfig
        return ForgettingConfig(gamma=float(self.gamma),
                                window=int(self.window),
                                replay_rho=float(self.replay_rho))


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    """Per-slice replay-SGD schedule for policies with a train hook.
    ``train_steps=None`` derives the fixed per-slice budget from
    ``epochs`` (``repro.sim.neuralucb_train_schedule``).
    ``precision`` selects the forward/backward compute dtype of the
    train path ("f32" | "bf16"); losses, gradients, and optimizer state
    stay f32 either way (DESIGN.md §14.2)."""

    epochs: int = 5
    train_steps: Optional[int] = None
    batch_size: int = 256
    precision: str = "f32"

    def __post_init__(self):
        if self.epochs <= 0 or self.batch_size <= 0:
            raise ValueError("TrainSpec: epochs and batch_size must be "
                             "positive")
        if self.train_steps is not None and self.train_steps <= 0:
            raise ValueError("TrainSpec: train_steps must be positive "
                             "or None")
        if self.precision not in ("f32", "bf16"):
            raise ValueError(f"TrainSpec: precision must be 'f32' or "
                             f"'bf16', got {self.precision!r}")


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """One policy-zoo entry.

    * ``policy`` — registry name (``repro.sim.POLICIES``).
    * ``name`` — display label (defaults to ``policy``); must be unique
      within a spec so forgetting variants of the same policy can
      coexist (``neuralucb`` / ``neuralucb-forget``).
    * ``axes`` — hyper-grid axes as ``((field, (v0, v1, ...)), ...)``;
      the grid is the cartesian product in the given axis order, and
      each field must exist in the policy's hypers pytree. ``None`` is
      accepted only for ``cost_lambda`` (the "env's own reward table"
      sentinel).
    * ``overrides`` — scalar builder-kwarg overrides, e.g.
      ``(("explore", 0.2),)``.
    * ``forgetting`` — per-entry adaptivity variant; ``None`` inherits
      the spec-level default.
    """

    policy: str
    name: Optional[str] = None
    axes: Tuple[Tuple[str, Tuple[Any, ...]], ...] = ()
    overrides: Tuple[Tuple[str, Any], ...] = ()
    forgetting: Optional[ForgettingSpec] = None

    def __post_init__(self):
        seen = set()
        for field, values in self.axes:
            if field in seen:
                raise ValueError(f"PolicySpec({self.label}): duplicate "
                                 f"axis {field!r}")
            seen.add(field)
            if not values:
                raise ValueError(f"PolicySpec({self.label}): axis "
                                 f"{field!r} has no values")
            if any(v is None for v in values) and field != "cost_lambda":
                raise ValueError(f"PolicySpec({self.label}): axis "
                                 f"{field!r} has a null value (only "
                                 f"cost_lambda accepts the null "
                                 f"sentinel)")

    @property
    def label(self) -> str:
        return self.name or self.policy


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    """Serving-storm study (DESIGN.md §12): drive the spec's single
    policy through the async serving engine under a traffic pattern and
    scripted faults instead of the batch protocol sweep.

    * ``requests`` / ``waves`` — total request budget, shaped into
      arrival waves by ``pattern`` (``repro.serving.TRAFFIC_PATTERNS``).
    * ``outages`` — announced ``(arm, start_wave, end_wave)`` windows.
    * ``fail_decide_calls`` — decide-call indices whose router call is
      forced to raise (the engine must degrade, not crash).
    * ``train_every`` — run the router's train hook every that many
      waves (0 = never).
    * Gates: ``require_zero_lost`` (accounting invariant),
      ``p99_decide_ms`` (None = unbounded), ``max_shed_fraction``
      (shed / submitted ceiling). They decide the cell's ``serving_ok``
      flag and hence ``ExperimentResult.ok`` — the CI exit status.
    """

    requests: int = 20_000
    waves: int = 40
    pattern: str = "flash_crowd"
    decide_batch: int = 256
    serve_batch: int = 256
    queue_capacity: int = 4096
    outages: Tuple[Tuple[int, int, int], ...] = ()
    fail_decide_calls: Tuple[int, ...] = ()
    train_every: int = 0
    max_train_lag: int = 0
    p99_decide_ms: Optional[float] = None
    max_shed_fraction: float = 1.0
    require_zero_lost: bool = True
    seed: int = 0

    def __post_init__(self):
        if self.waves <= 0 or self.requests < self.waves:
            raise ValueError(f"ServingSpec: need requests >= waves >= 1, "
                             f"got {self.requests}/{self.waves}")
        if self.decide_batch <= 0 or self.serve_batch <= 0 \
                or self.queue_capacity <= 0:
            raise ValueError("ServingSpec: decide_batch, serve_batch and "
                             "queue_capacity must be positive")
        for o in self.outages:
            if len(o) != 3:
                raise ValueError(f"ServingSpec: outage {o!r} is not "
                                 f"(arm, start_wave, end_wave)")
            arm, s, e = o
            if arm < 0 or s < 0 or not s < e:
                raise ValueError(f"ServingSpec: bad outage window {o!r} "
                                 f"(need arm >= 0, 0 <= start < end)")
        if self.train_every < 0:
            raise ValueError("ServingSpec: train_every must be >= 0")
        if self.max_train_lag < 0:
            raise ValueError("ServingSpec: max_train_lag must be >= 0 "
                             "(0 = synchronous end-of-slice train)")
        if self.p99_decide_ms is not None and self.p99_decide_ms <= 0:
            raise ValueError("ServingSpec: p99_decide_ms must be "
                             "positive or None")
        if not 0.0 <= self.max_shed_fraction <= 1.0:
            raise ValueError("ServingSpec: max_shed_fraction must be in "
                             "[0, 1]")


@dataclasses.dataclass(frozen=True)
class ArmPoolSpec:
    """Physical arm pool (DESIGN.md §16): each arm is a real
    ``ModelConfig`` from ``repro.configs`` with cost/latency derived
    from the roofline model on ``hardware``; quality comes from the
    RouterBench tables via the explicit ``mapping`` (defaulting to
    ``repro.armpool.DEFAULT_RB_MAPPING``).

    * ``arms`` — pool members (registry arch ids or their dashed
      aliases); duplicates and unknown names raise at compile.
    * ``mapping`` — ``(arm, routerbench_model)`` overrides; every arm
      must resolve to a table column (no positional pairing).
    * ``decode_batch`` / ``context`` — the serving operating point the
      roofline is evaluated at (a "price shock" is a re-derivation at a
      different point or target).
    * ``cost_source`` — ``"roofline"`` ($/token from chip-seconds) or
      ``"routerbench"`` (mapped replay columns as-is; the parity leg).
    * ``calibrate`` — fold the measured/analytic decode-step ratio into
      the tables for arms up to ``calibrate_max_params`` (times real
      jitted decode steps; keep off in CI).
    * Serving: arms up to ``serve_real_max_params`` execute REAL jitted
      decode steps in the storm (``reduced_decode`` uses the config's
      CPU-runnable reduced variant); larger arms sleep their roofline
      step time scaled by ``latency_scale``; ``max_new`` tokens are
      generated per request.
    * ``local`` — ``(arm, ((key, value), ...))``: arms served on this
      host whatever their size, each at the chip share the pairs give
      (``ModelConfig.chip_share``: ``num_layers``, ``experts_held``,
      ``expert_first``). The tables still cost the arm from its full
      published configuration.
    """

    arms: Tuple[str, ...] = ()
    hardware: str = "tpu-v5e"
    mapping: Tuple[Tuple[str, str], ...] = ()
    decode_batch: int = 8
    context: int = 2048
    cost_source: str = "roofline"
    calibrate: bool = False
    calibrate_max_params: int = 2_000_000_000
    serve_real_max_params: int = 200_000_000
    reduced_decode: bool = True
    latency_scale: float = 1.0
    max_new: int = 4
    local: Tuple[Tuple[str, Tuple[Tuple[str, int], ...]], ...] = ()

    def __post_init__(self):
        if not self.arms:
            raise ValueError("ArmPoolSpec: no arms (list at least one "
                             "repro.configs arch id)")
        stray = sorted({a for a, _ in self.local} - set(self.arms))
        if stray:
            raise ValueError(f"ArmPoolSpec: local arm(s) {stray} are not "
                             f"in arms {self.arms}")
        if self.decode_batch <= 0 or self.context <= 0:
            raise ValueError("ArmPoolSpec: decode_batch and context "
                             "must be positive")
        if self.cost_source not in ("roofline", "routerbench"):
            raise ValueError(f"ArmPoolSpec: cost_source must be "
                             f"'roofline' or 'routerbench', got "
                             f"{self.cost_source!r}")
        if self.latency_scale < 0:
            raise ValueError("ArmPoolSpec: latency_scale must be >= 0")
        if self.max_new <= 0:
            raise ValueError("ArmPoolSpec: max_new must be positive")
        for pair in self.mapping:
            if len(pair) != 2:
                raise ValueError(f"ArmPoolSpec: mapping entry {pair!r} "
                                 f"is not (arm, routerbench_model)")
        keys = [a for a, _ in self.mapping]
        if len(set(keys)) != len(keys):
            dup = sorted({k for k in keys if keys.count(k) > 1})
            raise ValueError(f"ArmPoolSpec: duplicate mapping keys "
                             f"{dup}")


@dataclasses.dataclass(frozen=True)
class PretrainSpec:
    """Offline pretraining phase (DESIGN.md §13.3): build a logged
    corpus, run every hooked policy's ``pretrain`` on it, and inject the
    resulting states into the online sweep as warm starts.

    * ``corpus_size`` — logged rows. ``behavior="random"`` uses the
      exact-propensity RouterBench replay generator
      (``repro.data.replay_corpus``); any other value must be a policy
      REGISTRY name, which is run online once (``record_log=True``) and
      subsampled to ``corpus_size``.
    * ``steps`` / ``batch_size`` — offline SGD budget (the ridge folds
      consume the whole corpus regardless).
    * ``warm_start`` — sweepable axis: for each value every hooked
      policy entry is expanded into a warm (pretrained state injected,
      no slice-0 uniform warm-up) and/or cold variant, labeled
      ``<name>:warm`` / ``<name>:cold`` when both are present.
    * ``cache`` — checkpoint pretrained states keyed by the spec hash
      (``training/checkpoint.py``) so repeated CI/bench runs skip the
      offline phase.
    """

    corpus_size: int = 20_000
    behavior: str = "random"
    steps: int = 512
    batch_size: int = 256
    warm_start: Tuple[bool, ...] = (True,)
    seed: int = 0
    cache: bool = True

    def __post_init__(self):
        if self.corpus_size <= 0 or self.steps <= 0 or self.batch_size <= 0:
            raise ValueError("PretrainSpec: corpus_size, steps and "
                             "batch_size must be positive")
        if not self.warm_start:
            raise ValueError("PretrainSpec: warm_start needs at least one "
                             "value (True and/or False)")
        ws = [bool(w) for w in self.warm_start]
        if len(set(ws)) != len(ws):
            raise ValueError(f"PretrainSpec: duplicate warm_start values "
                             f"{tuple(self.warm_start)}")


@dataclasses.dataclass(frozen=True)
class OPESpec:
    """Off-policy evaluation phase (DESIGN.md §13.4): one logged run of
    the ``behavior`` policy scores every ``targets`` policy
    counterfactually via ``repro.core.protocol.estimate_offline`` —
    policies that never ran get IPS / SNIPS / DR value estimates.

    * ``behavior`` — policy REGISTRY name producing the propensity-aware
      log (run online with ``record_log=True``); ``behavior_overrides``
      are its builder kwargs (e.g. a wider ``explore`` for coverage).
    * ``targets`` — registry names to score offline. Pretrainable
      targets are first pretrained ON THE BEHAVIOR LOG (offline policy
      selection); their decided actions are scored as the declared
      ε-smoothed point mass (``repro.sim.OPE_SMOOTHING_EPS``).
    * ``parity`` — subset of targets ALSO run on-policy; each cell's
      ``ope_ok`` gate requires |DR − on-policy value| <= ``parity_tol``
      (the satellite-c sanity pin; keep it to deterministic targets).
    * ``clip`` — importance-weight truncation (None = unclipped).
    """

    targets: Tuple[str, ...] = ()
    behavior: str = "eps_greedy"
    behavior_overrides: Tuple[Tuple[str, Any], ...] = ()
    seed: int = 0
    clip: Optional[float] = None
    parity: Tuple[str, ...] = ()
    parity_tol: float = 0.05

    def __post_init__(self):
        if not self.targets:
            raise ValueError("OPESpec: no targets to score")
        if len(set(self.targets)) != len(self.targets):
            raise ValueError(f"OPESpec: duplicate targets "
                             f"{tuple(self.targets)}")
        extra = set(self.parity) - set(self.targets)
        if extra:
            raise ValueError(f"OPESpec: parity names {sorted(extra)} are "
                             f"not in targets")
        if self.clip is not None and self.clip <= 0:
            raise ValueError("OPESpec: clip must be positive or None")
        if self.parity_tol <= 0:
            raise ValueError("OPESpec: parity_tol must be positive")


@dataclasses.dataclass(frozen=True)
class SummarizeSpec:
    """Artifact shaping: ``skip_first`` excludes the warm-start slice
    (paper §4.2); ``curves`` attaches seed-mean per-slice reward curves
    to each cell; ``per_seed`` attaches the per-seed summary values."""

    skip_first: bool = True
    curves: bool = True
    per_seed: bool = False


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """The one typed input (module docstring). ``scenarios`` entries are
    registry names or ``None`` (stationary fast path)."""

    name: str
    data: DataSpec = DataSpec()
    policies: Tuple[PolicySpec, ...] = (PolicySpec("neuralucb"),)
    scenarios: Tuple[Optional[str], ...] = (None,)
    seeds: Tuple[int, ...] = (0,)
    train: TrainSpec = TrainSpec()
    forgetting: ForgettingSpec = ForgettingSpec()
    ucb_backend: str = "jnp"
    summarize: SummarizeSpec = SummarizeSpec()
    serving: Optional[ServingSpec] = None
    pretrain: Optional[PretrainSpec] = None
    ope: Optional[OPESpec] = None
    armpool: Optional[ArmPoolSpec] = None

    def __post_init__(self):
        if not self.policies:
            raise ValueError("ExperimentSpec: no policies")
        if self.ope is not None and self.serving is not None:
            raise ValueError("ExperimentSpec: off-policy evaluation and "
                             "a serving storm cannot share a spec")
        if self.serving is not None:
            if len(self.policies) != 1 or self.policies[0].axes:
                raise ValueError("ExperimentSpec: a serving storm takes "
                                 "exactly one policy with no grid axes")
            if tuple(self.scenarios) != (None,):
                raise ValueError("ExperimentSpec: serving storms take "
                                 "outage windows (serving.outages), not "
                                 "sim scenarios; use scenarios=(None,)")
        if not self.seeds:
            raise ValueError("ExperimentSpec: no seeds")
        if not self.scenarios:
            raise ValueError("ExperimentSpec: no scenarios (use (None,) "
                             "for the stationary run)")
        labels = [p.label for p in self.policies]
        if len(set(labels)) != len(labels):
            dup = sorted({l for l in labels if labels.count(l) > 1})
            raise ValueError(f"ExperimentSpec: duplicate policy labels "
                             f"{dup}; set PolicySpec.name to "
                             f"disambiguate variants")


# ------------------------------------------------------------ JSON codec --
def _train_to_json(train: TrainSpec) -> Dict[str, Any]:
    tr = dataclasses.asdict(train)
    if tr.get("precision") == "f32":
        # default elided, so pre-mixed-precision specs keep their hashes
        tr.pop("precision")
    return tr


def spec_to_json(spec: ExperimentSpec) -> Dict[str, Any]:
    """Spec -> plain JSON-serializable dict (schema-versioned). Inverse
    of :func:`spec_from_json`: round-trips are identity."""
    j = {
        "schema": SPEC_SCHEMA_VERSION,
        "name": spec.name,
        "data": dataclasses.asdict(spec.data),
        "policies": [
            {
                "policy": p.policy,
                "name": p.name,
                "axes": [[f, list(v)] for f, v in p.axes],
                "overrides": [[k, v] for k, v in p.overrides],
                "forgetting": (None if p.forgetting is None
                               else dataclasses.asdict(p.forgetting)),
            }
            for p in spec.policies
        ],
        "scenarios": list(spec.scenarios),
        "seeds": list(spec.seeds),
        "train": _train_to_json(spec.train),
        "forgetting": dataclasses.asdict(spec.forgetting),
        "ucb_backend": spec.ucb_backend,
        "summarize": dataclasses.asdict(spec.summarize),
    }
    if spec.serving is not None:
        # emitted only when set, so pre-serving specs keep their hashes
        sv = dataclasses.asdict(spec.serving)
        sv["outages"] = [list(o) for o in spec.serving.outages]
        sv["fail_decide_calls"] = list(spec.serving.fail_decide_calls)
        if sv["max_train_lag"] == 0:
            # elide the default so pre-overlap serving specs keep their
            # hashes (same contract as _train_to_json's precision pop)
            sv.pop("max_train_lag")
        j["serving"] = sv
    if spec.pretrain is not None:
        # same emit-only-when-set contract: pre-lifecycle specs keep
        # their hashes
        pt = dataclasses.asdict(spec.pretrain)
        pt["warm_start"] = [bool(w) for w in spec.pretrain.warm_start]
        j["pretrain"] = pt
    if spec.ope is not None:
        op = dataclasses.asdict(spec.ope)
        op["targets"] = list(spec.ope.targets)
        op["parity"] = list(spec.ope.parity)
        op["behavior_overrides"] = [[k, v] for k, v
                                    in spec.ope.behavior_overrides]
        j["ope"] = op
    if spec.armpool is not None:
        # emit-only-when-set: pre-PR-10 specs keep their hashes
        ap = dataclasses.asdict(spec.armpool)
        ap["arms"] = list(spec.armpool.arms)
        ap["mapping"] = [[a, m] for a, m in spec.armpool.mapping]
        # emit-only-when-set: pre-PR-15 pool specs keep their hashes
        ap.pop("local")
        if spec.armpool.local:
            ap["local"] = [[a, [list(kv) for kv in cut]]
                           for a, cut in spec.armpool.local]
        j["armpool"] = ap
    return j


def _strict(cls, d: Dict[str, Any]):
    """Construct a spec dataclass rejecting unknown keys — a typo'd
    field in a spec file must fail loudly, not silently run defaults."""
    if not isinstance(d, dict):
        raise ValueError(f"{cls.__name__}: expected an object, got "
                         f"{type(d).__name__}")
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - fields
    if unknown:
        raise ValueError(f"{cls.__name__}: unknown keys "
                         f"{sorted(unknown)} (known: {sorted(fields)})")
    return cls(**d)


def _policy_from_json(d: Dict[str, Any]) -> PolicySpec:
    d = dict(d)
    known = {"policy", "name", "axes", "overrides", "forgetting"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"PolicySpec: unknown keys {sorted(unknown)} "
                         f"(known: {sorted(known)})")
    axes = tuple((f, tuple(v)) for f, v in d.get("axes", ()))
    overrides = tuple((k, v) for k, v in d.get("overrides", ()))
    fg = d.get("forgetting")
    return PolicySpec(
        policy=d["policy"], name=d.get("name"), axes=axes,
        overrides=overrides,
        forgetting=None if fg is None else _strict(ForgettingSpec, fg))


def _serving_from_json(d: Dict[str, Any]) -> ServingSpec:
    d = dict(d)
    known = {f.name for f in dataclasses.fields(ServingSpec)}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"ServingSpec: unknown keys {sorted(unknown)} "
                         f"(known: {sorted(known)})")
    if "outages" in d:
        d["outages"] = tuple(tuple(int(x) for x in o)
                             for o in d["outages"])
    if "fail_decide_calls" in d:
        d["fail_decide_calls"] = tuple(int(x)
                                       for x in d["fail_decide_calls"])
    return ServingSpec(**d)


def spec_from_json(d: Dict[str, Any]) -> ExperimentSpec:
    """Strict inverse of :func:`spec_to_json`. Unknown keys anywhere in
    the document raise ``ValueError``; an unknown / missing ``schema``
    tag raises too (a future schema must be converted, not guessed at).
    """
    if not isinstance(d, dict):
        raise ValueError("spec_from_json: expected a JSON object")
    d = dict(d)
    schema = d.pop("schema", None)
    if schema != SPEC_SCHEMA_VERSION:
        raise ValueError(f"spec_from_json: schema {schema!r} is not "
                         f"{SPEC_SCHEMA_VERSION!r}")
    known = {"name", "data", "policies", "scenarios", "seeds", "train",
             "forgetting", "ucb_backend", "summarize", "serving",
             "pretrain", "ope", "armpool"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"ExperimentSpec: unknown keys "
                         f"{sorted(unknown)} (known: {sorted(known)})")
    if "name" not in d:
        raise ValueError("ExperimentSpec: missing required key 'name'")
    kw: Dict[str, Any] = {"name": d["name"]}
    if "data" in d:
        kw["data"] = _strict(DataSpec, d["data"])
    if "policies" in d:
        if not isinstance(d["policies"], (list, tuple)):
            raise ValueError("ExperimentSpec: 'policies' must be a "
                             "list of policy objects")
        kw["policies"] = tuple(_policy_from_json(p)
                               for p in d["policies"])
    if "scenarios" in d:
        # a bare scalar (e.g. --set scenarios=price_shock) means a
        # one-element list — NOT a string to iterate character-wise
        v = d["scenarios"]
        kw["scenarios"] = tuple(v) if isinstance(v, (list, tuple)) \
            else (v,)
    if "seeds" in d:
        v = d["seeds"]
        if not isinstance(v, (list, tuple)):
            v = [v]
        try:
            kw["seeds"] = tuple(int(s) for s in v)
        except (TypeError, ValueError) as e:
            raise ValueError(f"ExperimentSpec: 'seeds' must be a list "
                             f"of ints, got {d['seeds']!r}") from e
    if "train" in d:
        kw["train"] = _strict(TrainSpec, d["train"])
    if "forgetting" in d:
        kw["forgetting"] = _strict(ForgettingSpec, d["forgetting"])
    if "ucb_backend" in d:
        kw["ucb_backend"] = d["ucb_backend"]
    if "summarize" in d:
        kw["summarize"] = _strict(SummarizeSpec, d["summarize"])
    if "serving" in d and d["serving"] is not None:
        kw["serving"] = _serving_from_json(d["serving"])
    if "pretrain" in d and d["pretrain"] is not None:
        p = dict(d["pretrain"])
        if "warm_start" in p:
            v = p["warm_start"]
            p["warm_start"] = tuple(bool(w) for w in v) \
                if isinstance(v, (list, tuple)) else (bool(v),)
        kw["pretrain"] = _strict(PretrainSpec, p)
    if "ope" in d and d["ope"] is not None:
        o = dict(d["ope"])
        for f in ("targets", "parity"):
            if f in o:
                v = o[f]
                o[f] = tuple(v) if isinstance(v, (list, tuple)) else (v,)
        if "behavior_overrides" in o:
            o["behavior_overrides"] = tuple(
                (k, v) for k, v in o["behavior_overrides"])
        kw["ope"] = _strict(OPESpec, o)
    if "armpool" in d and d["armpool"] is not None:
        a = dict(d["armpool"])
        if "arms" in a:
            v = a["arms"]
            a["arms"] = tuple(v) if isinstance(v, (list, tuple)) \
                else (v,)
        if "mapping" in a:
            a["mapping"] = tuple((arm, m) for arm, m in a["mapping"])
        if "local" in a:
            a["local"] = tuple((arm, tuple((k, v) for k, v in cut))
                               for arm, cut in a["local"])
        kw["armpool"] = _strict(ArmPoolSpec, a)
    return ExperimentSpec(**kw)


def spec_hash(spec: ExperimentSpec) -> str:
    """Content hash of the canonical JSON form — the artifact manifest's
    reproducibility key (same spec <=> same hash, field order
    irrelevant)."""
    canon = json.dumps(spec_to_json(spec), sort_keys=True,
                       separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


# ------------------------------------------------------- ``--set`` paths --
def parse_override_value(text: str) -> Any:
    """Parse one ``--set key=value`` right-hand side: JSON when it
    parses (numbers, null, true/false, quoted strings, [lists]),
    comma-split into a list otherwise, bare string as a fallback."""
    try:
        return json.loads(text)
    except (json.JSONDecodeError, ValueError):
        pass
    if "," in text:
        return [parse_override_value(v) for v in text.split(",")]
    return text


def _set_path(node: Any, parts, value):
    head, rest = parts[0], parts[1:]
    if isinstance(node, list):
        # integer index, or a policy entry matched by its display label
        if head.lstrip("-").isdigit():
            target = node[int(head)]
        else:
            matches = [p for p in node
                       if isinstance(p, dict)
                       and (p.get("name") or p.get("policy")) == head]
            if not matches:
                raise KeyError(f"no policy entry labeled {head!r}")
            target = matches[0]
        if not rest:
            raise KeyError("cannot replace a whole policy entry via "
                           "--set; set its fields instead")
        return _set_path(target, rest, value)
    if not isinstance(node, dict):
        raise KeyError(f"cannot descend into {type(node).__name__} at "
                       f"{head!r}")
    if head == "axes" and rest:
        # axes are [field, values] pairs: address by hyper-field name
        if len(rest) != 1:
            raise KeyError(f"axes paths take exactly one field name, "
                           f"got {'.'.join(rest)!r}")
        field = rest[0]
        vals = value if isinstance(value, list) else [value]
        axes = node.setdefault("axes", [])
        for pair in axes:
            if pair[0] == field:
                pair[1] = vals
                return
        axes.append([field, vals])
        return
    if not rest:
        if head not in node:
            raise KeyError(f"unknown spec key {head!r} (known: "
                           f"{sorted(node)})")
        if head == "policies":
            # policies=<label,...> FILTERS the spec's entries by display
            # label (the CI-shrink idiom); entries can't be built from
            # scalar values, only selected
            labels = value if isinstance(value, list) else [value]
            by_label = {(p.get("name") or p.get("policy")): p
                        for p in node[head]}
            missing = [l for l in labels if l not in by_label]
            if missing:
                raise KeyError(f"no policy entry labeled "
                               f"{missing[0]!r} (have: "
                               f"{sorted(by_label)})")
            node[head] = [by_label[l] for l in labels]
            return
        node[head] = value
        return
    if head not in node:
        raise KeyError(f"unknown spec key {head!r} (known: "
                       f"{sorted(node)})")
    return _set_path(node[head], rest, value)


def apply_overrides(spec: ExperimentSpec,
                    assignments: Dict[str, Any]) -> ExperimentSpec:
    """Apply dotted-path overrides to a spec (the CLI's ``--set``).

    Paths address the JSON form: ``data.n_samples=1500``,
    ``seeds=0,1``, ``train.train_steps=32``,
    ``scenarios=price_shock,arm_outage``,
    ``policies.neuralucb.axes.beta=0.25,0.5,1.0`` (policy entries are
    addressed by display label, axes by hyper-field name). The result
    re-validates through the strict JSON codec, so a typo'd path or an
    invalid value errors loudly."""
    doc = spec_to_json(spec)
    for path, value in assignments.items():
        parts = path.split(".")
        if not parts or parts[0] == "schema":
            raise KeyError(f"cannot set {path!r}")
        try:
            _set_path(doc, parts, value)
        except (KeyError, IndexError) as e:
            raise KeyError(f"--set {path}: {e}") from e
    return spec_from_json(doc)
