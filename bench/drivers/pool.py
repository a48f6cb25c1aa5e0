"""Pool driver: the router in front of a mixed fleet whose small and
mid-size arms decode for real on this chip.

The program under test is the async engine (``AsyncRouterEngine``) in
front of the device-resident router (``DevicePolicyRouter``), as in the
serve driver, over the arm pool of the configuration's ``pool``: the
pool is compiled from the published configurations
(``repro.armpool.build_pool_env``: quality from the RouterBench-shaped
tables, cost from each arm's roofline), and ``build_arm_engines`` gives
each arm its engine. The arms named ``real`` decode on this chip through
``DecodeArmEngine`` -> ``ServingEngine`` -> ``models.model.decode_step``:
``mamba2_130m`` at its published widths, and the arm under ``local`` at
the chip share the configuration file states (its ``layer_types``,
``num_local_experts`` from ``expert_first``); the others run on other
hosts of a deployment, and answer at once (``latency_scale`` 0).

The load is a closed loop of ``population`` clients, as the serve
driver offers it (its loop is reused), with chat-shaped prompts. The
warm-up compiles every program and lets the routing mix settle; the
window measures for the run's seconds. In a traced run the profiler's
stop is left out of the window's clock, and the trace is reduced here
(``bench.arm_trace``) before it is handed on.

Correctness: the router's legs are checked as in the serve driver. The
real arms' weights are drawn from the run's seed. A seeded reservoir
keeps, per real arm, sampled arm batches of the window (an arm the
window never sampled: of the warm-up, else one batch after the window):
for some of their rows (the shortest, and others drawn by the seed) the
prompt, the tokens generated, the logits the program produced at every
position, and after the last step the first Mamba-2 layer's SSM state
and the first attention layer's cached keys and values. After the
window the benchmark's reference (``bench.reference.granite4h``)
recomputes each row's forward pass over its prompt and the tokens fed
back (``arm_numbers``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import arm_trace
from bench.common import Reservoir, Spans, split_seed
from bench.drivers import serve as S
from bench.reference import granite4h as REF

# the check's name for each real arm
ARM_KEYS = {"granite_4_0_h_small": "granite4h", "mamba2_130m": "mamba2"}


# --------------------------------------------------------------- arms --
def arm_arch(cfg: dict, name: str) -> dict:
    """The arm's description, from the configuration file alone: the
    local arm from the file's own keys (the published config's names),
    the others from ``arms.<name>.config``."""
    if name == cfg["pool"]["local"]:
        c = cfg
        types = list(c["layer_types"])
        pub = c["published"]["layer_types"]
        attn = [i for i, t in enumerate(pub) if t == "attention"]
        heads = c["num_attention_heads"]
        return {
            "d": c["hidden_size"], "vocab": c["vocab_size"],
            "eps": c["rms_norm_eps"], "layer_types": types,
            "ssm_only": False, "period": attn[1] - attn[0],
            "attn_position": attn[0] % (attn[1] - attn[0]),
            "embedding_multiplier": c["embedding_multiplier"],
            "residual_multiplier": c["residual_multiplier"],
            "logits_scaling": c["logits_scaling"],
            "mamba": {"heads": c["mamba_n_heads"],
                      "head_dim": c["mamba_d_head"],
                      "state": c["mamba_d_state"],
                      "conv": c["mamba_d_conv"]},
            "attention": {"heads": heads,
                          "kv_heads": c["num_key_value_heads"],
                          "head_dim": c["hidden_size"] // heads,
                          "scale": c["attention_multiplier"]},
            "moe": {"experts": c["published"]["num_local_experts"],
                    "held": c["num_local_experts"],
                    "first": c["expert_first"],
                    "top_k": c["num_experts_per_tok"],
                    "width": c["intermediate_size"],
                    "shared": c["shared_intermediate_size"]},
        }
    c = cfg["arms"][name]["config"]
    return {
        "d": c["d_model"], "vocab": c["vocab_size"], "eps": c["norm_eps"],
        "layer_types": ["mamba"] * c["n_layer"], "ssm_only": True,
        "period": 1, "attn_position": -1, "embedding_multiplier": 1.0,
        "residual_multiplier": 1.0, "logits_scaling": 1.0,
        "mamba": {"heads": c["expand"] * c["d_model"] // c["headdim"],
                  "head_dim": c["headdim"], "state": c["d_state"],
                  "conv": c["d_conv"]},
    }


def _check_widths(mcfg, arch: dict, name: str) -> None:
    """The program's configuration against the file's: every width, the
    depth and the experts held."""
    m = arch["mamba"]
    want = {"d_model": arch["d"], "vocab_size": arch["vocab"],
            "num_layers": len(arch["layer_types"]),
            "ssm_state": m["state"], "ssm_head_dim": m["head_dim"],
            "ssm_conv_width": m["conv"],
            "ssm_expand": m["heads"] * m["head_dim"] // arch["d"]}
    if not arch["ssm_only"]:
        a, e = arch["attention"], arch["moe"]
        want.update(num_heads=a["heads"], num_kv_heads=a["kv_heads"],
                    resolved_head_dim=a["head_dim"],
                    attention_scale=a["scale"], use_rope=False,
                    attn_every=arch["period"],
                    attn_position=arch["attn_position"],
                    num_experts=e["experts"], local_experts=e["held"],
                    expert_first=e["first"], experts_per_token=e["top_k"],
                    d_ff=e["width"], shared_expert_ff=e["shared"],
                    embedding_multiplier=arch["embedding_multiplier"],
                    residual_multiplier=arch["residual_multiplier"],
                    logits_scaling=arch["logits_scaling"])
    bad = {k: (getattr(mcfg, k), v) for k, v in want.items()
           if getattr(mcfg, k) != v}
    if bad:
        raise ValueError(f"{name}: the program's configuration differs "
                         f"from the file's (program, file): {bad}")


def first_state(cache: dict, arch: dict):
    """The first Mamba-2 layer's SSM state in the program's decode cache,
    (rows, heads, head_dim, state)."""
    layers = cache["layers"]
    if arch["ssm_only"]:
        return layers["ssm"][0]
    return layers["mamba"]["ssm"][0, 0]


def first_kv(cache: dict, arch: dict):
    """The first attention layer's cached keys and values, each (rows,
    kv_heads, slots, head_dim); None for an arm without attention."""
    if arch["ssm_only"]:
        return None
    a = cache["layers"]["attn"]
    return a["k"][0], a["v"][0]


class ArmProxy:
    """Wraps a real arm's engine: host time and work in each call, and
    for a sampled call, of a few rows, on the host: the prompt, the new
    tokens, every position's logits, and after the last step the first
    Mamba-2 layer's SSM state and the first attention layer's keys and
    values."""

    real_decode = True

    def __init__(self, inner, samples: Reservoir, rows: int,
                 rng: np.random.Generator, arch: dict):
        self.inner = inner
        self.name = inner.name
        self.arch = arch
        self.vocab = int(arch["vocab"])   # the logits compared: the real vocab
        self.max_seq = inner.max_seq
        self.samples = samples
        self.keep_rows = int(rows)
        self.rng = rng
        self.recording = False
        self.reset()

    def reset(self):
        self.secs = 0.0
        self.calls = []        # (prompt width, [each row's prompt length])

    def generate(self, tokens, max_new: int = 8, lengths=None):
        toks = np.asarray(tokens)
        B, S = toks.shape
        lengths = np.full(B, S) if lengths is None else np.asarray(lengths)
        # the engine's contract: each row's last ``keep`` prompt tokens,
        # left-padded to the call's width W, so its prompt ends at W - 1
        keep = max(1, self.max_seq - max_new - 1)
        W = min(S, keep)
        kept = np.minimum(lengths, W)
        slot = self.samples.offer() if self.recording else None
        logs, last, hooks, pick = {}, {}, {}, None
        if slot is not None:
            import jax.numpy as jnp
            pick = [int(np.argmin(kept))]
            rest = [i for i in range(B) if i != pick[0]]
            pick += [int(i) for i in self.rng.permutation(rest)[
                :self.keep_rows - 1]]
            # a fixed number of rows, so the gathers compile once
            idx = jnp.asarray(pick + pick[:1] * (self.keep_rows - len(pick)),
                              jnp.int32)

            def on_step(i, logits):
                logs[i] = np.asarray(jnp.take(logits, idx, axis=0))

            def on_cache(cache):
                last["state"] = np.asarray(jnp.take(
                    first_state(cache, self.arch), idx, axis=0))
                kv = first_kv(cache, self.arch)
                if kv is not None:
                    last["k"], last["v"] = (np.asarray(jnp.take(
                        t, idx, axis=0)) for t in kv)
            hooks = {"on_step": on_step, "on_cache": on_cache}
        t0 = time.perf_counter()
        new, steps = self.inner.generate(tokens, max_new=max_new,
                                         lengths=lengths, **hooks)
        self.secs += time.perf_counter() - t0
        self.calls.append((W, kept.tolist()))
        if slot is not None:
            V = self.vocab
            rows = []
            for j, b in enumerate(pick):
                n = int(kept[b])
                prompt = toks[b, :lengths[b]][-n:].astype(np.int32)
                start = W - n
                pos = n + max_new - 1
                row = {
                    "tokens": np.concatenate(
                        [prompt, new[b, :max_new - 1].astype(np.int32)]),
                    "logits": np.stack([logs[start + p][j, :V] for p in
                                        range(pos)]).astype(np.float32),
                    "state": last["state"][j]}
                if "k" in last:
                    # slots start .. start + pos - 1 hold the row's tokens
                    row.update({k: np.moveaxis(last[k][j][:, start:start + pos],
                                               0, 1) for k in ("k", "v")})
                rows.append(row)
            self.samples.put(slot, rows)
        return new, steps


# ------------------------------------------------------------- traffic --
def prompt_pool(traffic: dict, rng: np.random.Generator) -> list:
    """Prompt token arrays. Lengths: a fixed multiset from the traffic
    file's ``shape_seed`` (lognormal with the given median and sigma,
    rounded and clipped), in the seed's order; ids from the run's seed,
    in every real arm's vocabulary."""
    pl = traffic["prompt_len"]
    n = int(traffic["prompt_pool"])
    base = np.random.default_rng(int(traffic["shape_seed"]))
    lens = np.clip(np.rint(np.exp(base.normal(np.log(pl["median"]),
                                              pl["sigma"], n))),
                   pl["min"], pl["max"]).astype(int)
    lens = lens[rng.permutation(n)]
    vocab = int(traffic["token_vocab"])
    return [rng.integers(1, vocab, size=int(L), dtype=np.int32)
            for L in lens]


# --------------------------------------------------------------- world --
def build(cfg: dict, traffic: dict, seed: int, trace: bool):
    """Data, router, arms and engine for one run, all from ``seed``."""
    from repro.experiments.spec import ArmPoolSpec, DataSpec

    pool = cfg["pool"]
    local = pool["local"]
    arch = {name: arm_arch(cfg, name) for name in pool["real"]}
    # the spec first: a program without chip shares stops here, at once
    aspec = ArmPoolSpec(
        arms=tuple(pool["arms"]), mapping=tuple(pool["mapping"].items()),
        hardware=pool["hardware"], decode_batch=pool["decode_batch"],
        context=pool["context"], reduced_decode=False, latency_scale=0.0,
        max_new=int(traffic["max_new"]),
        local=((local, (("num_layers", len(cfg["layer_types"])),
                        ("experts_held", cfg["num_local_experts"]),
                        ("expert_first", cfg["expert_first"]))),))

    from repro.armpool import build_arm_engines, build_pool_env
    from repro.serving import AsyncRouterEngine, DevicePolicyRouter
    from repro.sim.engine import _tables
    from repro.sim.env import DeviceReplayEnv
    from repro.sim.policies import TRAIN_CHUNK, make_policy

    w = S.World()
    w.cfg, w.traffic = cfg, traffic
    w.seeds = split_seed(seed)
    w.spans = Spans(trace)
    rng = np.random.default_rng(w.seeds["checks"])
    k = traffic["sample_checks"]
    w.samples = {name: Reservoir(k[name], rng)
                 for name in ("decide", "update", "slice")}

    # the fleet's tables are the deployment's, the same every run; the
    # seed draws the order the requests come in
    fleet = cfg["fleet"]
    henv, compiled = build_pool_env(aspec, DataSpec(
        seed=fleet["data_seed"], n_samples=fleet["n_samples"],
        n_slices=fleet["n_slices"]))
    env = DeviceReplayEnv.from_host(henv)
    ucfg = S._router_config(cfg)
    if (henv.x_emb.shape[1], env.K) != (ucfg.emb_dim, ucfg.num_actions):
        raise ValueError(f"data has emb {henv.x_emb.shape[1]} and "
                         f"{env.K} arms; the configuration says "
                         f"{ucfg.emb_dim} and {ucfg.num_actions}")
    r = cfg["router"]
    pol, hyp = make_policy(
        r["policy"], env, ucfg, beta=r["beta"], tau_g=r["tau_g"],
        gate_margin=r["gate_margin"], lr=cfg["train"]["lr"],
        ridge_lambda0=r["ridge_lambda0"], ucb_backend=r["ucb_backend"])
    w.x_emb = np.asarray(henv.x_emb, np.float32)
    w.x_feat = np.asarray(henv.data["x_feat"], np.float32)
    w.domain = np.asarray(henv.data["domain"], np.int32)
    w.stream = np.random.default_rng(w.seeds["data"]).permutation(
        np.concatenate([np.asarray(s) for s in henv.slices]))
    w.train_chunks = -(-int(traffic["train_steps"]) // TRAIN_CHUNK)
    w.chunk_steps = TRAIN_CHUNK
    router = DevicePolicyRouter(
        pol, hyp, _tables(env), seed=w.seeds["router"],
        slice_width=traffic["decide_batch"],
        capacity_slices=traffic["ring_slices"],
        batch_size=traffic["train_batch"], train_chunks=w.train_chunks,
        max_train_lag=traffic["max_train_lag"])
    w.router = S.RouterProxy(router, w.spans, w.samples)

    engines, _ = build_arm_engines(compiled, aspec,
                                   max_batch=traffic["serve_batch"],
                                   seed=w.seeds["weights"])
    w.arms = {}
    for i, e in enumerate(engines):
        if e.name not in pool["real"]:
            continue
        if not e.real_decode:
            raise ValueError(f"{e.name} is not served on this chip")
        _check_widths(e.cfg, arch[e.name], e.name)
        w.arms[e.name] = engines[i] = ArmProxy(
            e, Reservoir(k["arm_batches"], rng), k["arm_rows"], rng,
            arch[e.name])
    w.arch = arch
    w.engine = AsyncRouterEngine(
        w.router, env.K, engines=engines,
        reward_table=np.asarray(env.reward),
        quality_table=np.asarray(env.quality),
        cost_table=np.asarray(env.cost),
        queue_capacity=traffic["queue_capacity"],
        decide_batch=traffic["decide_batch"],
        serve_batch=traffic["serve_batch"], max_new=traffic["max_new"])
    w.prompts = prompt_pool(traffic, np.random.default_rng(
        w.seeds["traffic"]))
    w.cursor = 0
    w.profile = S._Profile(None, 0.0)
    return w


class _Profile(S._Profile):
    """The serve driver's profiler, with its stop (the trace's writing)
    kept off the window's clock: ``paused_s``."""

    paused_s = 0.0

    def stop(self):
        was = self.span is not None
        t0 = time.perf_counter()
        super().stop()
        if was:
            self.paused_s += time.perf_counter() - t0


def layer_data(w, tally, counters0: dict) -> dict:
    out = S.layer_data(w, tally, counters0)
    out["max_new"] = int(w.traffic["max_new"])
    out["serve_batch"] = int(w.traffic["serve_batch"])
    out["real_arms"] = {
        name: {"key": ARM_KEYS[name], "index": w.cfg["pool"]["arms"].index(
            name), "arch": w.arch[name], "arm_s": a.secs, "calls": a.calls}
        for name, a in w.arms.items()}
    return out


# --------------------------------------------------------------- check --
def _one_batch(arm: ArmProxy, prompts: list, max_new: int) -> None:
    """One sampled arm batch of ``prompts``, right-padded."""
    toks = np.zeros((len(prompts), max(len(p) for p in prompts)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    arm.recording = True
    arm.generate(toks, max_new=max_new, lengths=[len(p) for p in prompts])


def rounded_share(state: np.ndarray) -> float:
    """Share of the nonzero entries of a float32 array whose mantissa
    ends in 8 zero bits: 1 in 256 of float32 arithmetic's results, every
    entry of one held in a 16-bit (or narrower) float."""
    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    live = bits & 0x7FFFFFFF != 0
    return float(np.mean(bits[live] & 0xFF == 0)) if live.any() else 1.0


def arm_numbers(w, params: dict) -> dict:
    """The real arms' checks over their sampled rows, each the worst
    row's (no sample: NaN, not correct):

    * ``arm_logit_err.<arm>``: each position's logits against the
      reference's forward pass over the same tokens, the widest gap
      over the widest reference logit;
    * ``arm_state_rounded.<arm>``: ``rounded_share`` of the first
      Mamba-2 layer's SSM state after the row's last step (the state is
      float32);
    * ``arm_kv_err.<arm>`` (an arm with attention): the first attention
      layer's cached keys and values against the reference's, the
      widest gap over the widest reference entry, keys and values each.
    """
    out = {}
    for name, a in w.arms.items():
        key = ARM_KEYS[name]
        rows = [r for batch in a.samples.items for r in batch]
        got = {"arm_logit_err." + key: float("nan"),
               "arm_state_rounded." + key: float("nan")}
        if "attention" in w.arch[name]["layer_types"]:
            got["arm_kv_err." + key] = float("nan")
        if rows:
            T = max(len(r["tokens"]) for r in rows)
            toks = np.zeros((len(rows), T), np.int32)
            for i, r in enumerate(rows):
                toks[i, :len(r["tokens"])] = r["tokens"]
            ref, extra = REF.forward(params[name], w.arch[name], toks,
                                     keep=True)
            got["arm_logit_err." + key] = max(
                REF.widest_gap(r["logits"], ref[i, :len(r["logits"])])
                for i, r in enumerate(rows))
            got["arm_state_rounded." + key] = max(
                rounded_share(r["state"]) for r in rows)
            if "k" in extra:
                got["arm_kv_err." + key] = max(
                    REF.widest_gap(r[k].astype(np.float32),
                                  extra[k][i, :len(r[k])])
                    for i, r in enumerate(rows) for k in ("k", "v"))
        out.update(got)
    return out


# ----------------------------------------------------------------- run --
def run(ctx: dict) -> dict:
    """One run of one pool cell (see ``bench.harness``)."""
    from bench.harness import _xplane
    from repro.common.compilation import CompileCount

    cfg, traffic = ctx["config"], ctx["traffic"]
    w = build(cfg, traffic, ctx["seed"], ctx["trace"])
    # the warm-up records too, so the copies the checks take compile
    # there; an arm the window never samples keeps its warm-up samples
    w.router.recording = True
    for a in w.arms.values():
        a.recording = True
    S.drive(w, 0.0, decides=int(traffic["warmup_decides"]))
    for res in w.samples.values():
        res.seen, res.items = 0, []
    fallback = {}
    for name, a in w.arms.items():
        fallback[name] = a.samples.items
        a.samples.seen, a.samples.items = 0, []
        a.reset()
    w.router.secs = {k: 0.0 for k in w.router.secs}
    w.router.rows = {k: 0 for k in w.router.rows}
    counters0 = dict(w.engine.counters)
    w.profile = _Profile(ctx.get("trace_dir"),
                         float(traffic.get("trace_seconds", ctx["seconds"])),
                         w)
    with CompileCount() as cc:
        w.t_window = time.perf_counter()
        w.profile.start()
        tally = S.drive(w, float(ctx["seconds"]))
        w.profile.stop()
    tally.window_s -= w.profile.paused_s
    w.router.recording = False
    memory = ctx["memory_peak"]()
    layer = layer_data(w, tally, counters0)
    if ctx["trace"]:
        layer["arm_trace"] = arm_trace.reduce(_xplane(ctx["trace_dir"]))
    for name, a in w.arms.items():
        a.samples.items = a.samples.items or fallback[name]
        if not a.samples.items:
            # the router never chose the arm in this run: its check
            # takes one batch of the traffic's prompts, after the window
            _one_batch(a, w.prompts[:int(traffic["serve_batch"])],
                       int(traffic["max_new"]))
        a.recording = False
    out = {"t_window": w.t_window, "attempted": tally.issued,
           "failed": tally.failed + (w.engine.counters["decide_errors"]
                                     - counters0["decide_errors"]),
           "end_to_end": S.end_to_end(w, tally), "layer": layer,
           "memory_peak_bytes": memory, "compiles_in_window": cc.compiles}
    # the program's state goes before the reference runs; the arms'
    # weights stay, for the reference to read
    params = {name: a.inner.engine.params for name, a in w.arms.items()}
    for a in w.arms.values():
        a.inner = None
    w.engine = S._Accounting(w.engine)
    w.router = None
    t0 = time.perf_counter()
    out["numbers"] = dict(S.numbers(w, ctx.get("control")),
                          **arm_numbers(w, params))
    out["check_s"] = time.perf_counter() - t0
    return out
