"""Serve driver: requests through the router's served path.

The program under test is the async engine (``AsyncRouterEngine``) in
front of the device-resident router (``DevicePolicyRouter``) over a
fleet of table-replay arms. This driver builds them from the
configuration file and offers the traffic mix's load from one host
thread as a closed loop: ``population`` clients, each sending its next
request when the previous one completes. A request's latency runs from
its issue to its completion.

``end_slice`` (replay SGD + A^-1 rebuild) runs every
``slice_every_decides`` microbatches, as a deployment's train cadence.
The warm-up serves ``warmup_decides`` microbatches through the same loop
(every program compiles there, and the replay ring fills), then the
window measures for the run's seconds.

Correctness: a seeded reservoir of decided microbatches, Woodbury
updates and slices (the replay SGD and the rebuild) is captured during
the window, with a host copy of the router state each leg read and
wrote, and compared after the window with ``bench.reference.router``.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Reservoir, Spans, p, split_seed, worst

GATE_BAND = 2e-3   # gate values this close to tau_g may take either branch


# ------------------------------------------------------------- proxies --
class RouterProxy:
    """Wraps the router: host time in each call, and host copies of the
    state each sampled leg read and wrote, for the check after the
    window. The copies go to the host at once: the router donates its
    state to the next update or train, and a device copy still in flight
    then can read the buffer after the donating program has written
    it."""

    serving_v2 = True

    def __init__(self, inner, spans: Spans, samples: dict):
        self.inner = inner
        self.spans = spans
        self.samples = samples
        self.secs = {"decide": 0.0, "update": 0.0, "end_slice": 0.0}
        self.rows = {"decide": 0, "update": 0}
        self.recording = False

    def _copy(self, tree):
        import jax
        return jax.tree.map(np.array, tree)

    def decide(self, x_emb=None, x_feat=None, domain=None, *,
               sample_idx=None, avail=None):
        slot = self.samples["decide"].offer() if self.recording else None
        snap = None
        if slot is not None:
            st = self.inner.state
            snap = self._copy({"params": st["params"], "ainv": st["ainv"]})
        t0 = time.perf_counter()
        with self.spans("bench.router.decide"):
            out = self.inner.decide(sample_idx=sample_idx, avail=avail)
        self.secs["decide"] += time.perf_counter() - t0
        self.rows["decide"] += int(out["n"])
        if slot is not None:
            self.samples["decide"].put(slot, {
                "state": snap, "ids": np.asarray(out["ids"]).copy(),
                "action": out["action"].copy(),
                "g": np.array(out["aux"][0]),
                "mu_safe": np.array(out["aux"][1]), "n": int(out["n"])})
        return out

    def update_wave(self, decision, served, rewards, learn_mask=None):
        slot = self.samples["update"].offer() if self.recording else None
        before = None
        if slot is not None:
            before = self._copy(self.inner.state["ainv"])
        t0 = time.perf_counter()
        with self.spans("bench.router.update"):
            n = self.inner.update_wave(decision, served, rewards,
                                       learn_mask=learn_mask)
        self.secs["update"] += time.perf_counter() - t0
        self.rows["update"] += int(n)
        if slot is not None:
            B = decision["n"]
            learn = (np.ones(B, bool) if learn_mask is None
                     else np.asarray(learn_mask, bool).copy())
            learn &= np.asarray(served)[:B] == decision["action"]
            self.samples["update"].put(slot, {
                "before": before,
                "after": self._copy(self.inner.state["ainv"]),
                "g": np.array(decision["aux"][0]), "learn": learn,
                "n": B})
        return n

    def end_slice(self, epochs=None):
        slot = self.samples["slice"].offer() if self.recording else None
        before = None
        if slot is not None:
            r = self.inner
            st = r.state
            # what the replay SGD reads: weights, optimizer state, its
            # key, the ring; train and rebuild never write the ring
            before = self._copy({
                "params": st["params"], "opt": st["opt"], "key": r._key,
                "bufs": st["bufs"], "env_idx": r._env_idx})
            before["cum0"] = np.concatenate(
                [[0], np.cumsum(r._counts)]).astype(np.int64)
            before["t"] = min(r.wave, r.T) - 1
        t0 = time.perf_counter()
        with self.spans("bench.router.end_slice"):
            self.inner.end_slice(epochs)
        self.secs["end_slice"] += time.perf_counter() - t0
        if slot is not None:
            st = self.inner.state
            self.samples["slice"].put(slot, {"before": before,
                                             "after": self._copy({
                                                 "params": st["params"],
                                                 "opt": st["opt"],
                                                 "ainv": st["ainv"]})})


# ------------------------------------------------------------- traffic --
def prompt_pool(traffic: dict, rng: np.random.Generator) -> list:
    """Prompt token arrays. Lengths: a fixed multiset from the traffic
    file's ``shape_seed`` (uniform over [min, max]), in the seed's
    order; token ids: from the run's seed."""
    pl = traffic["prompt_len"]
    n = int(traffic.get("prompt_pool", 4096))
    base = np.random.default_rng(int(traffic["shape_seed"]))
    lens = base.integers(pl["min"], pl["max"] + 1, size=n)
    lens = lens[rng.permutation(n)]
    vocab = int(traffic.get("token_vocab", 50_000))
    return [rng.integers(1, vocab, size=int(L), dtype=np.int32)
            for L in lens]


# --------------------------------------------------------------- world --
class World:
    """Everything one run builds from its seed."""


def _router_config(cfg: dict):
    from repro.core.utilitynet import UtilityNetConfig
    r = cfg["router"]
    return UtilityNetConfig(
        emb_dim=r["emb_dim"], feat_dim=r["feat_dim"],
        num_domains=r["num_domains"], num_actions=cfg["fleet"]["arms"],
        d_domain=r["d_domain"], d_action=r["d_action"], d_text=r["d_text"],
        d_feat=r["d_feat"], d_hidden=r["d_hidden"], d_last=r["d_last"],
        d_gate=r["d_gate"], huber_delta=cfg["train"]["huber_delta"])


def build(cfg: dict, traffic: dict, seed: int, trace: bool) -> World:
    """Data, router and engine for one run, all from ``seed``."""
    from repro.data.routerbench import RouterBenchSim
    from repro.serving import AsyncRouterEngine, DevicePolicyRouter
    from repro.sim.engine import _tables
    from repro.sim.env import DeviceReplayEnv
    from repro.sim.policies import TRAIN_CHUNK, make_policy

    w = World()
    w.cfg, w.traffic = cfg, traffic
    w.seeds = split_seed(seed)
    w.spans = Spans(trace)
    rng = np.random.default_rng(w.seeds["checks"])
    k = traffic["sample_checks"]
    w.samples = {name: Reservoir(k[name], rng)
                 for name in ("decide", "update", "slice")}

    fleet = cfg["fleet"]
    henv = RouterBenchSim(seed=w.seeds["data"], n_samples=fleet["n_samples"],
                          n_slices=fleet["n_slices"])
    env = DeviceReplayEnv.from_host(henv)
    ucfg = _router_config(cfg)
    if (henv.x_emb.shape[1], env.K) != (ucfg.emb_dim, ucfg.num_actions):
        raise ValueError(f"data has emb {henv.x_emb.shape[1]} and "
                         f"{env.K} arms; the configuration says "
                         f"{ucfg.emb_dim} and {ucfg.num_actions}")
    r = cfg["router"]
    pol, hyp = make_policy(
        r["policy"], env, ucfg, beta=r["beta"], tau_g=r["tau_g"],
        gate_margin=r["gate_margin"], lr=cfg["train"]["lr"],
        ridge_lambda0=r["ridge_lambda0"], ucb_backend=r["ucb_backend"])
    # host copies of the request features, for the reference
    w.x_emb = np.asarray(henv.x_emb, np.float32)
    w.x_feat = np.asarray(henv.data["x_feat"], np.float32)
    w.domain = np.asarray(henv.data["domain"], np.int32)
    w.stream = np.concatenate([np.asarray(s) for s in henv.slices])
    w.train_chunks = -(-int(traffic["train_steps"]) // TRAIN_CHUNK)
    w.chunk_steps = TRAIN_CHUNK
    router = DevicePolicyRouter(
        pol, hyp, _tables(env), seed=w.seeds["router"],
        slice_width=traffic["decide_batch"],
        capacity_slices=traffic["ring_slices"],
        batch_size=traffic["train_batch"], train_chunks=w.train_chunks,
        max_train_lag=traffic["max_train_lag"])
    w.router = RouterProxy(router, w.spans, w.samples)
    w.engine = AsyncRouterEngine(
        w.router, env.K, reward_table=np.asarray(env.reward),
        quality_table=np.asarray(env.quality),
        cost_table=np.asarray(env.cost),
        queue_capacity=traffic["queue_capacity"],
        decide_batch=traffic["decide_batch"],
        serve_batch=traffic["serve_batch"])
    w.prompts = prompt_pool(traffic, np.random.default_rng(
        w.seeds["traffic"]))
    w.cursor = 0
    w.profile = _Profile(None, 0.0)
    return w


def _requests(w: World, n: int):
    from repro.serving.batcher import Request
    out = []
    P, S = len(w.prompts), w.stream.size
    for _ in range(n):
        c = w.cursor
        out.append(Request(tokens=w.prompts[c % P],
                           sample_idx=int(w.stream[c % S])))
        w.cursor += 1
    return out


# ---------------------------------------------------------------- loop --
class Tally:
    """What one stretch of the loop measured."""

    def __init__(self):
        self.lat = []          # seconds from issue to completion
        self.issued = 0
        self.ok = 0
        self.failed = 0
        self.frontend_s = 0.0
        self.slices = 0


def _slice_due(w: World) -> None:
    every = int(w.traffic["slice_every_decides"])
    while w.engine.counters["decide_calls"] - w.last_slice >= every:
        w.engine.end_slice()
        w.last_slice += every
        w.tally.slices += 1


def _timed(w: World, fn, *a, **kw):
    """Front-end call: its wall, less what the router spent."""
    inner0 = w.router.secs["decide"] + w.router.secs["update"]
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    inner = w.router.secs["decide"] + w.router.secs["update"] - inner0
    w.tally.frontend_s += (time.perf_counter() - t0) - inner
    return out


def _complete(w: World, recs, due: dict, t: float) -> None:
    for rec in recs:
        d = due.pop(rec["rid"], None)
        if d is None:
            continue
        if rec["status"] == "ok":
            w.tally.ok += 1
            w.tally.lat.append(t - d)
        else:
            w.tally.failed += 1
            w.missing.append(d)


def _submit(w: World, reqs, due: dict, t_due: float) -> None:
    for r in reqs:
        due[r.rid] = t_due
    shed0 = w.engine.counters["shed_queue_full"]
    with w.spans("bench.frontend.submit"):
        _timed(w, w.engine.submit, reqs)
    shed = w.engine.counters["shed_queue_full"] - shed0
    if shed:
        # the engine sheds the newest arrivals when the queue is full
        for r in reqs[len(reqs) - shed:]:
            w.missing.append(due.pop(r.rid))
        w.tally.failed += shed
    w.tally.issued += len(reqs)


def _pump(w: World, due: dict, force: bool = False) -> None:
    with w.spans("bench.frontend.pump"):
        recs = _timed(w, w.engine.drain if force else w.engine.pump)
    _complete(w, recs, due, time.perf_counter())
    _slice_due(w)
    w.profile.tick()


def _closed(w: World, seconds: float, decides: int = 0) -> float:
    pop = int(w.traffic["population"])
    due: dict = {}
    t0 = time.perf_counter()
    _submit(w, _requests(w, pop), due, t0)
    while True:
        _pump(w, due)
        t = time.perf_counter()
        done = (w.engine.counters["decide_calls"] >= decides if decides
                else t - t0 >= seconds)
        if done:
            break
        # each completed client sends its next request at once
        missing = pop - len(due)
        if missing:
            _submit(w, _requests(w, missing), due, t)
    while due:
        _pump(w, due, force=True)
    return time.perf_counter() - t0


class _Profile:
    """The profiler over the first ``seconds`` of the window (a trace of
    a whole window of a busy cell runs to hundreds of MB), inside the
    host span ``bench.window`` that the trace reduction clips to."""

    def __init__(self, trace_dir, seconds: float, w=None):
        self.dir, self.seconds, self.w = trace_dir, seconds, w
        self.t0 = self.span = None
        self.counts = {}       # what the traced part did

    def _counts(self):
        return {"decide_calls": self.w.engine.counters["decide_calls"]}

    def start(self):
        if self.dir:
            import jax
            self.counts = self._counts()
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.t0 = time.perf_counter()

    def tick(self):
        if self.span is not None and \
                time.perf_counter() - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        if self.span is not None:
            import jax
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()
            self.counts = {k: v - self.counts[k]
                           for k, v in self._counts().items()}


def drive(w: World, seconds: float, decides: int = 0) -> Tally:
    """Offer the traffic mix for ``seconds`` (or, in the warm-up, until
    ``decides`` microbatches). Returns what was measured."""
    w.tally = Tally()
    w.missing = []
    w.last_slice = w.engine.counters["decide_calls"]
    w.tally.window_s = _closed(w, seconds, decides)
    return w.tally


def end_to_end(w: World, tally: Tally) -> dict:
    """The cell's end-to-end numbers from one window."""
    win = tally.window_s
    # a request that failed or never completed misses every limit: it
    # counts as waiting from its issue to the end of the window
    lat = list(tally.lat) + [win - (d - w.t_window) for d in w.missing]
    return {"requests_per_s": tally.ok / win,
            "latency_p95_ms": p(lat, 95) * 1e3}


def layer_data(w: World, tally: Tally, counters0: dict) -> dict:
    """Raw numbers the per-layer metric readers take theirs from."""
    c = {k: w.engine.counters[k] - counters0[k] for k in counters0}
    walls = w.engine.decide_wall_s[counters0["decide_calls"]:]
    return {"window_s": tally.window_s, "completed": tally.ok,
            "attempted": tally.issued, "frontend_s": tally.frontend_s,
            "router_s": dict(w.router.secs),
            "router_rows": dict(w.router.rows), "slices": tally.slices,
            "decide_walls_s": list(walls), "counters": c,
            "ring_rows": int(min(w.router.inner.wave,
                                 w.router.inner.T)) * w.router.inner.S,
            "decide_batch": int(w.traffic["decide_batch"]),
            "train_steps": int(w.traffic["train_steps"]),
            "train_batch": int(w.traffic["train_batch"]),
            "router_widths": w.cfg["router"], "arms": w.cfg["fleet"]["arms"],
            # decide calls inside the traced part
            "traced": dict(w.profile.counts)}


# --------------------------------------------------------------- check --
def _train_batches(w: World, before: dict) -> list:
    """The minibatches the slice's replay SGD drew, built from the
    reference's own tables and the ring's feedback."""
    from bench.reference import router as R
    rows, cols = R.train_rows(before["key"], before["cum0"], before["t"],
                              w.train_chunks, w.chunk_steps,
                              int(w.traffic["train_batch"]))
    bufs, env_idx = before["bufs"], before["env_idx"]
    out = []
    for row, col in zip(rows, cols):
        sid = env_idx[row, col]
        out.append({"x_emb": w.x_emb[sid], "x_feat": w.x_feat[sid],
                    "domain": w.domain[sid],
                    **{k: bufs[k][row, col] for k in (
                        "action", "reward", "gate_label", "w", "gate_w")}})
    return out


def _slice_numbers(w: World, s: dict, prec) -> dict:
    """Replay SGD and rebuild of one sampled slice. With ``prec`` None
    the program's results are compared with the reference; otherwise
    the reference at ``prec`` stands in the program's place."""
    from bench.reference import router as R
    hp = {k: v for k, v in w.cfg["train"].items() if k != "optimizer"}
    r = w.cfg["router"]
    before, after = s["before"], s["after"]
    batches = _train_batches(w, before)
    want = R.train(before["params"], before["opt"], batches, hp)
    got = after
    if prec is not None:
        low = R.train(before["params"], before["opt"], batches, hp, prec)
        got = {"params": low[0], "opt": low[1]}
    out = R.train_numbers(before, got,
                          {"params": want[0], "opt": want[1]}, want[2],
                          hp["b2"], len(batches))
    # the rebuild from the weights the slice trained, over the ring
    t = before["t"]
    ids = np.asarray(before["env_idx"])[:t + 1].reshape(-1)
    args = (got["params"], w.x_emb[ids], w.x_feat[ids], w.domain[ids],
            np.asarray(before["bufs"]["action"])[:t + 1].reshape(-1),
            np.asarray(before["bufs"]["w"])[:t + 1].reshape(-1),
            r["ridge_lambda0"])
    ref = R.rebuild(*args)
    ainv = after["ainv"] if prec is None else R.rebuild(*args, prec=prec)
    out["rebuild_err"] = R.matrix_err(ainv, ref)
    return out


def numbers(w: World, prec=None) -> dict:
    """Compare the sampled legs with the reference. Returns the numbers,
    each the worst over its samples (the limits live in the
    configuration file). ``prec`` set: the control, the reference in
    that precision in the program's place (not run by the benchmark's
    own runs)."""
    from bench.reference import router as R
    r = w.cfg["router"]
    out = {"decide_gap": 0.0, "feature_err": 0.0, "mu_safe_err": 0.0,
           "update_err": 0.0, "train_step_err": 0.0,
           "train_grad_err": 0.0, "rebuild_err": 0.0}

    def take(got):
        for k, v in got.items():
            out[k] = worst(out[k], v)

    for s in w.samples["decide"].items:
        ids, n = s["ids"], s["n"]
        args = (s["state"]["params"], s["state"]["ainv"], w.x_emb[ids],
                w.x_feat[ids], w.domain[ids], r["beta"], r["tau_g"])
        ref = R.decide(*args)
        if prec is None:
            a, g, mu = (s["action"], np.asarray(s["g"])[:n],
                        np.asarray(s["mu_safe"])[:n])
        else:
            a, g, mu = R.choose(R.decide(*args, prec=prec), r["tau_g"])
        take(R.decide_numbers(ref, r["tau_g"], GATE_BAND, a, g, mu))
    for s in w.samples["update"].items:
        g = np.asarray(s["g"])[:s["n"]][s["learn"]]
        want = R.woodbury(s["before"], g)
        got = s["after"] if prec is None else R.woodbury(s["before"], g,
                                                         prec)
        take({"update_err": R.matrix_err(got, want)})
    for s in w.samples["slice"].items:
        take(_slice_numbers(w, s, prec))
    if prec is None:
        out["lost"] = float(w.engine.check_accounting()["lost"])
    return out


# ----------------------------------------------------------------- run --
def run(ctx: dict) -> dict:
    """One run of one serve cell (see ``bench.harness``)."""
    from repro.common.compilation import CompileCount

    cfg, traffic = ctx["config"], ctx["traffic"]
    w = build(cfg, traffic, ctx["seed"], ctx["trace"])
    # the warm-up is the window's own loop, so it compiles just the
    # shapes the traffic uses; it records too, so the state copies the
    # window's checks take compile here
    w.router.recording = True
    drive(w, 0.0, decides=int(traffic["warmup_decides"]))
    for res in w.samples.values():
        res.seen, res.items = 0, []
    w.router.secs = {k: 0.0 for k in w.router.secs}
    w.router.rows = {k: 0 for k in w.router.rows}
    counters0 = dict(w.engine.counters)
    w.profile = _Profile(ctx.get("trace_dir"),
                         float(traffic.get("trace_seconds", ctx["seconds"])),
                         w)
    with CompileCount() as cc:
        w.t_window = time.perf_counter()
        w.profile.start()
        tally = drive(w, float(ctx["seconds"]))
        w.profile.stop()
    w.router.recording = False
    memory = ctx["memory_peak"]()
    out = {"t_window": w.t_window, "attempted": tally.issued,
           "failed": tally.failed + (w.engine.counters["decide_errors"]
                                     - counters0["decide_errors"]),
           "end_to_end": end_to_end(w, tally),
           "layer": layer_data(w, tally, counters0),
           "memory_peak_bytes": memory, "compiles_in_window": cc.compiles}
    # the program's state goes before the reference runs
    w.engine = _Accounting(w.engine)
    w.router = None
    t0 = time.perf_counter()
    out["numbers"] = numbers(w, ctx.get("control"))
    out["check_s"] = time.perf_counter() - t0
    return out


class _Accounting:
    """What the check still needs of the engine once the router state is
    released: its accounting verdict, taken at the window's close."""

    def __init__(self, engine):
        self._acct = engine.check_accounting()

    def check_accounting(self):
        return self._acct
