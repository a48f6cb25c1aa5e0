"""Device-resident serving router: any `BanditPolicy` behind the async
engine (DESIGN.md §12.2).

The sim engine (`sim/engine.py`) already runs the whole protocol —
DECIDE / UPDATE / TRAIN / REBUILD — as jitted device code against
resident replay tables. This adapter reuses those exact policy callbacks
for SERVING: router state (net, optimizer, A^-1, outcome ring buffers)
never leaves the device, requests carry only their sample id (features
are gathered on device from the resident tables — zero host feature
transfer per request), and each microbatch is ONE jitted decide call and
ONE jitted update call regardless of batch width, each crossing between
host and device once each way: its small inputs travel as one packed
int32 buffer, the decide's outputs come back as one.

Outcome buffers are a (T, S) ring: row = wave mod capacity, S = the
microbatch width. `end_slice` runs the policy's chunked replay SGD +
Cholesky rebuild over everything the ring holds, with the same PRNG
discipline as the scanned runner — a wave-per-slice serving run is
bit-identical to `run_policy_device` (tests/test_serving_async.py).

Fallback remaps (a request rerouted after decide because its arm went
down mid-flight) are EXCLUDED from learning by default: the decide aux
(features g, safe mean) describes the decided arm, and the adapter is
policy-agnostic so it cannot recompute aux for an arbitrary policy.
Remapped rows get weight 0 and are counted by the engine; the common
outage path never hits this — `decide` takes the live availability mask,
so availability-aware policies never pick a down arm in the first place.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.common.tracing import span
from repro.sim.policies import (
    VANILLA_FORGETTING,
    BanditPolicy,
    ForgettingConfig,
    PolicyCtx,
)

_STATIC = ("policy", "fcfg", "train_chunks", "batch_size")


def _ctx(tables, hyp, *, env_idx=None, cum0=None, t=None, idx=None,
         mask=None, avail=None, fcfg=VANILLA_FORGETTING, train_chunks=1,
         batch_size=256):
    return PolicyCtx(tables=tables, env_idx=env_idx, cum0=cum0, hyp=hyp,
                     eff=None, t=t, idx=idx, mask=mask, avail=avail,
                     delay=0, fcfg=fcfg, train_chunks=train_chunks,
                     batch_size=batch_size)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _srv_init(policy: BanditPolicy, key, tables, hyp, env_idx,
              fcfg=VANILLA_FORGETTING, train_chunks=1, batch_size=256):
    tables = policy.prepare(tables, hyp)
    cum0 = jnp.zeros(env_idx.shape[0] + 1, jnp.int32)
    ctx = _ctx(tables, hyp, env_idx=env_idx, cum0=cum0, fcfg=fcfg,
               train_chunks=train_chunks, batch_size=batch_size)
    state, key = policy.init(key, ctx)
    return state, key, tables


def _decide(policy: BanditPolicy, state, key, tables, hyp, ids, avail, t,
            fcfg=VANILLA_FORGETTING, train_chunks=1, batch_size=256):
    """The policy's decide over resident-table rows ``ids`` with ``key``
    as given: ``(a, logp, aux)``."""
    batch = {"x_emb": tables["x_emb"][ids], "x_feat": tables["x_feat"][ids],
             "domain": tables["domain"][ids]}
    ctx = _ctx(tables, hyp, t=t, idx=ids, avail=avail, fcfg=fcfg,
               train_chunks=train_chunks, batch_size=batch_size)
    with jax.named_scope("router.decide"):
        return policy.decide(state, key, batch, ctx)


# offline scoring (experiments/ope.py): the decide with an explicit key
# and unpacked outputs
_srv_decide_rows = functools.partial(
    jax.jit, static_argnames=_STATIC)(_decide)


def _pack_decision(a, logp):
    """int32 (2, S): the actions, then logp's float32 bits, so the host
    reads both with one copy (:func:`_unpack_decision`). A policy's
    scalar or zeros logp is broadcast to the actions' shape."""
    lp = jnp.broadcast_to(jnp.asarray(logp, jnp.float32), a.shape)
    return jnp.stack([a.astype(jnp.int32),
                      lax.bitcast_convert_type(lp, jnp.int32)])


def _unpack_decision(packed: np.ndarray, B: int):
    """Host side of :func:`_pack_decision`: (action int32, logp float32)
    of the first ``B`` rows, each its own array."""
    return packed[0, :B].copy(), packed[1, :B].copy().view(np.float32)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _srv_decide(policy: BanditPolicy, state, key, tables, hyp, host, avail,
                fcfg=VANILLA_FORGETTING, train_chunks=1, batch_size=256):
    """One serving decide. ``host`` is the call's one int32 input: the
    ids padded to S, then the step t. The carried ``key`` splits here,
    once a call, as the scanned runner splits per decide (threefry is
    integer arithmetic, so this equals a split on the host). Returns
    ``(packed, aux, key)``: :func:`_pack_decision`'s buffer, the decide
    aux (left on the device for the update) and the next carried key."""
    key, k = jax.random.split(key)
    a, logp, aux = _decide(policy, state, k, tables, hyp, host[:-1], avail,
                           host[-1], fcfg=fcfg, train_chunks=train_chunks,
                           batch_size=batch_size)
    return _pack_decision(a, logp), aux, key


def _pack_update(row: int, ids, served, rewards, learn, perm) -> np.ndarray:
    """The update's one int32 input, of ``1 + 5 S`` entries: ``row``, then
    the ids, served arms, rewards' float32 bits and learn mask's float32
    bits, each zero-padded to ``S = perm.size``, then ``perm``
    (:func:`_unpack_update` inverts it exactly)."""
    S, B = perm.size, len(ids)
    buf = np.zeros(1 + 5 * S, np.int32)
    buf[0] = row
    seg = buf[1:].reshape(5, S)
    seg[0, :B] = ids
    seg[1, :B] = served
    seg[2, :B] = np.asarray(rewards, np.float32).view(np.int32)
    seg[3, :B] = np.asarray(learn, np.float32).view(np.int32)
    seg[4] = perm
    return buf


def _unpack_update(host):
    """``(row, ids, a, r, mask, perm)`` of a :func:`_pack_update` buffer,
    inside the program: int32 values sliced, float32 values bitcast."""
    S = (host.shape[0] - 1) // 5
    ids, a, r, mask, perm = host[1:].reshape(5, S)
    return (host[0], ids, a, lax.bitcast_convert_type(r, jnp.float32),
            lax.bitcast_convert_type(mask, jnp.float32), perm)


def _update(policy: BanditPolicy, state, env_idx, tables, hyp, row, ids, a,
            r, mask, perm, aux, fcfg=VANILLA_FORGETTING, train_chunks=1,
            batch_size=256):
    """One microbatch's feedback write + A^-1 maintenance. ``perm``
    compacts learnable rows to the row prefix (ring rows keep the
    prefix-validity layout `_sample_valid` assumes); identity when
    nothing was remapped or shed, so the permuted gather is a no-op and
    the sim-parity path stays bit-exact."""
    n = perm.shape[0]
    ids, a, r, mask = ids[perm], a[perm], r[perm], mask[perm]
    aux = jax.tree_util.tree_map(
        lambda x: x[perm] if (getattr(x, "ndim", 0) >= 1
                              and x.shape[0] == n) else x, aux)
    env_idx = env_idx.at[row].set(ids)
    batch = {"x_emb": tables["x_emb"][ids], "x_feat": tables["x_feat"][ids],
             "domain": tables["domain"][ids]}
    ctx = _ctx(tables, hyp, env_idx=env_idx, t=row, idx=ids, mask=mask,
               fcfg=fcfg, train_chunks=train_chunks, batch_size=batch_size)
    with jax.named_scope("router.update"):
        state = policy.update(state, batch, a, r, ctx, aux)
    return state, env_idx


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("state", "env_idx"))
def _srv_update(policy: BanditPolicy, state, env_idx, tables, hyp, host,
                aux, fcfg=VANILLA_FORGETTING, train_chunks=1,
                batch_size=256):
    """:func:`_update` fed by the call's one int32 input ``host``
    (:func:`_pack_update`). ``state`` and ``env_idx`` are donated — the
    router rebinds both from the outputs every wave, so the ring buffers
    and A^-1 update in place."""
    return _update(policy, state, env_idx, tables, hyp,
                   *_unpack_update(host), aux, fcfg=fcfg,
                   train_chunks=train_chunks, batch_size=batch_size)


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("state",))
def _srv_train(policy: BanditPolicy, state, key, tables, hyp, env_idx,
               cum0, t, fcfg=VANILLA_FORGETTING, train_chunks=1,
               batch_size=256):
    """``state`` is donated: the sync path rebinds it immediately, and
    the overlapped path (``max_train_lag > 0``) feeds a device-side copy
    so the committed state decide reads stays live."""
    ctx = _ctx(tables, hyp, env_idx=env_idx, cum0=cum0, t=t, fcfg=fcfg,
               train_chunks=train_chunks, batch_size=batch_size)
    with jax.named_scope("router.train_sgd"):
        state, key = policy.train(state, key, ctx)
    with jax.named_scope("router.rebuild"):
        state = policy.rebuild(state, ctx)
    return state, key


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("state",))
def _srv_train_sgd(policy: BanditPolicy, state, key, tables, hyp, env_idx,
                   cum0, t, fcfg=VANILLA_FORGETTING, train_chunks=1,
                   batch_size=256):
    """Replay-SGD stage only — the overlapped path dispatches this and
    `_srv_rebuild` as SEPARATE device programs so an interleaved decide
    queues behind at most one stage, not the whole train (the fused
    `_srv_train` would head-of-line-block the decide stream for its full
    duration on a busy device)."""
    ctx = _ctx(tables, hyp, env_idx=env_idx, cum0=cum0, t=t, fcfg=fcfg,
               train_chunks=train_chunks, batch_size=batch_size)
    with jax.named_scope("router.train_sgd"):
        return policy.train(state, key, ctx)


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("state",))
def _srv_rebuild(policy: BanditPolicy, state, tables, hyp, env_idx, cum0,
                 t, fcfg=VANILLA_FORGETTING, train_chunks=1,
                 batch_size=256):
    """A^-1 rebuild stage of the staged overlapped train."""
    ctx = _ctx(tables, hyp, env_idx=env_idx, cum0=cum0, t=t, fcfg=fcfg,
               train_chunks=train_chunks, batch_size=batch_size)
    with jax.named_scope("router.rebuild"):
        return policy.rebuild(state, ctx)


def _merge_trained(new, cur):
    """Commit a finished train into the live router state: trained
    leaves come from ``new``, but the outcome ring keeps the LIVE
    ``cur["bufs"]`` — waves absorbed while the train was in flight must
    not be rolled back to the dispatch-time snapshot (train/rebuild
    never write bufs, so ``new["bufs"]`` is exactly that stale
    snapshot). Non-dict or ring-less states commit wholesale."""
    if (isinstance(new, dict) and isinstance(cur, dict)
            and "bufs" in new and "bufs" in cur):
        return dict(new, bufs=cur["bufs"])
    return new


def _tree_ready(tree) -> bool:
    return all(leaf.is_ready()
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "is_ready"))


class DevicePolicyRouter:
    """Serving face of the `BanditPolicy` zoo (class docstring above).

    ``tables`` is the resident-table dict (`sim.engine._tables(env)`);
    ``slice_width`` is the microbatch capacity S (decide pads shorter
    batches); ``capacity_slices`` is the ring depth T. The PRNG
    discipline mirrors the scanned runner exactly: one split per decide
    call, run inside the decide program, which returns the carried key;
    train splits further from the carried stream.

    ``max_train_lag`` bounds the zero-sync train overlap (DESIGN.md
    §15.2). 0 (default): ``end_slice`` blocks until train + rebuild
    finish — bit-identical to the sim scan. N > 0: ``end_slice``
    dispatches train on a device-side copy of the freshest state and
    returns immediately; decide keeps reading the last COMMITTED state
    while at most N trains are in flight (dispatching the (N+1)-th
    blocks on the oldest). Finished trains commit lazily before each
    decide — trained params/opt/A^-1 land atomically while the live
    outcome ring (which kept absorbing waves) is preserved, so feedback
    is never lost and decide staleness is bounded by
    ``train_epoch - committed_epoch <= max_train_lag``."""

    def __init__(self, policy: BanditPolicy, hypers: Any, tables: Dict,
                 *, seed: int = 0, slice_width: int = 256,
                 capacity_slices: int = 256, batch_size: int = 256,
                 train_chunks: int = 1,
                 fcfg: ForgettingConfig = VANILLA_FORGETTING,
                 pretrained_state: Any = None, log_capacity: int = 0,
                 max_train_lag: int = 0):
        self.policy = policy
        self.hyp = hypers
        self.S = int(slice_width)
        self.T = int(capacity_slices)
        self.batch_size = int(batch_size)
        self.train_chunks = int(train_chunks)
        self.fcfg = fcfg
        self.num_actions = int(np.asarray(tables["reward"]).shape[1])
        env_idx = jnp.zeros((self.T, self.S), jnp.int32)
        self.state, self._key, self.tables = _srv_init(
            policy, jax.random.PRNGKey(seed), tables, hypers, env_idx,
            fcfg=fcfg, train_chunks=train_chunks, batch_size=batch_size)
        if pretrained_state is not None:
            # warm start (DESIGN.md §13.3): the offline phase's state
            # (sim.pretrain_policy_state) replaces the fresh init; the
            # PRNG stream is untouched, matching the scanned runner's
            # init_state injection. A REAL copy (not asarray's identity
            # on device arrays): update/train donate their state args,
            # and the caller's checkpoint must survive that.
            self.state = jax.tree_util.tree_map(
                lambda x: jnp.array(x), pretrained_state)
        self._env_idx = env_idx
        # zero-sync train overlap (max_train_lag > 0): FIFO of dispatched
        # but uncommitted (epoch, state) train results
        self.max_train_lag = int(max_train_lag)
        if self.max_train_lag < 0:
            raise ValueError("max_train_lag must be >= 0")
        self._pending: list = []
        self.train_epoch = 0       # trains dispatched
        self.committed_epoch = 0   # trains visible to decide
        self._counts = np.zeros(self.T, np.int64)  # learned rows per ring row
        self.wave = 0          # microbatches absorbed (ring write cursor)
        self.slices = 0        # end_slice count (0 = warm)
        # propensity-aware request log (DESIGN.md §13.1): bounded ring of
        # LEARNED rows, drained by to_logged(); 0 disables (the storm
        # bench path pays nothing)
        self.log_capacity = int(log_capacity)
        self._log: list = []
        self._log_rows = 0

    def _statics(self):
        return dict(fcfg=self.fcfg, train_chunks=self.train_chunks,
                    batch_size=self.batch_size)

    # --------------------------------------------- train-overlap plumbing --
    @property
    def decide_staleness(self) -> int:
        """Trains dispatched but not yet visible to decide; bounded by
        ``max_train_lag`` at every point (tests/test_serving_async.py)."""
        return self.train_epoch - self.committed_epoch

    def _commit(self, epoch, out) -> None:
        self.state = _merge_trained(out, self.state)
        self.committed_epoch = epoch

    def _dispatch_rebuild(self, entry) -> None:
        """Advance a pending train from its finished SGD stage to the
        rebuild stage (one async dispatch). The SGD output is donated
        into the rebuild but stays referenced as the entry's keep-alive:
        dropping the last reference to a donated array blocks the host
        until the consuming computation finishes."""
        _epoch, _stage, s1, _keep, (env_c, cum0, t) = entry
        s2 = _srv_rebuild(self.policy, s1, self.tables, self.hyp,
                          env_c, cum0, t, **self._statics())
        entry[1] = "rebuild"
        entry[2] = s2
        entry[3] = s1

    def _advance(self) -> None:
        """Non-blocking pipeline tick (called before each decide reads
        the state and at every slice boundary): dispatch the rebuild
        stage for any train whose SGD finished, then commit every
        train whose rebuild finished, oldest first."""
        for entry in self._pending:
            if entry[1] == "sgd" and _tree_ready(entry[2]):
                self._dispatch_rebuild(entry)
        while (self._pending and self._pending[0][1] == "rebuild"
               and _tree_ready(self._pending[0][2])):
            entry = self._pending.pop(0)
            self._commit(entry[0], entry[2])

    def _force_oldest(self) -> None:
        """Blockingly drive the oldest in-flight train to commit."""
        entry = self._pending.pop(0)
        if entry[1] == "sgd":
            jax.block_until_ready(entry[2])
            self._dispatch_rebuild(entry)
        jax.block_until_ready(entry[2])
        self._commit(entry[0], entry[2])

    def _flush(self) -> None:
        """Block until every in-flight train is committed — snapshot,
        log-export, and restore paths need the fully-settled state."""
        while self._pending:
            self._force_oldest()

    def warmup(self) -> None:
        """Compile both decide variants (mask-free fast path and masked
        outage path) at their packed signatures with a throwaway key
        and input, so jit compile time never lands in a storm's
        decide-latency samples. State and PRNG stream are untouched —
        compilation caches by shape, not value, and the key the program
        returns is dropped."""
        host = jax.device_put(np.zeros(self.S + 1, np.int32))
        for av in (None, jnp.ones(self.num_actions, jnp.float32)):
            packed, _, _ = _srv_decide(
                self.policy, self.state, jax.random.PRNGKey(0), self.tables,
                self.hyp, host, av, **self._statics())
            jax.block_until_ready(packed)

    # ----------------------------------------------------------- DECIDE --
    def decide(self, *, sample_idx, avail=None) -> Dict:
        """Decide for a microbatch of replay sample ids. ``avail`` is the
        engine's live arm-health mask ((K,) float, 1 = up); None or
        all-up takes the stationary fast trace (bit-identical to the sim
        scan's no-scenario path). The fast path crosses between host
        and device once each way: the ids and step go over as one
        buffer, the program splits the carried key, and action and logp
        come back as one; a mask with an arm down is one more put."""
        ids = np.asarray(sample_idx, np.int64).reshape(-1)
        B = ids.size
        with span("router.decide", rows=B):
            with span("router.decide.prep") as prep:
                if not 0 < B <= self.S:
                    raise ValueError(
                        f"microbatch size {B} outside (0, {self.S}]")
                host = np.zeros(self.S + 1, np.int32)
                host[:B] = ids
                host[-1] = min(self.slices, 1)
                av = None
                if avail is not None and not np.all(np.asarray(avail) > 0):
                    av = jax.device_put(np.asarray(avail, np.float32))
                if self._pending:
                    # overlapped mode: tick the train pipeline (SGD ->
                    # rebuild -> commit) — decide reads the freshest
                    # COMMITTED state
                    self._advance()
                host = jax.device_put(host)
                prep.set_metadata(puts=1 + (av is not None))
            with span("router.decide.launch"):
                packed, aux, self._key = _srv_decide(
                    self.policy, self.state, self._key, self.tables,
                    self.hyp, host, av, **self._statics())
            with span("router.decide.fetch", gets=1):
                action, logp = _unpack_decision(jax.device_get(packed), B)
        return {"action": action, "logp": logp, "ids": ids, "aux": aux,
                "n": B}

    # ----------------------------------------------------------- UPDATE --
    def update_wave(self, decision: Dict, served, rewards,
                    learn_mask=None) -> int:
        """Absorb one decided microbatch's outcomes into the ring.
        ``served`` are the arms actually run (== decided unless a
        fallback remapped); ``learn_mask`` marks rows to learn from
        (sheds and remaps excluded by the engine). The row, ids,
        outcomes, mask and row order go to the device as one buffer.
        Returns the number of rows learned."""
        B = decision["n"]
        with span("router.update", rows=B):
            with span("router.update.prep", puts=1):
                served = np.asarray(served, np.int32).reshape(-1)
                rewards = np.asarray(rewards, np.float32).reshape(-1)
                assert served.size == B and rewards.size == B
                learn = (np.ones(B, bool) if learn_mask is None
                         else np.asarray(learn_mask, bool).reshape(-1))
                decided = decision["action"]
                # remapped rows: aux is stale
                learn = learn & (served == decided)
                order = np.argsort(~learn, kind="stable")
                perm = np.concatenate(
                    [order, np.arange(B, self.S)]).astype(np.int32)
                row = self.wave % self.T
                host = jax.device_put(_pack_update(
                    row, decision["ids"], served, rewards, learn, perm))
            with span("router.update.launch"):
                self.state, self._env_idx = _srv_update(
                    self.policy, self.state, self._env_idx, self.tables,
                    self.hyp, host, decision["aux"], **self._statics())
        self._counts[row] = int(learn.sum())
        self.wave += 1
        if self.log_capacity and learn.any():
            lp = decision.get("logp")
            lp = (np.zeros(B, np.float32) if lp is None
                  else np.asarray(lp, np.float32).reshape(-1))
            self._log.append((
                np.asarray(decision["ids"], np.int64)[learn],
                served[learn].copy(), rewards[learn].copy(), lp[learn],
                np.full(int(learn.sum()), self.slices, np.int32)))
            self._log_rows += int(learn.sum())
            while self._log_rows > self.log_capacity and len(self._log) > 1:
                self._log_rows -= len(self._log.pop(0)[0])
        return int(learn.sum())

    # ------------------------------------------------------- REQUEST LOG --
    def to_logged(self):
        """Round-trip the serving request log into a
        :class:`repro.data.logged.LoggedInteractions` (DESIGN.md §13.1):
        the production loop's log -> pretrain -> redeploy closer. Only
        LEARNED rows are logged (sheds and fallback remaps carry no
        usable propensity); contexts are gathered from the resident
        tables. Requires ``log_capacity > 0`` at construction."""
        from repro.data.logged import LoggedInteractions
        if not self.log_capacity:
            raise ValueError(
                "DevicePolicyRouter: request logging is disabled "
                "(log_capacity=0); construct with log_capacity > 0")
        if not self._log:
            raise ValueError("DevicePolicyRouter: request log is empty — "
                             "serve some traffic first")
        ids = np.concatenate([c[0] for c in self._log])
        a = np.concatenate([c[1] for c in self._log])
        r = np.concatenate([c[2] for c in self._log])
        lp = np.concatenate([c[3] for c in self._log])
        sl = np.concatenate([c[4] for c in self._log])
        return LoggedInteractions(
            x_emb=np.asarray(self.tables["x_emb"])[ids],
            x_feat=np.asarray(self.tables["x_feat"])[ids],
            domain=np.asarray(self.tables["domain"])[ids],
            action=a, reward=r, logp=lp, slice_idx=sl,
            num_actions=self.num_actions,
            behavior=f"serving:{self.policy.name}", sample_idx=ids)

    # ------------------------------------------------- TRAIN + REBUILD --
    def end_slice(self, epochs: Optional[int] = None) -> None:
        """Replay-SGD + A^-1 rebuild over the ring (one jitted dispatch);
        ends the warm phase. The SGD budget is the constructor's static
        ``train_chunks``; ``epochs`` is accepted and ignored (no caller in
        ``src/`` passes it; the benchmark's ``RouterProxy`` forwards its
        own ``None``, a debt in ROADMAP C2).

        ``max_train_lag == 0``: dispatch and BLOCK (the train pause owns
        its own wall time instead of bleeding into the next decide's
        latency sample — and the next decide reads the trained state,
        bit-identical to the sim scan). ``max_train_lag > 0``: dispatch
        on a copy of the freshest state and return without syncing; the
        host thread goes straight back to admitting and deciding the
        next microbatches while the device grinds the train program."""
        del epochs
        with span("router.end_slice"):
            self._train_slice()
        self.slices += 1

    def _train_slice(self) -> None:
        """Dispatch the slice's train (and, with ``max_train_lag`` 0,
        wait for it); nothing before the first microbatch."""
        if self.wave == 0:
            return
        t = min(self.wave, self.T) - 1
        cum0 = jnp.asarray(np.concatenate(
            [[0], np.cumsum(self._counts)]).astype(np.int32))
        if self.max_train_lag == 0:
            with span("router.train.launch"):
                self.state, self._key = _srv_train(
                    self.policy, self.state, self._key, self.tables,
                    self.hyp, self._env_idx, cum0, jnp.int32(t),
                    **self._statics())
            self.train_epoch += 1
            self.committed_epoch = self.train_epoch
            with span("router.train.wait"):
                jax.block_until_ready(self.state)
            return
        # bounded staleness: dispatching the (lag+1)-th in-flight train
        # blocks on the oldest, so decide never lags the freshest
        # dispatched train by more than max_train_lag
        with span("router.train.commit"):
            self._advance()
            while len(self._pending) >= self.max_train_lag:
                self._force_oldest()
        base = (_merge_trained(self._pending[-1][2], self.state)
                if self._pending else self.state)
        with span("router.train.launch"):
            # donate a device-side copy: `base` aliases the committed
            # state (and possibly a pending commit target) that decide
            # keeps reading while this train is in flight. env_idx is
            # copied too — the live buffer is donated away by the next
            # update_wave, and the rebuild stage reads it later than
            # this dispatch.
            tin = jax.tree_util.tree_map(jnp.copy, base)
            env_c = jnp.copy(self._env_idx)
            t32 = jnp.int32(t)
            s1, self._key = _srv_train_sgd(
                self.policy, tin, self._key, self.tables, self.hyp,
                env_c, cum0, t32, **self._statics())
        self.train_epoch += 1
        # `tin` rides along as a keep-alive: dropping the last reference
        # to a DONATED array blocks the host until the consuming
        # computation finishes (which would make this "zero-sync"
        # dispatch silently synchronous). It is released when the stage
        # completes, when deletion is free. Entry: [epoch, stage, output,
        # keep, rebuild ctx].
        self._pending.append(
            [self.train_epoch, "sgd", s1, tin, (env_c, cum0, t32)])

    # --------------------------------------------------------- SNAPSHOT --
    def state_dict(self) -> Dict:
        self._flush()
        return {
            "arrays": {
                "state": jax.tree_util.tree_map(np.asarray, self.state),
                "key": np.asarray(self._key),
                "env_idx": np.asarray(self._env_idx),
                "counts": self._counts.copy(),
            },
            "meta": {"wave": int(self.wave), "slices": int(self.slices)},
        }

    def load_state_dict(self, d: Dict) -> None:
        arrays = d["arrays"]
        # in-flight trains describe the state being replaced — discard
        self._pending = []
        self.committed_epoch = self.train_epoch
        self.state = jax.tree_util.tree_map(jnp.asarray, arrays["state"])
        self._key = jnp.asarray(arrays["key"])
        self._env_idx = jnp.asarray(arrays["env_idx"])
        self._counts = np.asarray(arrays["counts"], np.int64).copy()
        self.wave = int(d["meta"]["wave"])
        self.slices = int(d["meta"]["slices"])
