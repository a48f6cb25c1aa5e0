"""NeuralUCB routing policy (paper §3.3 + Algorithm 1).

DECIDE:   s(x,a) = mu(x,a) + beta * sqrt(g^T A^-1 g); take argmax_a s if
          the gate fires (p(x) >= tau_g), else the mean-greedy safe action.
          On TPU the scores come from the Pallas ucb_score kernel; the jnp
          einsum path is the portable fallback (see default_ucb_backend).
UPDATE:   push (x, a, r, y_gate) into the replay buffer; blocked rank-k
          Woodbury update of the shared A^-1 with the slice's g(x, a).
TRAIN:    E replay epochs of Huber + BCE on the buffer (AdamW).
REBUILD:  recompute all buffered features with the new net; Cholesky.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import neuralucb as NU
from repro.core import utilitynet as UN
from repro.core.replay import ReplayBuffer
from repro.kernels.ucb_score.ops import ucb_score
from repro.training.optim import adamw_init, adamw_update, clip_by_global_norm


def default_ucb_backend() -> str:
    """'pallas' on TPU (native Pallas kernel), 'jnp' elsewhere. The ops
    in repro.kernels self-dispatch the same way (kernels.backend), so
    backend='pallas' is safe everywhere — off-TPU it runs each op's jnp
    reference, never the interpreter."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _decide_jit(params, cfg: UN.UtilityNetConfig, ainv, beta, tau_g,
                x_emb, x_feat, domain, backend: str = "jnp"):
    mu, h, gate_p = UN.utilitynet_all_actions(params, cfg, x_emb, x_feat, domain)
    g = NU.augment(h)                                   # (B, K, F)
    if backend == "pallas":
        # serving path: (B*K, F) quadratic forms as one MXU GEMM sweep
        # with A^-1 VMEM-resident (repro.kernels.ucb_score); the op
        # picks compiled-vs-reference itself (kernels.backend)
        scores = ucb_score(g, ainv, mu, beta)
    else:
        bonus = NU.ucb_bonus(ainv, g)                   # (B, K)
        scores = mu + beta * bonus
    a_ucb = jnp.argmax(scores, axis=-1)
    a_safe = jnp.argmax(mu, axis=-1)
    use_ucb = gate_p >= tau_g
    actions = jnp.where(use_ucb, a_ucb, a_safe)
    g_taken = jnp.take_along_axis(
        g, actions[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    mu_safe = jnp.take_along_axis(mu, a_safe[:, None], axis=1)[:, 0]
    return actions, g_taken, mu_safe, gate_p, scores


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _score_jit(params, cfg: UN.UtilityNetConfig, ainv, x_emb, x_feat,
               domain, backend: str = "jnp"):
    """Raw scoring pieces for the non-UCB exploration rules: per-arm mean
    utility, posterior bonus sqrt(g^T A^-1 g), gate prob, and the full
    augmented feature tensor (B, K, F) so the chosen arm's g can be
    gathered host-side after the exploration draw."""
    mu, h, gate_p = UN.utilitynet_all_actions(params, cfg, x_emb, x_feat,
                                              domain)
    g = NU.augment(h)
    if backend == "pallas":
        bonus = ucb_score(g, ainv, jnp.zeros_like(mu), 1.0)
    else:
        bonus = NU.ucb_bonus(ainv, g)
    return mu, bonus, gate_p, g


@functools.partial(jax.jit, static_argnames=("cfg",))
def _train_step_jit(params, opt, cfg: UN.UtilityNetConfig, batch, lr):
    (loss, metrics), grads = jax.value_and_grad(
        UN.utilitynet_loss, has_aux=True)(params, cfg, batch)
    grads, gn = clip_by_global_norm(grads, 1.0)
    params, opt = adamw_update(grads, opt, params, lr=lr, weight_decay=1e-4)
    metrics = dict(metrics, grad_norm=gn, loss=loss)
    return params, opt, metrics


@functools.partial(jax.jit, static_argnames=("cfg",))
def _features_jit(params, cfg: UN.UtilityNetConfig, x_emb, x_feat, domain,
                  action):
    _, h, _ = UN.utilitynet_apply(params, x_emb, x_feat, domain, action)
    return NU.augment(h)


class NeuralUCBRouter:
    """Stateful host router implementing the paper's policy, the
    reference that `core.protocol.run_protocol` drives — and, via
    ``exploration``, the host face of the policy zoo (DESIGN.md §10):
    the same UtilityNet / replay / A^-1 stack with the decision rule
    swapped. Serving runs the device twin,
    :class:`repro.serving.policy_router.DevicePolicyRouter`.

    * ``"ucb"`` (default) — the paper's gated UCB (§3.3).
    * ``"ts"`` — NeuralTS: scores mu + scale * bonus * z, z ~ N(0, 1).
    * ``"eps"`` — ε-greedy: argmax mu, uniform arm with prob ``scale``.
    * ``"boltzmann"`` — softmax(mu / scale) sampling.

    Hyperparameters follow §4.1: lr 1e-3, beta 1, ridge lambda0 1; tau_g and
    the gate-label margin are under-specified in the paper — see DESIGN.md §6.
    """

    def __init__(self, cfg: UN.UtilityNetConfig, *, seed: int = 0,
                 beta: float = 1.0, tau_g: float = 0.5,
                 ridge_lambda0: float = 1.0, lr: float = 1e-3,
                 gate_margin: float = 0.05, batch_size: int = 256,
                 ucb_backend: Optional[str] = None,
                 exploration: str = "ucb", explore_scale: float = 1.0):
        if exploration not in ("ucb", "ts", "eps", "boltzmann"):
            raise ValueError(f"unknown exploration rule {exploration!r}")
        self.cfg = cfg
        self.ucb_backend = ucb_backend or default_ucb_backend()
        self.exploration = exploration
        self.explore_scale = explore_scale
        self.beta = beta
        self.tau_g = tau_g
        self.ridge_lambda0 = ridge_lambda0
        self.lr = lr
        self.gate_margin = gate_margin
        self.batch_size = batch_size
        key = jax.random.PRNGKey(seed)
        self.params = UN.init_utilitynet(key, cfg)
        self.opt = adamw_init(self.params)
        self.ainv = NU.init_ainv(cfg.ucb_feature_dim, ridge_lambda0)
        self.buffer = ReplayBuffer(cfg.emb_dim, cfg.feat_dim)
        self.np_rng = np.random.default_rng(seed + 1)
        self.warm = True  # slice 1 explores uniformly (warm-start init)

    # ----------------------------------------------------------- DECIDE --
    def decide(self, x_emb: np.ndarray, x_feat: np.ndarray,
               domain: np.ndarray) -> Dict[str, np.ndarray]:
        B = x_emb.shape[0]
        if self.warm:
            actions = self.np_rng.integers(0, self.cfg.num_actions, size=B)
            g = np.asarray(_features_jit(
                self.params, self.cfg, jnp.asarray(x_emb), jnp.asarray(x_feat),
                jnp.asarray(domain), jnp.asarray(actions, jnp.int32)))
            mu_safe = np.zeros(B, np.float32)
            gate_p = np.ones(B, np.float32)
        elif self.exploration == "ucb":
            a, g, mu_safe, gate_p, _ = _decide_jit(
                self.params, self.cfg, self.ainv,
                jnp.float32(self.beta), jnp.float32(self.tau_g),
                jnp.asarray(x_emb), jnp.asarray(x_feat), jnp.asarray(domain),
                backend=self.ucb_backend)
            actions = np.asarray(a)
            g, mu_safe, gate_p = map(np.asarray, (g, mu_safe, gate_p))
        else:
            actions, g, mu_safe, gate_p = self._decide_explore(
                x_emb, x_feat, domain)
        return {"action": actions.astype(np.int32), "g": g,
                "mu_safe": mu_safe, "gate_p": gate_p}

    def _decide_explore(self, x_emb, x_feat, domain):
        """The zoo's non-UCB decision rules (class docstring), sharing
        the jitted scorer; exploration draws come from the host RNG that
        already owns the warm-slice stream."""
        mu, bonus, gate_p, g_all = map(np.asarray, _score_jit(
            self.params, self.cfg, self.ainv, jnp.asarray(x_emb),
            jnp.asarray(x_feat), jnp.asarray(domain),
            backend=self.ucb_backend))
        B, K = mu.shape
        a_safe = mu.argmax(axis=-1)
        s = self.explore_scale
        if self.exploration == "ts":
            actions = (mu + s * bonus
                       * self.np_rng.standard_normal(mu.shape)
                       ).argmax(axis=-1)
        elif self.exploration == "eps":
            flip = self.np_rng.random(B) < s
            actions = np.where(flip, self.np_rng.integers(0, K, size=B),
                               a_safe)
        else:                                   # boltzmann
            z = mu / max(s, 1e-6)
            p = np.exp(z - z.max(axis=-1, keepdims=True))
            p = p / p.sum(axis=-1, keepdims=True)
            # vectorized inverse-CDF draw (one RNG call for the batch)
            u = self.np_rng.random(B)
            actions = (p.cumsum(axis=-1) > u[:, None]).argmax(axis=-1)
        actions = actions.astype(np.int32)
        g = g_all[np.arange(B), actions]
        mu_safe = mu[np.arange(B), a_safe].astype(np.float32)
        return actions, g, mu_safe, gate_p

    # ----------------------------------------------------------- UPDATE --
    def update(self, x_emb, x_feat, domain, decision: Dict, reward) -> None:
        reward = np.asarray(reward, np.float32)
        # gate label (DESIGN.md §6): exploration would have been beneficial
        # iff the realized reward fell short of the predicted safe utility.
        gate_label = (reward < decision["mu_safe"] - self.gate_margin
                      ).astype(np.float32)
        gate_mask = np.zeros_like(gate_label) if self.warm else \
            np.ones_like(gate_label)
        self.buffer.add_batch(x_emb, x_feat, domain, decision["action"],
                              reward, gate_label, gate_mask)
        # blocked rank-k Woodbury: one Cholesky solve per block instead of
        # n sequential rank-1 Sherman-Morrison updates (DESIGN.md §6)
        self.ainv = NU.woodbury_update(self.ainv, jnp.asarray(decision["g"]))

    # ------------------------------------------------------------ TRAIN --
    def train(self, epochs: int = 5) -> Dict[str, float]:
        # The short shuffle tail IS consumed: each distinct tail length
        # retraces _train_step_jit once (<= batch_size - 1 shapes over a
        # run's lifetime, small net), which we accept on this host
        # reference path so every sample trains each epoch; jit-hot
        # callers can pass drop_tail=True instead (repro.core.replay).
        last = {}
        for _ in range(epochs):
            for mb in self.buffer.minibatches(self.np_rng, self.batch_size):
                jb = {k: jnp.asarray(v) for k, v in mb.items()}
                self.params, self.opt, m = _train_step_jit(
                    self.params, self.opt, self.cfg, jb, jnp.float32(self.lr))
                last = {k: float(v) for k, v in m.items()}
        return last

    # ---------------------------------------------------------- REBUILD --
    def rebuild(self) -> None:
        data = self.buffer.data()
        gs = []
        bs = 4096
        for i in range(0, len(self.buffer), bs):
            gs.append(np.asarray(_features_jit(
                self.params, self.cfg,
                jnp.asarray(data["x_emb"][i:i + bs]),
                jnp.asarray(data["x_feat"][i:i + bs]),
                jnp.asarray(data["domain"][i:i + bs]),
                jnp.asarray(data["action"][i:i + bs]))))
        self.ainv = NU.rebuild_ainv(jnp.asarray(np.concatenate(gs)),
                                    self.ridge_lambda0)

    def end_slice(self, epochs: int = 5) -> Dict[str, float]:
        metrics = self.train(epochs)
        self.rebuild()
        self.warm = False
        return metrics
