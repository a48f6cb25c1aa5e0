"""Plain reference of the NeuralUCB router's served legs (paper §3.2-3.3).

Written from the paper's equations, independent of ``src/``:

* UtilityNet: text MLP (two GELU layers) on the L2-normalised request
  embedding, a domain embedding joined to the auxiliary features through
  one GELU layer, then per action the trunk (two GELU layers over
  ``[h_text, h_feat, e_action]``), the utility head ``mu`` and the gate
  ``p = sigmoid(gate2(gelu(gate1([h_text, h_feat]))))``.
* Feature ``g = [h / |h|; 1] / sqrt(2)`` (F = d_last + 1).
* Decide: gated UCB. Where ``p >= tau_g`` pick ``argmax_a mu + beta *
  sqrt(g' A^-1 g)``, else ``argmax_a mu``; ``mu_safe = max_a mu``.
* Update: ``A^-1 <- (A + G'G)^-1`` over the learned rows (Woodbury).
* Train: replay SGD on the feedback ring. Each step draws a minibatch
  uniformly over the ring's learned rows, takes the Huber loss of ``mu``
  against the reward plus a weighted cross-entropy of the gate against
  its label, clips the gradient's global norm and applies AdamW.
* Rebuild: ``A^-1 = (lambda0 I + sum_i w_i g_i g_i')^-1`` over the ring.

``prec`` sets the arithmetic. ``"highest"`` (the reference) runs every
product in float32 at ``Precision.HIGHEST`` (a TPU otherwise rounds
float32 matmul inputs to bfloat16) and takes the inverses in float64 on
the host. ``"high"`` (the control for a program run at ``highest``) runs
the products at ``Precision.HIGH`` (three bfloat16 passes) and the
inverses in float32 at that precision. ``"bfloat16"`` casts weights,
inputs and products to bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST, HIGH, BF16 = "highest", "high", "bfloat16"
_PRECISION = {HIGHEST: jax.lax.Precision.HIGHEST,
              HIGH: jax.lax.Precision.HIGH, BF16: None}


def _dtype(prec):
    return jnp.bfloat16 if prec == BF16 else jnp.float32


def _mm(x, w, prec):
    dt = _dtype(prec)
    return jnp.matmul(x.astype(dt), w.astype(dt),
                      precision=_PRECISION[prec])


def _lin(p, x, prec):
    return _mm(x, p["w"], prec) + p["b"].astype(_dtype(prec))


def _gelu(x):
    # GELU in its tanh form: the paper names GELU without the variant,
    # and the form changes mu by about 1e-3, above the decide gap the
    # comparison can resolve, so the reference takes the served one
    return jax.nn.gelu(x, approximate=True)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(dtype)
                        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                        else jnp.asarray(a), tree)


def context(params, x_emb, x_feat, domain, prec=HIGHEST):
    """Action-independent encodings: (h_text, h_feat, gate_p)."""
    dt = _dtype(prec)
    p = _cast(params, dt)
    x = jnp.asarray(x_emb).astype(jnp.float32)
    x = (x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                         1e-6)).astype(dt)
    h_text = _gelu(_lin(p["text2"], _gelu(_lin(p["text1"], x, prec)), prec))
    e_d = p["emb_d"][jnp.asarray(domain)]
    f_in = jnp.concatenate([jnp.asarray(x_feat).astype(dt), e_d], -1)
    h_feat = _gelu(_lin(p["feat"], f_in, prec))
    z_g = jnp.concatenate([h_text, h_feat], -1)
    gate = jax.nn.sigmoid(_lin(p["gate2"], _gelu(_lin(p["gate1"], z_g,
                                                      prec)), prec))[:, 0]
    return h_text, h_feat, gate


def trunk(params, h_text, h_feat, action, prec=HIGHEST):
    """Last hidden layer h(x, a) and utility mu(x, a) for one action per
    row (``action`` (B,) int)."""
    p = _cast(params, _dtype(prec))
    e_a = p["emb_a"][jnp.asarray(action)]
    z = jnp.concatenate([h_text, h_feat, e_a], -1)
    h = _gelu(_lin(p["trunk2"], _gelu(_lin(p["trunk1"], z, prec)), prec))
    mu = _lin(p["u_head"], h, prec)[:, 0]
    return h, mu


def feature(h):
    """g = [h / |h|; 1] / sqrt(2), computed in float32."""
    h = jnp.asarray(h, jnp.float32)
    hn = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
    ones = jnp.ones(hn.shape[:-1] + (1,), jnp.float32)
    return jnp.concatenate([hn, ones], -1) / np.float32(np.sqrt(2.0))


# --------------------------------------------------------------- decide --
def decide(params, ainv, x_emb, x_feat, domain, beta, tau_g, prec=HIGHEST):
    """Scores of every action. Returns a dict of float32 numpy arrays:
    ``mu`` (B, K), ``score`` (B, K), ``g`` (B, K, F), ``gate`` (B,)."""
    mu, score, g, gate = _decide(params, ainv, jnp.asarray(x_emb),
                                 jnp.asarray(x_feat), jnp.asarray(domain),
                                 jnp.float32(beta), prec=prec)
    return {"mu": np.asarray(mu), "score": np.asarray(score),
            "g": np.asarray(g), "gate": np.asarray(gate, np.float32)}


@functools.partial(jax.jit, static_argnames=("prec",))
def _decide(params, ainv, x_emb, x_feat, domain, beta, prec):
    h_text, h_feat, gate = context(params, x_emb, x_feat, domain, prec)
    B = h_text.shape[0]
    K = params["emb_a"].shape[0]
    mus, gs = [], []
    for a in range(K):
        h, mu = trunk(params, h_text, h_feat, jnp.full((B,), a, jnp.int32),
                      prec)
        mus.append(mu.astype(jnp.float32))
        gs.append(feature(h))
    mu = jnp.stack(mus, 1)
    g = jnp.stack(gs, 1)                                     # (B, K, F)
    dt = _dtype(prec)
    gd = g.astype(dt)
    quad = jnp.einsum("bkf,fe,bke->bk", gd,
                      jnp.asarray(ainv, jnp.float32).astype(dt), gd,
                      precision=_PRECISION[prec]).astype(jnp.float32)
    score = mu + beta * jnp.sqrt(jnp.maximum(quad, 0.0))
    return mu, score, g, gate


def choose(ref, tau_g):
    """The decision the reference itself takes: (action, g, mu_safe)."""
    use_ucb = ref["gate"] >= tau_g
    a = np.where(use_ucb, ref["score"].argmax(1), ref["mu"].argmax(1))
    g = ref["g"][np.arange(a.size), a]
    return a.astype(np.int32), g, ref["mu"].max(1)


def decide_numbers(ref, tau_g, gate_band, action, g, mu_safe):
    """Compare one decided microbatch with the reference's scores.

    * ``decide_gap``: the widest gap by which the chosen action's score
      lies below the reference's best, on the branch the reference's
      gate takes (rows whose gate lies within ``gate_band`` of ``tau_g``
      may take either branch, and count the smaller gap);
    * ``feature_err``: the widest gap between the returned feature g and
      the reference's g of the same action;
    * ``mu_safe_err``: the widest gap between the returned safe mean and
      the reference's ``max_a mu``."""
    action = np.asarray(action)
    rows = np.arange(action.size)
    ucb_gap = ref["score"].max(1) - ref["score"][rows, action]
    safe_gap = ref["mu"].max(1) - ref["mu"][rows, action]
    near = np.abs(ref["gate"] - tau_g) <= gate_band
    gap = np.where(near, np.minimum(ucb_gap, safe_gap),
                   np.where(ref["gate"] >= tau_g, ucb_gap, safe_gap))
    g_err = np.abs(np.asarray(g, np.float32) - ref["g"][rows, action])
    mu_err = np.abs(np.asarray(mu_safe, np.float32) - ref["mu"].max(1))
    return {"decide_gap": float(gap.max(initial=0.0)),
            "feature_err": float(g_err.max(initial=0.0)),
            "mu_safe_err": float(mu_err.max(initial=0.0))}


# --------------------------------------------------------------- update --
def woodbury(ainv, gs, prec=HIGHEST):
    """(A + G'G)^-1 from A^-1 and the rows G (n, F)."""
    if prec == HIGHEST:
        A = np.asarray(ainv, np.float64)
        G = np.asarray(gs, np.float64)
        u = G @ A
        S = np.eye(G.shape[0]) + u @ G.T
        return (A - u.T @ np.linalg.solve(S, u)).astype(np.float32)
    return np.asarray(_woodbury_low(jnp.asarray(ainv), jnp.asarray(gs),
                                    prec=prec))


@functools.partial(jax.jit, static_argnames=("prec",))
def _woodbury_low(ainv, gs, prec):
    """The products at ``prec``; the (n, n) solve in float32 (there is
    no bfloat16 solve)."""
    u = _mm(gs, ainv, prec)
    S = (jnp.eye(gs.shape[0], dtype=u.dtype) + _mm(u, gs.T, prec)
         ).astype(jnp.float32)
    with jax.default_matmul_precision(prec if prec == HIGH else "default"):
        x = jnp.linalg.solve(S, u.astype(jnp.float32))
    return (ainv.astype(u.dtype) - _mm(u.T, x, prec)).astype(jnp.float32)


# -------------------------------------------------------------- rebuild --
def rebuild(params, x_emb, x_feat, domain, action, w, ridge_lambda0,
            prec=HIGHEST, block=16384):
    """A^-1 = (lambda0 I + sum_i w_i g_i g_i')^-1 over the given rows,
    features recomputed with ``params``; rows stream in blocks so a full
    ring fits beside the program's state."""
    w = np.asarray(w, np.float32)
    keep = np.flatnonzero(w > 0)
    F = np.asarray(params["u_head"]["w"]).shape[0] + 1
    gram = jnp.zeros((F, F), jnp.float32)
    for s in range(0, keep.size, block):
        r = keep[s:s + block]
        pad = block - r.size if keep.size > block else 0
        r = np.concatenate([r, np.full(pad, r[0])])
        wr = np.concatenate([w[r[:r.size - pad]], np.zeros(pad, np.float32)])
        gram = _gram(gram, params, jnp.asarray(x_emb[r]),
                     jnp.asarray(x_feat[r]), jnp.asarray(domain[r]),
                     jnp.asarray(action[r]), jnp.asarray(wr), prec=prec)
    if prec == HIGHEST:
        A = np.asarray(gram, np.float64) + ridge_lambda0 * np.eye(F)
        return np.linalg.inv(A).astype(np.float32)
    A = gram + jnp.float32(ridge_lambda0) * jnp.eye(F, dtype=jnp.float32)
    if prec == BF16:
        A = A.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision(prec if prec == HIGH else "default"):
        return np.asarray(jnp.linalg.inv(A))


@functools.partial(jax.jit, static_argnames=("prec",))
def _gram(gram, params, x_emb, x_feat, domain, action, w, prec):
    """gram + sum_i w_i g_i g_i' over one block of rows."""
    ht, hf, _ = context(params, x_emb, x_feat, domain, prec)
    h, _ = trunk(params, ht, hf, action, prec)
    g = feature(h) * jnp.sqrt(w)[:, None]
    return gram + _mm(g.T, g, prec).astype(jnp.float32)


def matrix_err(got, want):
    """Widest entry gap, as a share of the reference's widest entry."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------- train --
def train_rows(key, cum0, t, chunks, steps, batch):
    """The minibatch rows the replay SGD of one slice draws: per chunk of
    ``steps`` steps a fresh key split from ``key``, per step ``batch``
    draws uniform over the ``cum0[t + 1]`` learned rows of ring rows
    ``0..t`` (ring row r holds ``cum0[r + 1] - cum0[r]`` learned rows at
    its front). Returns (ring row, column) arrays of shape
    (chunks * steps, batch)."""
    cum0 = np.asarray(cum0, np.int64)
    count = jnp.maximum(jnp.int32(cum0[t + 1]), 1)
    key = jnp.asarray(key)
    flats = []
    for _ in range(chunks):
        key, kc = jax.random.split(key)
        for k in jax.random.split(kc, steps):
            flats.append(np.asarray(jax.random.randint(k, (batch,), 0,
                                                       count)))
    flat = np.stack(flats)
    row = np.searchsorted(cum0, flat, side="right") - 1
    return row, flat - cum0[row]


def train(params, opt, batches, hp: dict, prec=HIGHEST):
    """Replay SGD from ``params`` and the AdamW state ``opt`` ({"mu",
    "nu", "count"}) over ``batches`` (a list of dicts of numpy arrays:
    x_emb, x_feat, domain, action, reward, gate_label, w, gate_w).
    ``hp`` holds lr, b1, b2, eps, weight_decay, clip_norm, huber_delta,
    gate_weight. Returns (params, opt, gradient norm per leaf per step
    as the optimizer took it, after clipping)."""
    hp_t = tuple(sorted((k, float(v)) for k, v in hp.items()))
    params = jax.tree.map(jnp.asarray, params)
    mu = jax.tree.map(jnp.asarray, opt["mu"])
    nu = jax.tree.map(jnp.asarray, opt["nu"])
    count = jnp.asarray(opt["count"])
    norms = []
    for b in batches:
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, mu, nu, count, gn = _train_step(params, mu, nu, count, b,
                                                hp=hp_t, prec=prec)
        norms.append([float(x) for x in jax.tree.leaves(gn)])
    out = {"mu": mu, "nu": nu, "count": count}
    return (jax.tree.map(np.asarray, params), jax.tree.map(np.asarray, out),
            norms)


def _loss(params, b, hp, prec):
    ht, hf, gate = context(params, b["x_emb"], b["x_feat"], b["domain"],
                           prec)
    _, mu = trunk(params, ht, hf, b["action"], prec)
    mu, gate = mu.astype(jnp.float32), gate.astype(jnp.float32)
    err = jnp.abs(mu - b["reward"])
    quad = jnp.minimum(err, hp["huber_delta"])
    huber = 0.5 * quad ** 2 + hp["huber_delta"] * (err - quad)
    l_u = (huber * b["w"]).sum() / jnp.maximum(b["w"].sum(), 1.0)
    p = jnp.clip(gate, 1e-6, 1 - 1e-6)
    y = b["gate_label"]
    bce = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
    l_g = (bce * b["gate_w"]).sum() / jnp.maximum(b["gate_w"].sum(), 1.0)
    return l_u + hp["gate_weight"] * l_g


@functools.partial(jax.jit, static_argnames=("hp", "prec"))
def _train_step(params, mu, nu, count, b, hp, prec):
    hp = dict(hp)
    grads = jax.grad(_loss)(params, b, hp, prec)
    total = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["clip_norm"] / jnp.maximum(total, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    count = count + 1
    c = count.astype(jnp.float32)
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def step(p, m, v):
        upd = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + hp["eps"])
        return p - hp["lr"] * (upd + hp["weight_decay"] * p)

    params = jax.tree.map(step, params, mu, nu)
    return params, mu, nu, count, jax.tree.map(jnp.linalg.norm, grads)


def _leaves(tree):
    return [np.asarray(x, np.float64)
            for x in jax.tree.leaves(jax.tree.map(np.asarray, tree))]


def train_numbers(before, got, want, grad_norms, b2, steps):
    """Compare one slice's replay SGD with the reference's, leaf by leaf.

    * ``train_step_err``: the gap between the norms of the program's and
      the reference's parameter change over the slice;
    * ``train_grad_err``: the gap between the norms of the squared
      gradient the slice added to AdamW's second moment (``nu_after -
      b2**steps * nu_before``, the gradient as the optimizer got it),
      each taken as a root.

    Each gap is over the reference's norm of that leaf or of the median
    leaf, whichever is larger, and the worst leaf counts. Leaves whose
    reference gradient (mean over the steps) is under a thousandth of
    the median leaf's are left out: they move by round-off and weight
    decay alone."""
    g = np.array([np.mean([n_step[i] for n_step in grad_norms])
                  for i in range(len(grad_norms[0]))])
    keep = g >= 1e-3 * np.median(g)
    p0 = _leaves(before["params"])
    dp_got = [np.linalg.norm(a - b) for a, b in zip(_leaves(got["params"]),
                                                    p0)]
    dp_ref = [np.linalg.norm(a - b) for a, b in zip(_leaves(want["params"]),
                                                    p0)]
    decay = b2 ** steps
    nu0 = _leaves(before["opt"]["nu"])

    def grad(opt):
        return [np.sqrt(max(np.sum(v - decay * v0), 0.0))
                for v, v0 in zip(_leaves(opt["nu"]), nu0)]

    return {"train_step_err": _worst_leaf(dp_got, dp_ref, keep),
            "train_grad_err": _worst_leaf(grad(got["opt"]),
                                          grad(want["opt"]), keep)}


def _worst_leaf(got, want, keep):
    got, want = np.asarray(got), np.asarray(want)
    floor = np.median(want[keep])
    gap = np.abs(got - want) / np.maximum(want, floor)
    return float(np.max(gap[keep], initial=0.0))
