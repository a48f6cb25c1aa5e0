"""Public fused NeuralUCB decide op.

``nucb_decide`` takes raw UtilityNet params + a request batch, runs the
action-independent context encode (text/feat MLPs + domain gather + gate
head — O(B), K-times smaller than the per-action trunk) in plain jnp,
splits trunk1 into its context GEMM and per-action bias rows, and hands
the per-action hot loop to the Pallas kernel. Backend selection follows
:mod:`repro.kernels.backend`: compiled kernel on TPU, jnp reference
elsewhere, interpreter only on request.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import utilitynet as UN
from repro.kernels.backend import INTERPRET, REF, resolve_backend
from repro.kernels.nucb_decide.kernel import nucb_decide_padded
from repro.kernels.nucb_decide.ref import nucb_decide_ref


def prepare_decide_inputs(params, x_emb, x_feat, domain):
    """Action-independent preprocessing shared by kernel and ref: the
    encoded context, the gate probability, trunk1 split into its context
    weight block and per-action bias rows (b1 folded in), and the flat
    trunk2/u-head weights."""
    h_emb, h_feat = UN._context_encode(params, x_emb, x_feat, domain)
    ctx = jnp.concatenate([h_emb, h_feat], axis=-1)          # (B, C)
    gp = jax.nn.gelu(ctx @ params["gate1"]["w"] + params["gate1"]["b"])
    gate_p = jax.nn.sigmoid(
        gp @ params["gate2"]["w"] + params["gate2"]["b"])[..., 0]
    C = ctx.shape[-1]
    w1 = params["trunk1"]["w"]                               # (C + A, H)
    act1 = params["emb_a"] @ w1[C:] + params["trunk1"]["b"]  # (K, H)
    return (ctx, gate_p, w1[:C], act1, params["trunk2"]["w"],
            params["trunk2"]["b"], params["u_head"]["w"][:, 0],
            params["u_head"]["b"][0])


def nucb_decide(params, cfg: UN.UtilityNetConfig, x_emb, x_feat, domain,
                ainv, beta, tau_g, avail=None, *, block_b: int = 256,
                interpret: Optional[bool] = None,
                compute_dtype=jnp.float32):
    """Fused gated-UCB decision over all actions.

    Returns (a (B,) i32, g (B, F) f32 — the chosen arm's augmented
    feature, mu_safe (B,) f32 — the safe-greedy mean reference,
    gate_p (B,) f32)."""
    ctx, gate_p, w1ctx, act1, w2, b2, wu, bu = prepare_decide_inputs(
        params, x_emb, x_feat, domain)
    if avail is not None:
        avail = avail.astype(jnp.float32)
    backend = resolve_backend(interpret)
    if backend == REF:
        a, g, mu_safe = nucb_decide_ref(
            ctx, w1ctx, act1, w2, b2, wu, bu, ainv,
            gate_p, avail, beta, tau_g)
        return a, g, mu_safe, gate_p
    a, g, mu_safe = _nucb_decide_pallas(
        ctx, w1ctx, act1, w2, b2, wu,
        jnp.asarray(bu, jnp.float32).reshape(()),
        ainv, gate_p, avail,
        jnp.asarray(beta, jnp.float32).reshape(()),
        jnp.asarray(tau_g, jnp.float32).reshape(()),
        num_actions=cfg.num_actions, block_b=block_b,
        interpret=backend == INTERPRET, compute_dtype=compute_dtype)
    return a, g[:, :cfg.ucb_feature_dim], mu_safe, gate_p


@functools.partial(jax.jit, static_argnames=("num_actions", "block_b",
                                             "interpret",
                                             "compute_dtype"))
def _nucb_decide_pallas(ctx, w1ctx, act1, w2, b2, wu, bu, ainv, gate_p,
                        avail, beta, tau_g, *, num_actions: int,
                        block_b: int, interpret: bool, compute_dtype):
    B, C = ctx.shape
    H = w1ctx.shape[1]
    D = w2.shape[1]
    F = ainv.shape[0]
    K = num_actions
    if H % 128 or D % 128:
        raise ValueError(f"nucb_decide kernel needs d_hidden and d_last "
                         f"to be multiples of 128, got {H} and {D}")

    pad_c = (-C) % 128
    pad_f = (-F) % 128
    pad_k = (-K) % 8
    bb = min(block_b, max(8, B))
    pad_b = (-B) % bb
    if pad_c:
        ctx = jnp.pad(ctx, ((0, 0), (0, pad_c)))
        w1ctx = jnp.pad(w1ctx, ((0, pad_c), (0, 0)))
    if pad_f:
        # zero padding keeps the (unused) padded block of A^-1 inert:
        # the kernel only reads the leading (D+1, D+1) entries
        ainv = jnp.pad(ainv, ((0, pad_f), (0, pad_f)))
    if pad_k:
        act1 = jnp.pad(act1, ((0, pad_k), (0, 0)))
    if pad_b:
        ctx = jnp.pad(ctx, ((0, pad_b), (0, 0)))
        gate_p = jnp.pad(gate_p, (0, pad_b))

    # padded action rows are never read (the kernel's loop is static
    # over the true K); zeros keep them inert regardless
    avail_full = jnp.zeros((1, K + pad_k), jnp.float32)
    avail_full = avail_full.at[0, :K].set(
        1.0 if avail is None else avail)
    scal = jnp.stack([beta.astype(jnp.float32),
                      tau_g.astype(jnp.float32),
                      bu.astype(jnp.float32)])[None, :]
    a, g, mu_safe = nucb_decide_padded(
        ctx, w1ctx, act1, w2.astype(jnp.float32),
        b2.reshape(1, -1).astype(jnp.float32),
        wu.reshape(1, -1).astype(jnp.float32),
        ainv.astype(jnp.float32), gate_p.astype(jnp.float32)[:, None],
        avail_full, scal, num_actions=K, d_last=D, block_b=bb,
        interpret=interpret, compute_dtype=compute_dtype)
    return a[:B, 0], g[:B], mu_safe[:B, 0]
