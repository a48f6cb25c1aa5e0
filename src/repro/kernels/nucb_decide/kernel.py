r"""Fused NeuralUCB decide Pallas kernel (TPU target) — one launch for the
whole per-request hot path of paper §3.3 / Algorithm 1 line 4:

    trunk forward  ->  mu(x,a)          (UtilityNet trunk + u-head)
    augment        ->  g = [h; 1]/|.|   (NeuralUCB feature)
    bonus          ->  g^T A^-1 g       (shared inverse covariance)
    gate + mask    ->  argmax_a         (availability-masked, gated UCB)

The context half of trunk1 is action-independent, so the caller
precomputes ``base-GEMM`` inputs once per request and the kernel
amortizes them over all K actions:

    z_u @ W1 + b1 = ctx @ W1[:C] + (e_a[k] @ W1[C:] + b1)
                    \__ one GEMM __/   \__ act1[k], (K, H), tiny __/

Per row-block the kernel runs ONE (Bb, C)x(C, H) context GEMM, then a
static K-unrolled loop of two small GEMMs + the A^-1 quadratic form per
action, tracking the running masked argmax — mu, h, g, scores for all
(request, action) pairs never round-trip to HBM. A^-1 and all weights
stay VMEM-resident across the grid; requests stream in blocks.

Outputs per row: chosen action, its augmented feature g (the Woodbury
update input), and the safe-greedy mean mu[argmax mu] (the gate-label
reference) — exactly what ``sim.policies._decide_ucb`` needs.

VMEM per step at block_b=256, C=384, H=256, F_pad=256: ~1.8 MB f32.

``compute_dtype`` selects the GEMM input precision (f32 or bf16); all
accumulation, the augment normalization, and the quadratic form stay
f32 (``preferred_element_type=jnp.float32``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = float("-inf")


def _decide_kernel(ctx_ref, w1ctx_ref, act1_ref, w2_ref, b2_ref, wu_ref,
                   ainv_ref, gate_ref, avail_ref, scal_ref,
                   a_ref, g_ref, mu_safe_ref, *,
                   num_actions: int, d_last: int, compute_dtype):
    f32 = jnp.float32
    cd = compute_dtype
    beta = scal_ref[0, 0]
    tau_g = scal_ref[0, 1]
    bu = scal_ref[0, 2]

    # one context GEMM, shared by all K actions
    base = jax.lax.dot(ctx_ref[...].astype(cd), w1ctx_ref[...].astype(cd),
                       preferred_element_type=f32)           # (Bb, H)
    w2 = w2_ref[...].astype(cd)                               # (H, D)
    b2 = b2_ref[...].astype(f32)                              # (1, D)
    wu = wu_ref[...].astype(f32)                              # (1, D)
    ainv = ainv_ref[...].astype(f32)                          # (Fp, Fp)
    a11 = ainv[:d_last, :d_last]
    a12 = ainv[:d_last, d_last]                               # (D,)
    a21 = ainv[d_last, :d_last]                               # (D,)
    a22 = ainv[d_last, d_last]
    # per-row values are (Bb, 1) columns throughout: Mosaic cannot lay out
    # a 1-D (Bb,) vector against a (Bb, D) tile, nor reshape an i1 vector
    use_ucb = gate_ref[...] >= tau_g                          # (Bb, 1)

    nb = base.shape[0]
    best_sel = jnp.full((nb, 1), _NEG, f32)
    best_mu = jnp.full((nb, 1), _NEG, f32)
    best_a = jnp.zeros((nb, 1), jnp.int32)
    h_best = jnp.zeros((nb, d_last), f32)
    inv_s2 = f32(1.0) / jnp.sqrt(f32(2.0))

    for k in range(num_actions):  # static unroll (K ~ 11)
        h1 = jax.nn.gelu(base + act1_ref[k, :].astype(f32)[None, :])
        h = jax.nn.gelu(
            jax.lax.dot(h1.astype(cd), w2,
                        preferred_element_type=f32) + b2)     # (Bb, D)
        mu_k = jnp.sum(h * wu, axis=1, keepdims=True) + bu    # (Bb, 1)
        # augment (core.neuralucb.augment): L2-normalize h, append 1,
        # scale by 1/sqrt(2); the quadratic form expands blockwise so g
        # is never materialized per action:
        #   2 quad = hn^T A11 hn + hn . (a12 + a21) + a22
        hn = h / jnp.maximum(
            jnp.sqrt(jnp.sum(h * h, axis=1, keepdims=True)), 1e-6)
        v = jax.lax.dot(hn, a11, preferred_element_type=f32)
        quad = 0.5 * (jnp.sum(v * hn, axis=1, keepdims=True)
                      + jnp.sum(hn * (a12 + a21)[None, :], axis=1,
                                keepdims=True)
                      + a22)
        score = mu_k + beta * jnp.sqrt(jnp.maximum(quad, 0.0))
        ok = avail_ref[0, k] > 0.0
        score_m = jnp.where(ok, score, _NEG)
        mu_m = jnp.where(ok, mu_k, _NEG)
        sel = jnp.where(use_ucb, score_m, mu_m)
        upd = sel > best_sel                 # strict: first max wins,
        best_sel = jnp.where(upd, sel, best_sel)  # matching jnp.argmax
        best_a = jnp.where(upd, k, best_a)
        h_best = jnp.where(upd, hn, h_best)
        best_mu = jnp.maximum(best_mu, mu_m)

    a_ref[...] = best_a
    mu_safe_ref[...] = best_mu
    g_ref[:, 0:d_last] = h_best * inv_s2
    tail = g_ref.shape[1] - d_last
    cix = jax.lax.broadcasted_iota(jnp.int32, (nb, tail), 1)
    g_ref[:, d_last:] = jnp.where(cix == 0, inv_s2, 0.0)


@functools.partial(jax.jit, static_argnames=("num_actions", "d_last",
                                             "block_b", "interpret",
                                             "compute_dtype"))
def nucb_decide_padded(ctx, w1ctx, act1, w2, b2, wu, ainv, gate_p, avail,
                       scal, *, num_actions: int, d_last: int,
                       block_b: int = 256, interpret: bool = False,
                       compute_dtype=jnp.float32):
    """Padded entry: ctx (B, Cp) with B % block_b == 0, Cp % 128 == 0;
    w1ctx (Cp, H); act1 (Kp, H); w2 (H, D); b2, wu (1, D); ainv (Fp, Fp)
    with Fp % 128 == 0 and d_last == D % 128 == 0; gate_p (B, 1);
    avail (1, Kp) f32 SMEM; scal (1, 3) f32 SMEM = [[beta, tau_g, bu]].
    Returns (a (B, 1) i32, g (B, Fp) f32, mu_safe (B, 1) f32).

    The SMEM operands are rows, not 1-D vectors: under ``vmap`` (one
    lane per sweep point) each gains a squeezed leading block dim, and
    Mosaic accepts that only when the block's last two dims span the
    array's — (1, n) of (L, 1, n) does, (n,) of (L, n) does not."""
    B, Cp = ctx.shape
    H = w1ctx.shape[1]
    D = w2.shape[1]
    Fp = ainv.shape[0]
    Kp = act1.shape[0]
    nr = B // block_b
    kern = functools.partial(_decide_kernel, num_actions=num_actions,
                             d_last=d_last, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kern,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_b, Cp), lambda i: (i, 0)),
            pl.BlockSpec((Cp, H), lambda i: (0, 0)),
            pl.BlockSpec((Kp, H), lambda i: (0, 0)),
            pl.BlockSpec((H, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((Fp, Fp), lambda i: (0, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, Fp), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, Fp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(ctx, w1ctx, act1, w2, b2, wu, ainv, gate_p, avail, scal)
