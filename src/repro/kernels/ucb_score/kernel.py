"""NeuralUCB scoring Pallas kernel (TPU target) — the paper's serving-time
hot loop: for every (request, action) pair score

    s = mu + beta * sqrt(g^T A^-1 g)

over the shared last-layer feature g(x,a) = [h(x,a); 1] and the shared
inverse covariance A^-1 (paper §3.3). At router scale this is R=batch*K
quadratic forms of width F (feature dim + bias), i.e. a (R x F) @ (F x F)
GEMM on the MXU followed by a row-wise VPU reduce — exactly the layout
this kernel uses. A^-1 stays VMEM-resident across the whole grid; G rows
stream through in blocks of ``block_r``.

VMEM per step: Ainv (F x F) + g (block_r x F) + h (block_r x F); with
F=256, block_r=512: ~1.3 MB f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ucb_kernel(g_ref, ainv_ref, mu_ref, beta_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)        # (Br, F)
    ainv = ainv_ref[...].astype(jnp.float32)  # (F, F)
    mu = mu_ref[...].astype(jnp.float32)      # (Br, 1)
    beta = beta_ref[0, 0]

    h = jax.lax.dot(g, ainv, preferred_element_type=jnp.float32)  # (Br, F)
    quad = jnp.sum(h * g, axis=1, keepdims=True)                   # (Br, 1)
    bonus = jnp.sqrt(jnp.maximum(quad, 0.0))
    out_ref[...] = mu + beta * bonus


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def ucb_score_padded(g, ainv, mu, beta, *, block_r: int = 512,
                     interpret: bool = True):
    """g: (R, F) with R % block_r == 0, F % 128 == 0; ainv: (F, F);
    mu: (R, 1); beta: (1, 1) f32 SMEM. Returns scores (R, 1) f32.

    Per-row values are (R, 1) columns: a 1-D block's tiling need not
    match the one XLA picks for the array, and under ``vmap`` a 1-D
    block cannot tile at all (see ``nucb_decide_padded``)."""
    R, F = g.shape
    nr = R // block_r
    return pl.pallas_call(
        _ucb_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_r, F), lambda i: (i, 0)),
            pl.BlockSpec((F, F), lambda i: (0, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(g, ainv, mu, beta)
