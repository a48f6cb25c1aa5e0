"""Fused rank-k Woodbury A^-1 update as ONE Pallas launch.

The third leg of Algorithm 1's hot loop (after the fused decide and the
blocked-Cholesky rebuild): fold a slice's observed features G (n, F)
into the shared inverse covariance,

    (A + G_b^T G_b)^-1 = A^-1 - A^-1 G_b^T (I_k + G_b A^-1 G_b^T)^-1 G_b A^-1

applied block-by-block over row blocks G_b of ``block_k`` rows. The jnp
path (`core.neuralucb.woodbury_update`) runs the same recurrence as a
``fori_loop`` of XLA matmuls, round-tripping A^-1 through HBM between
blocks; here A^-1 lives in a single (Fp, Fp) f32 VMEM scratch for the
whole launch while the grid streams G row blocks past it:

    step 0:        acc <- A^-1 (copied once from HBM)
    every step i:  u = G_i acc            (block_k, Fp)   MXU
                   S = I + u G_i^T        (block_k, block_k)
                   Sinv = chol(S) solve   (in-VMEM blocked Cholesky,
                                           reused from kernels/ainv_rebuild)
                   x = Sinv u
                   acc <- sym(acc - u^T x)
    last step:     out <- acc             (written once to HBM)

Zero rows of G are exact no-ops (identity row/col in S, zero row in u),
so the caller pads both the row count (to a ``block_k`` multiple) and
the feature dim (to the 128-lane multiple) with zeros and slices the
result — the padded A^-1 block stays identically zero.

The symmetrization uses 0.5 * (u^T x + x^T u) — two `_GRAM`
dot_generals — instead of materializing a transpose, which Mosaic would
otherwise have to lay out separately; both forms keep acc bit-symmetric
given a symmetric input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ainv_rebuild.kernel import _GRAM, _spd_inverse

_INNER = (((1,), (1,)), ((), ()))   # (k,n) x (m,n) -> X Y^T


def _update_kernel(g_ref, ainv_ref, out_ref, acc_ref, *, block_s: int):
    i = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        acc_ref[...] = ainv_ref[...].astype(f32)

    g = g_ref[...].astype(f32)                               # (Bk, Fp)
    acc = acc_ref[...]
    u = jax.lax.dot(g, acc, preferred_element_type=f32)      # G A^-1
    k = g.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    eye = jnp.where(rows == cols, 1.0, 0.0).astype(f32)
    s = eye + jax.lax.dot_general(u, g, _INNER,
                                  preferred_element_type=f32)
    sinv = _spd_inverse(s, block_s)                          # (Bk, Bk)
    x = jax.lax.dot(sinv, u, preferred_element_type=f32)     # S^-1 G A^-1
    down = jax.lax.dot_general(u, x, _GRAM, preferred_element_type=f32)
    down_t = jax.lax.dot_general(x, u, _GRAM, preferred_element_type=f32)
    acc_ref[...] = acc - 0.5 * (down + down_t)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("block_k", "block_s", "interpret"))
def nucb_update_padded(gs, ainv, *, block_k: int = 128,
                       block_s: int = 128, interpret: bool = False):
    """gs (N, Fp) with N % block_k == 0 and Fp % 128 == 0 (zero rows and
    zero feature columns are exact no-ops); ainv (Fp, Fp) f32, zero in
    the padded block. block_s is the in-kernel Cholesky panel width and
    must divide block_k. Returns the updated A^-1 (Fp, Fp) f32."""
    n, fp = gs.shape
    assert n % block_k == 0 and block_k % block_s == 0, (n, block_k, block_s)
    nb = n // block_k
    kern = functools.partial(_update_kernel, block_s=block_s)
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_k, fp), lambda i: (i, 0)),
            pl.BlockSpec((fp, fp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((fp, fp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((fp, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((fp, fp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(gs, ainv)
