"""Blocked-Cholesky A^-1 rebuild Pallas kernel (TPU target) — Algorithm 1
line 8 as ONE launch:

    A = lambda0 I + sum_i w_i g_i g_i^T        (streamed Gram accumulation)
    A = L L^T ; A^-1 = L^-T L^-1               (blocked Cholesky inverse)

Feature rows g (N, F_pad) stream through the grid in blocks of
``block_r``; a VMEM scratch accumulates the Gram matrix in f32 across
grid steps (initialized to lambda0 I at step 0, one MXU GEMM per
block), and the final grid step factorizes and inverts in-VMEM — A and
A^-1 never round-trip to HBM between the two phases, unlike the jnp
path (`core.neuralucb.rebuild_ainv`), which materializes the (N, F)
feature matrix and calls a host-library Cholesky at full capacity.

The factorization is a right-looking *blocked* Cholesky: within a
column panel of width ``block_s`` the per-column pivot/scale/update
runs on the VPU restricted to the panel, and each finished panel
applies its trailing update as a single MXU GEMM. The triangular
inverse is a forward substitution with one (1, n) x (n, n) MXU row
solve per column. All index selection uses 2-D broadcasted_iota masks
(TPU has no 1-D iota and Mosaic prefers masked full-width ops over
sub-tile slicing); everything stays f32.

Padding contract: F padded to a 128 multiple with ZERO feature columns
and lambda0 on the FULL padded diagonal, so A_pad is block-diagonal
([A, 0; 0, lambda0 I]) and invertible, and A_pad^-1[:F, :F] is exactly
A^-1 (the caller slices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_OUTER = (((1,), (1,)), ((), ()))   # (n,1) x (n,1) -> outer product (n,n)
_GRAM = (((0,), (0,)), ((), ()))    # (m,n) x (m,k) -> X^T Y


def _chol_blocked(a, block_s: int):
    """Lower Cholesky factor of SPD ``a`` (n, n), right-looking with
    column panels of width ``block_s`` (n % block_s == 0)."""
    f32 = jnp.float32
    n = a.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    rvec = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def column(j, hi, m):
        """Finalize column j; trailing update restricted to cols < hi
        (the panel) — the inter-panel part goes through the GEMM."""
        pivot = jnp.sum(jnp.where((rows == j) & (cols == j), m, 0.0))
        d = jnp.sqrt(jnp.maximum(pivot, 1e-30))
        colj = jnp.sum(jnp.where(cols == j, m, 0.0), axis=1,
                       keepdims=True)                        # (n, 1)
        below = jnp.where(rvec > j, colj / d, 0.0)           # (n, 1)
        newcol = below + jnp.where(rvec == j, d, 0.0)
        m = jnp.where(cols == j, newcol, m)
        outer = jax.lax.dot_general(below, below, _OUTER,
                                    preferred_element_type=f32)
        upd = (rows > j) & (cols > j) & (cols < hi)
        return m - jnp.where(upd, outer, 0.0)

    m = a.astype(f32)
    for lo in range(0, n, block_s):                 # static panel loop
        hi = lo + block_s
        m = jax.lax.fori_loop(
            lo, hi, lambda j, mm: column(j, hi, mm), m)
        if hi < n:
            # one MXU GEMM applies the panel to the whole trailing block
            p = jnp.where((cols >= lo) & (cols < hi) & (rows >= hi),
                          m, 0.0)
            gram = jax.lax.dot_general(p, p, (((1,), (1,)), ((), ())),
                                       preferred_element_type=f32)
            m = m - jnp.where((rows >= hi) & (cols >= hi), gram, 0.0)
    return jnp.where(rows >= cols, m, 0.0)


def _tril_inv(ell):
    """Inverse of a lower-triangular ``ell`` (n, n) by forward
    substitution — one masked (1, n) x (n, n) MXU row solve per step."""
    f32 = jnp.float32
    n = ell.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cvec = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(j, x):
        lrow = jnp.sum(jnp.where(rows == j, ell, 0.0), axis=0,
                       keepdims=True)                        # (1, n)
        ljj = jnp.sum(jnp.where(cvec == j, lrow, 0.0))
        strict = jnp.where(cvec < j, lrow, 0.0)
        contrib = jax.lax.dot(strict, x,
                              preferred_element_type=f32)    # (1, n)
        ej = jnp.where(cvec == j, 1.0, 0.0).astype(f32)
        newrow = (ej - contrib) / ljj
        return jnp.where(rows == j, newrow, x)

    return jax.lax.fori_loop(0, n, body, jnp.zeros((n, n), f32))


def _spd_inverse(a, block_s: int):
    ell = _chol_blocked(a, block_s)
    linv = _tril_inv(ell)
    # A^-1 = L^-T L^-1, one Gram GEMM
    return jax.lax.dot_general(linv, linv, _GRAM,
                               preferred_element_type=jnp.float32)


def _rebuild_kernel(g_ref, w_ref, lam_ref, out_ref, acc_ref, *,
                    block_s: int):
    i = pl.program_id(0)
    n = acc_ref.shape[0]

    @pl.when(i == 0)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        acc_ref[...] = jnp.where(rows == cols, lam_ref[0, 0], 0.0)

    sw = jnp.sqrt(jnp.maximum(w_ref[...], 0.0))              # (Br, 1)
    gw = g_ref[...].astype(jnp.float32) * sw
    acc_ref[...] += jax.lax.dot_general(
        gw, gw, _GRAM, preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[...] = _spd_inverse(acc_ref[...], block_s)


@functools.partial(jax.jit, static_argnames=("block_r", "block_s",
                                             "interpret"))
def ainv_rebuild_padded(g, w, lam, *, block_r: int = 1024,
                        block_s: int = 128, interpret: bool = False):
    """g: (N, Fp) with N % block_r == 0 and Fp % 128 == 0 (zero-padded
    feature columns); w: (N, 1) row weights (padded rows carry 0);
    lam: (1, 1) f32 SMEM. Returns A_pad^-1 (Fp, Fp) f32.

    ``w`` is a column and ``lam`` a row so that a ``vmap``-ed call (one
    lane per sweep point) still tiles: the batched block's last two
    dims must span the array's or be (8, 128)-aligned, which a 1-D
    block under a squeezed lane dim never is."""
    N, Fp = g.shape
    nr = N // block_r
    kern = functools.partial(_rebuild_kernel, block_s=block_s)
    return pl.pallas_call(
        kern,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_r, Fp), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((Fp, Fp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, Fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Fp, Fp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(g, w, lam)
