"""Public A^-1 rebuild op: pads rows/features, runs the kernel.

Backend selection follows :mod:`repro.kernels.backend`: compiled kernel
on TPU, the jnp Cholesky-solve reference elsewhere, interpreter only on
request (tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ainv_rebuild.kernel import ainv_rebuild_padded
from repro.kernels.ainv_rebuild.ref import ainv_rebuild_ref
from repro.kernels.backend import INTERPRET, REF, resolve_backend


def ainv_rebuild(gs, ridge_lambda0=1.0, weights=None, *,
                 block_r: int = 1024, interpret: Optional[bool] = None):
    """gs: (N, F) buffered features; ``weights`` (N,) scales row i's
    contribution to A = lambda0 I + sum_i w_i g_i g_i^T linearly (rows
    are scaled by sqrt(w) inside the kernel). Returns A^-1 (F, F) f32.
    """
    backend = resolve_backend(interpret)
    if backend == REF:
        return ainv_rebuild_ref(gs, ridge_lambda0, weights=weights)
    if weights is None:
        weights = jnp.ones((gs.shape[0],), jnp.float32)
    return _ainv_rebuild_pallas(
        gs, weights, jnp.asarray(ridge_lambda0, jnp.float32).reshape(1, 1),
        block_r=block_r, interpret=backend == INTERPRET)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _ainv_rebuild_pallas(gs, weights, lam, *, block_r: int,
                         interpret: bool):
    N, F = gs.shape
    pad_f = (-F) % 128
    br = min(block_r, max(8, N))
    pad_n = (-N) % br
    if pad_f:
        # zero feature columns + lambda0 on the full padded diagonal
        # (kernel contract) keep A_pad block-diagonal: the [:F, :F]
        # block of its inverse is exactly A^-1
        gs = jnp.pad(gs, ((0, 0), (0, pad_f)))
    if pad_n:
        gs = jnp.pad(gs, ((0, pad_n), (0, 0)))
        weights = jnp.pad(weights, (0, pad_n))   # w=0: inert rows
    out = ainv_rebuild_padded(gs, weights.astype(jnp.float32)[:, None], lam,
                              block_r=br, interpret=interpret)
    return out[:F, :F]
