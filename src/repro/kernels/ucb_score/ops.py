"""Public NeuralUCB scoring op: pads rows/features, runs the kernel.

Backend selection is centralized in :mod:`repro.kernels.backend`:
``interpret=None`` (the default) runs the compiled kernel on TPU and
the jnp reference everywhere else, so call sites never carry their own
``jax.default_backend()`` gate and never fall into the interpreter by
accident. Pass ``interpret=True`` to force the interpreter (tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import INTERPRET, REF, resolve_backend
from repro.kernels.ucb_score.kernel import ucb_score_padded
from repro.kernels.ucb_score.ref import ucb_score_ref


def ucb_score(g, ainv, mu, beta, *, block_r: int = 512,
              interpret: Optional[bool] = None):
    """g: (..., F); ainv: (F, F); mu: (...,); beta scalar.
    Returns UCB scores with g's leading shape, f32.

    Feature padding is safe: padded g columns are zero, and padding A^-1
    with zeros (not identity) keeps the quadratic form unchanged.
    """
    backend = resolve_backend(interpret)
    if backend == REF:
        return ucb_score_ref(g, ainv, mu, beta)
    return _ucb_score_pallas(g, ainv, mu, beta, block_r=block_r,
                             interpret=backend == INTERPRET)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def _ucb_score_pallas(g, ainv, mu, beta, *, block_r: int,
                      interpret: bool):
    lead = g.shape[:-1]
    F = g.shape[-1]
    R = 1
    for d in lead:
        R *= d
    g2 = g.reshape(R, F)
    mu2 = mu.reshape(R)

    pad_f = (-F) % 128
    br = min(block_r, max(8, R))
    pad_r = (-R) % br
    if pad_f:
        g2 = jnp.pad(g2, ((0, 0), (0, pad_f)))
        ainv = jnp.pad(ainv, ((0, pad_f), (0, pad_f)))
    if pad_r:
        g2 = jnp.pad(g2, ((0, pad_r), (0, 0)))
        mu2 = jnp.pad(mu2, (0, pad_r))

    beta_arr = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    out = ucb_score_padded(g2, ainv, mu2[:, None], beta_arr, block_r=br,
                           interpret=interpret)
    return out[:R, 0].reshape(lead)
