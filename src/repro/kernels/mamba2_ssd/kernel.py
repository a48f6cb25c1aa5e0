"""Mamba2 SSD within-chunk Pallas kernel (TPU target).

The SSD decomposition (arXiv:2405.21060) splits the recurrence into a
quadratic *within-chunk* part (MXU-friendly: per chunk a (cs x cs) masked
"attention" matrix against decay factors) and a linear *inter-chunk* state
recurrence (done with ``lax.scan`` in ops.py — it is O(L/cs) sequential
steps and bandwidth-bound, not compute-bound).

This kernel computes, per (batch x head-block, chunk) grid step:
  y_diag      = ((C B^T) .* L) diag(dt) x          -- within-chunk output
  chunk_state = sum_s B_s (dt_s decay_to_end_s) x_s -- state contribution
  exp_acum    = exp(cumsum(A dt))                  -- for inter-chunk y_off
  decay_last  = exp(acum[-1])                      -- state carry decay

VMEM per step, cs=256, HB=8 heads, P=64, N=128 (mamba2-130m):
  x (cs,HB,P) + B,C (cs,N) + L (cs,cs,HB) + state (HB,P,N) ~= 2.5 MB f32,
  comfortably inside the ~16 MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                ydiag_ref, state_ref, expacum_ref, decay_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)     # (cs, HB, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)   # (cs, HB)
    A = a_ref[0].astype(jnp.float32)           # (HB,)
    Bm = b_ref[0, 0].astype(jnp.float32)       # (cs, N)
    Cm = c_ref[0, 0].astype(jnp.float32)       # (cs, N)

    adt = dt * A[None, :]                      # (cs, HB), negative
    acum = jnp.cumsum(adt, axis=0)             # (cs, HB)
    # decay(t<-s) = exp(acum_t - acum_s), lower triangular
    seg = acum[:, None, :] - acum[None, :, :]  # (t, s, HB)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    Lmat = jnp.where(t_idx >= s_idx, jnp.exp(seg), 0.0)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (t, s)
    wdt = CB[:, :, None] * Lmat * dt[None, :, :]   # (t, s, HB)
    ydiag = jnp.einsum("tsh,shp->thp", wdt, x)     # (cs, HB, P)

    decay_to_end = jnp.exp(acum[-1, :][None, :] - acum)  # (cs, HB)
    w = dt * decay_to_end
    state = jnp.einsum("sn,sh,shp->hpn", Bm, w, x)       # (HB, P, N)

    ydiag_ref[0, 0, 0] = ydiag.astype(ydiag_ref.dtype)
    state_ref[0, 0, 0] = state
    expacum_ref[0, 0, 0] = jnp.exp(acum)
    decay_ref[0, 0, 0] = jnp.exp(acum[-1, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_kernel(x, dt, A, Bm, Cm, *, interpret: bool = True):
    """x: (B, NC, cs, H, P); dt: (B, NC, cs, H); A: (H,);
    Bm, Cm: (B, NC, cs, N). Heads are processed in blocks of HB<=8.

    Returns: ydiag (B,NC,cs,H,P), chunk_state (B,NC,H,P,N),
             exp_acum (B,NC,cs,H), decay_last (B,NC,H).
    """
    B, NC, cs, H, P = x.shape
    N = Bm.shape[-1]
    HB = 8 if H % 8 == 0 else (4 if H % 4 == 0 else 1)
    nh = H // HB

    xg = x.reshape(B, NC, cs, nh, HB, P).transpose(0, 3, 1, 2, 4, 5)
    dtg = dt.reshape(B, NC, cs, nh, HB).transpose(0, 3, 1, 2, 4)
    Ag = A.reshape(nh, HB)

    ydiag, state, expacum, decay = pl.pallas_call(
        _ssd_kernel,
        grid=(B * nh, NC),
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, HB, P),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cs, HB),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0, 0)),
            pl.BlockSpec((1, HB), lambda bh, c, nh=nh: (bh % nh, 0)),
            pl.BlockSpec((1, 1, cs, N),
                         lambda bh, c, nh=nh: (bh // nh, c, 0, 0)),
            pl.BlockSpec((1, 1, cs, N),
                         lambda bh, c, nh=nh: (bh // nh, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, cs, HB, P),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, HB, P, N),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cs, HB),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, HB),
                         lambda bh, c, nh=nh: (bh // nh, bh % nh, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, NC, cs, HB, P), x.dtype),
            jax.ShapeDtypeStruct((B, nh, NC, HB, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, NC, cs, HB), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, NC, HB), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xg, dtg, Ag, Bm, Cm)

    ydiag = ydiag.transpose(0, 2, 3, 1, 4, 5).reshape(B, NC, cs, H, P)
    state = state.transpose(0, 2, 1, 3, 4, 5).reshape(B, NC, H, P, N)
    expacum = expacum.transpose(0, 2, 3, 1, 4).reshape(B, NC, cs, H)
    decay = decay.transpose(0, 2, 1, 3).reshape(B, NC, H)
    return ydiag, state, expacum, decay
