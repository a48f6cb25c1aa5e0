"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block in pure JAX.

The block's output is ``out_proj(rmsnorm(y * silu(z)) * norm)``: the
published Mamba-2 block's gated RMSNorm, over all ``d_inner`` channels
(one group, as in mamba2-130m and granite-4.0-h).

The chunked SSD algorithm: within-chunk work is quadratic in the chunk size
and maps onto the MXU; the inter-chunk recurrence is a ``lax.scan`` carrying
the (B, H, P, N) state. Decode is the O(1) recurrent update. A Pallas TPU
kernel for the within-chunk part lives in ``repro.kernels.mamba2_ssd``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.distributed import shard
from repro.models.layers import dense_init, rmsnorm


def mamba_dims(cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    d_inner = cfg.ssm_expand * d
    nheads = d_inner // cfg.ssm_head_dim
    return d, d_inner, nheads, cfg.ssm_head_dim, cfg.ssm_state


def init_mamba(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d, d_inner, H, P, N = mamba_dims(cfg, d_model)
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    conv_dim = d_inner + 2 * N
    return {
        "in_proj": dense_init(k1, d, 2 * d_inner + 2 * N + H, dt),
        "conv_w": (jax.random.normal(k2, (cfg.ssm_conv_width, conv_dim), jnp.float32)
                   / math.sqrt(cfg.ssm_conv_width)).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k3, (H,), jnp.float32,
                                       math.log(1e-3), math.log(1e-1))))),
        "norm": jnp.ones((d_inner,), dt),
        "out_proj": dense_init(k4, d_inner, d, dt),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array, d_inner: int, H: int, N: int):
    z, xc, Bm, Cm, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N], axis=-1)
    return z, xc, Bm, Cm, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B, L, C); w: (W, C).

    Returns (out, new_state) where state is the last W-1 inputs (B, W-1, C).
    """
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xin = jnp.concatenate([state, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + xin[:, i:i + x.shape[1]] * w[i]
    new_state = xin[:, -(W - 1):] if W > 1 else state
    return out + b, new_state


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, D: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.

    x: (B, L, H, P); dt: (B, L, H) (post-softplus); A: (H,) (negative);
    Bm, Cm: (B, L, N); D: (H,). Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    cs = min(chunk, L)
    nc = -(-L // cs)
    pad = nc * cs - L

    def padl(a):
        if pad == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[1] = (0, pad)
        return jnp.pad(a, widths)

    xp, dtp, Bp, Cp = padl(x), padl(dt), padl(Bm), padl(Cm)
    xc = xp.reshape(Bsz, nc, cs, H, P).transpose(1, 0, 2, 3, 4)
    dtc = dtp.reshape(Bsz, nc, cs, H).transpose(1, 0, 2, 3)
    Bc = Bp.reshape(Bsz, nc, cs, N).transpose(1, 0, 2, 3)
    Cc = Cp.reshape(Bsz, nc, cs, N).transpose(1, 0, 2, 3)

    tri = jnp.tril(jnp.ones((cs, cs), bool))

    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(state, inp):
        xb, dtb, Bb, Cb = inp  # (B,cs,H,P),(B,cs,H),(B,cs,N),(B,cs,N)
        adt = dtb.astype(jnp.float32) * A  # (B,cs,H), negative
        acum = jnp.cumsum(adt, axis=1)  # (B,cs,H)
        # decay(t<-s) = exp(acum_t - acum_s) for t>=s
        seg = acum[:, :, None, :] - acum[:, None, :, :]  # (B,t,s,H)
        Lmat = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        CB = jnp.einsum("btn,bsn->bts", Cb.astype(jnp.float32),
                        Bb.astype(jnp.float32))
        scores = CB[:, :, :, None] * Lmat  # (B,t,s,H)
        y_diag = jnp.einsum("btsh,bsh,bshp->bthp", scores,
                            dtb.astype(jnp.float32), xb.astype(jnp.float32))
        # contribution from carried state
        y_off = jnp.einsum("btn,bhpn,bth->bthp", Cb.astype(jnp.float32), state,
                           jnp.exp(acum))
        # state update
        decay_to_end = jnp.exp(acum[:, -1:, :] - acum)  # (B,cs,H)
        w = dtb.astype(jnp.float32) * decay_to_end
        new_contrib = jnp.einsum("bsn,bsh,bshp->bhpn", Bb.astype(jnp.float32),
                                 w, xb.astype(jnp.float32))
        state = state * jnp.exp(acum[:, -1, :])[:, :, None, None] + new_contrib
        y = y_diag + y_off
        return state, y

    final_state, ys = jax.lax.scan(step, init_state, (xc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, nc * cs, H, P)[:, :L]
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), final_state


def ssd_decode_step(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                    Cm: jax.Array, D: jax.Array, state: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrence. x: (B,H,P); dt: (B,H); Bm,Cm: (B,N);
    state: (B,H,P,N)."""
    dt32 = dt.astype(jnp.float32)
    decay = jnp.exp(dt32 * A)  # (B,H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt32, x.astype(jnp.float32),
                     Bm.astype(jnp.float32))
    state = state * decay[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    y = y + x.astype(jnp.float32) * D[None, :, None]
    return y.astype(x.dtype), state


def mamba_block(params: dict, cfg: ModelConfig, x: jax.Array, *,
                cache: Optional[dict] = None, rows: Optional[dict] = None
                ) -> Tuple[jax.Array, Optional[dict]]:
    """Full Mamba2 block. x: (B, S, D).

    cache (decode): {"conv": (B, W-1, conv_dim), "ssm": (B, H, P, N)}.
    rows (width-1 decode): {"active": (B,) bool}; an inactive row (a pad
    step) keeps its conv and SSM state as they were.
    """
    Bsz, S, Dm = x.shape
    _, d_inner, H, P, N = mamba_dims(cfg, Dm)
    proj = x @ params["in_proj"]
    z, xc, Bm, Cm, dt = _split_proj(cfg, proj, d_inner, H, N)

    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv_state = _causal_conv(conv_in, params["conv_w"],
                                            params["conv_b"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xc, Bm, Cm = jnp.split(conv_out, [d_inner, d_inner + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xc.reshape(Bsz, S, H, P)
    xh = shard(xh, "batch", "seq", "ssm_heads", "ssm_pdim")

    new_cache = None
    if cache is not None and S == 1:
        y, new_state = ssd_decode_step(xh[:, 0], dt[:, 0], A, Bm[:, 0],
                                       Cm[:, 0], params["D"], cache["ssm"])
        y = y[:, None]
        if rows is not None:
            act = rows["active"]
            new_state = jnp.where(act[:, None, None, None], new_state,
                                  cache["ssm"])
            new_conv_state = jnp.where(act[:, None, None], new_conv_state,
                                       cache["conv"])
        new_cache = {"conv": new_conv_state, "ssm": new_state}
    else:
        init_state = cache["ssm"] if cache is not None else None
        y, final_state = ssd_chunked(xh, dt, A, Bm, Cm, params["D"],
                                     cfg.ssm_chunk, init_state)
        if cache is not None:
            new_cache = {"conv": new_conv_state, "ssm": final_state}

    y = y.reshape(Bsz, S, d_inner) * jax.nn.silu(z)
    y = rmsnorm(params["norm"], y, cfg.norm_eps)
    out = y @ params["out_proj"]
    return shard(out, "batch", "seq", None), new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype,
                     d_model: Optional[int] = None) -> dict:
    _, d_inner, H, P, N = mamba_dims(cfg, d_model)
    conv_dim = d_inner + 2 * N
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
    }
