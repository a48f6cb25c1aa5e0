"""Flash-attention Pallas kernel (TPU target).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the last dim is the
reduction ("arbitrary") dimension; m/l/acc live in VMEM scratch and the
output block is written on the final KV step (the classic revisiting
pattern). GQA is handled in the K/V index_map: query head ``h`` reads KV
head ``h // group_size``, so K/V tiles are fetched once per group.

VMEM budget per step (bf16 inputs, f32 scratch):
  q (Bq x D) + k,v (Bk x D) + scratch acc (Bq x D) + p (Bq x Bk)
  with Bq=Bk=512, D=128: ~0.9 MB << 16 MB VMEM. MXU dims are multiples
  of 128 by construction (ops.py pads D and the sequence).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, kv_len: int, num_kv_blocks: int):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (Bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = i_q * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = i_k * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len  # padding
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(i_k == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "kv_len",
                     "scale_dim", "interpret"))
def flash_attention_padded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 512, block_k: int = 512,
                           kv_len: int = 0, scale_dim: int = 0,
                           interpret: bool = True) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KV, Sk, D); all dims pre-padded so that
    Sq % block_q == Sk % block_k == 0 and D % 128 == 0. ``kv_len`` is the
    true (unpadded) KV length; ``scale_dim`` the true head dim."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(scale_dim or D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=kv_len or Sk,
        num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
