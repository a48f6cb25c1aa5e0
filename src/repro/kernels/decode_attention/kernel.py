"""Flash-decode Pallas kernel (TPU target).

One query token per sequence attends over a long KV cache. The cache is
split along the sequence dimension (split-K); each grid step computes
partial softmax statistics (m, l, acc) for its span; ops.py does the
logsumexp combine over splits. This is how decode saturates HBM bandwidth
on TPU: every split streams its KV span HBM->VMEM exactly once, and the
(G x Bk) score tile plus (G x D) accumulator stay in VMEM/VREGs.

Grid: (batch, kv_heads, num_splits). Query layout (B, KV, G, D) groups the
GQA query heads that share a KV head, so the MXU contraction is
(G x D) @ (D x Bk) per step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, window: int, block_s: int):
    i_s = pl.program_id(2)
    pos = pos_ref[0]  # valid cache entries are [0, pos]
    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (Bs, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = i_s * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos <= pos
    if window > 0:
        mask &= k_pos > (pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)               # (G, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    acc = jax.lax.dot(p, v, preferred_element_type=jnp.float32)  # (G, D)

    m_ref[0, 0, 0] = m[:, 0]
    l_ref[0, 0, 0] = l[:, 0]
    acc_ref[0, 0, 0] = acc


@functools.partial(
    jax.jit,
    static_argnames=("window", "block_s", "scale_dim", "interpret"))
def decode_attention_padded(q, k, v, pos, *, window: int = 0,
                            block_s: int = 1024, scale_dim: int = 0,
                            interpret: bool = True):
    """q: (B, KV, G, D); k, v: (B, KV, S, D) with S % block_s == 0;
    pos: (1,) int32. Returns partial (m, l, acc) over splits:
    m, l: (B, KV, NS, G); acc: (B, KV, NS, G, D)."""
    B, KV, G, D = q.shape
    S = k.shape[2]
    ns = S // block_s
    scale = 1.0 / math.sqrt(scale_dim or D)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, G), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, G, D), lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, ns, G), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, ns, G), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, ns, G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(pos, q, k, v)
