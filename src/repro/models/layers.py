"""Shared building blocks for the model zoo.

Conventions
-----------
* Params are nested dicts of jnp arrays; every linear weight is a flat 2D
  matrix ``(in_dim, out_dim)`` so a single universal partition rule applies
  (column-parallel over ``model``, FSDP over ``data``); activations are
  annotated with logical axes via ``repro.distributed.shard``.
* RoPE uses the *interleaved* (even/odd pair) formulation so the pairing
  stays local under head_dim sharding (see DESIGN.md §7).
* Attention is memory-efficient (online-softmax over KV chunks with
  ``lax.scan``) whenever ``q_len * kv_len`` exceeds a threshold, so 32k+
  contexts lower without materializing the full score matrix. On real TPU
  hardware the Pallas kernels in ``repro.kernels`` replace this path.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.distributed import shard

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype) -> jax.Array:
    return jnp.ones((dim,), dtype)


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


# ---------------------------------------------------------------------------
# RoPE (interleaved pairing)
# ---------------------------------------------------------------------------


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> sin/cos of shape (..., head_dim//2)."""
    freqs = theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32) * 2.0 / head_dim)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, S, H, D); sin/cos: (B, S, D//2) or (S, D//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x_even = x[..., 0::2]
    x_odd = x[..., 1::2]
    if sin.ndim == 2:  # (S, D/2) -> broadcast over batch & heads
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:  # (B, S, D/2)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    r_even = x_even * cos - x_odd * sin
    r_odd = x_odd * cos + x_even * sin
    out = jnp.stack([r_even, r_odd], axis=-1).reshape(x.shape)
    return out.astype(dt)


def sinusoidal_positions(seq_len: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    pe = jnp.zeros((seq_len, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

# chunk sizes for the memory-efficient path
_Q_CHUNK = 1024
_KV_CHUNK = 1024
_DIRECT_LIMIT = 4096 * 4096  # q_len*kv_len above this -> chunked path


def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(kq, d, cfg.num_heads * hd, dt),
        "wk": dense_init(kk, d, cfg.num_kv_heads * hd, dt),
        "wv": dense_init(kv, d, cfg.num_kv_heads * hd, dt),
        "wo": dense_init(ko, cfg.num_heads * hd, d, dt),
        "q_norm": rmsnorm_init(hd, dt),
        "k_norm": rmsnorm_init(hd, dt),
    }


def _attn_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window) -> jax.Array:
    """(Q, K) boolean mask. window<=0 -> no window. ``window`` may be a
    traced scalar (gemma local/global flags are scanned over layers)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    window = jnp.asarray(window)
    in_window = k_pos[None, :] > (q_pos[:, None] - window)
    m &= (window <= 0) | in_window
    return m


def _sdpa_grouped(q, k, v, mask, scale=None) -> jax.Array:
    """Decode-path attention (q_len small): q: (B,Q,H,D); k,v in the KV
    cache's NATIVE layout (B,KV,S,D) — no transpose, so the multi-GB cache
    is never copied for a layout change; GQA via grouped reshape so it is
    never head-repeated either. mask: (B?,Q,K) bool."""
    B, Q, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, Q, KV, G, D)
    scores = jnp.einsum("bqkgd,bksd->bkgqs", qg, k).astype(jnp.float32)
    scores *= scale or 1.0 / math.sqrt(D)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bksd->bqkgd", p, v)
    return out.reshape(B, Q, H, D)


def _sdpa_folded(q, k, v, mask, scale=None) -> jax.Array:
    """Train/prefill attention with GQA groups FOLDED into the head dim and
    explicit sharding constraints on the score tensor. Without this, SPMD
    propagation computes (B, H, S, T) scores replicated over the model axis
    (measured: 88 x ~300 GB/op on mistral-large train) because the grouped
    (KV, G) einsum layout admits no 16-way head sharding. k/v are repeated
    to H heads — local (and fusable) when heads are model-sharded.

    q: (B,Q,H,D); k,v: (B,K,KV,D); mask (Q,K) or (B,Q,K)."""
    B, Q, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = shard(k, "batch", None, "heads", "head_dim")
    v = shard(v, "batch", None, "heads", "head_dim")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= scale or 1.0 / math.sqrt(D)
    scores = shard(scores, "batch", "heads", "attn_q_seq", None)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    p = shard(p, "batch", "heads", "attn_q_seq", None)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return shard(out, "batch", "attn_q_seq", "heads", "head_dim")


def _sdpa_local(q, k, v, window: int, scale=None) -> jax.Array:
    """Banded block attention for sliding-window layers: queries in blocks
    of ``window``; each block attends to its own and the previous block
    (2w keys) — O(S*w) score work/memory instead of the O(S^2) full band
    that the generic paths compute and mask away (gemma3: 29 of 34 layers
    are 1024-window local; at 32k prefill this is ~16x less score work).

    q: (B,S,H,D); k,v: (B,S,KV,D). Assumes causal, positions 0..S-1.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    w = window
    nb = -(-S // w)
    qp = _pad_axis(q, 1, nb * w)
    kp = _pad_axis(k, 1, nb * w)
    vp = _pad_axis(v, 1, nb * w)

    qb = qp.reshape(B, nb, w, H, D)
    kb = kp.reshape(B, nb, w, H, D)
    vb = vp.reshape(B, nb, w, H, D)
    # previous block (zeros before block 0)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (B, nb, 2w, H, D)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    qb = shard(qb, "batch", None, None, "heads", "head_dim")
    k2 = shard(k2, "batch", None, None, "heads", "head_dim")

    s = jnp.einsum("bnqhd,bnkhd->bhnqk", qb, k2).astype(jnp.float32)
    s *= scale or 1.0 / math.sqrt(D)
    s = shard(s, "batch", "heads", None, None, None)
    a_idx = jax.lax.broadcasted_iota(jnp.int32, (nb, w, 2 * w), 1)
    b_idx = jax.lax.broadcasted_iota(jnp.int32, (nb, w, 2 * w), 2)
    blk = jax.lax.broadcasted_iota(jnp.int32, (nb, w, 2 * w), 0)
    # dist = w + a - b: causal dist>=0, window dist<w; block 0 has no prev
    mask = (b_idx <= a_idx + w) & (b_idx > a_idx) & ((blk > 0) | (b_idx >= w))
    s = jnp.where(mask[None, None], s, -1e30)
    # padded tail queries attend only within pad; softmax stays finite via
    # the b==a+w diagonal (self) entry
    p = jax.nn.softmax(s, axis=-1).astype(v2.dtype)
    out = jnp.einsum("bhnqk,bnkhd->bnqhd", p, v2)
    out = out.reshape(B, nb * w, H, D)[:, :S]
    return out


def _sdpa_chunked(q, k, v, q_pos, k_pos, causal, window,
                  scale=None) -> jax.Array:
    """Online-softmax attention (the pure-XLA flash equivalent): ALL queries
    held live, ``lax.scan`` over KV chunks only; the live score block is
    (B, H, Q, kv_chunk). Folded-head layout like ``_sdpa_folded``, with the
    query dim sharded over "attn_q_seq" (context parallelism) when heads
    cannot be model-sharded — a sequential outer q-chunk scan would leave
    that dimension unshardable and the whole score computation replicated
    across the model axis (measured 16x memory waste on llama3.2-3b
    prefill_32k)."""
    B, Q, H, D = q.shape
    K, KV = k.shape[1], k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = shard(k, "batch", None, "heads", "head_dim")
    v = shard(v, "batch", None, "heads", "head_dim")
    kc = min(_KV_CHUNK, K)
    nk = -(-K // kc)
    kp = _pad_axis(k, 1, nk * kc)
    vp = _pad_axis(v, 1, nk * kc)
    kpos = _pad_axis(k_pos, 0, nk * kc, fill=10 ** 9)

    qh = q.transpose(0, 2, 1, 3)  # (B, H, Q, D)
    qh = shard(qh, "batch", "heads", "attn_q_seq", "head_dim")
    kblocks = kp.reshape(B, nk, kc, H, D).transpose(1, 0, 3, 2, 4)
    vblocks = vp.reshape(B, nk, kc, H, D).transpose(1, 0, 3, 2, 4)
    kpos_b = kpos.reshape(nk, kc)
    scale = scale or 1.0 / math.sqrt(D)

    def kv_block(state, kb):
        m_prev, l_prev, acc = state
        ktile, vtile, kpos_tile = kb  # (B,H,kc,D), (kc,)
        s = jnp.einsum("bhqd,bhsd->bhqs", qh, ktile).astype(jnp.float32)
        s *= scale
        s = shard(s, "batch", "heads", "attn_q_seq", None)
        mask = _attn_mask(q_pos, kpos_tile, causal, window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqs,bhsd->bhqd", p.astype(vtile.dtype), vtile
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, H, Q), -jnp.inf, jnp.float32),
        jnp.zeros((B, H, Q), jnp.float32),
        jnp.zeros((B, H, Q, D), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(kv_block, init, (kblocks, vblocks, kpos_b))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).transpose(0, 2, 1, 3)  # (B, Q, H, D)


def _pad_axis(x, axis, size, fill=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def attention(params: dict, cfg: ModelConfig, x: jax.Array, *,
              positions: jax.Array,
              causal: bool = True,
              window: int = 0,
              cache: Optional[dict] = None,
              cache_pos: Optional[jax.Array] = None,
              kv_source: Optional[jax.Array] = None,
              use_rope: bool = True,
              k_offset: jax.Array | int = 0,
              local_window: Optional[int] = None,
              rows: Optional[dict] = None
              ) -> Tuple[jax.Array, Optional[dict]]:
    """General attention: self/cross, train/prefill/decode.

    x: (B, S, D). positions: (S,) absolute positions of the query tokens.
    cache: {"k": (B, KV, S_max, hd), "v": ...} ring buffer written at
    ``cache_pos``; decode attends over the cache.
    kv_source: cross-attention memory (B, T, D) -- no cache path needed for
    training; for decode the cross K/V are precomputed in the cache.
    rows (width-1 decode of left-padded rows): {"active": (B,) bool,
    "start": (B,) int32}; an inactive row (a pad step) leaves its cache
    slot as it was, and row b attends only to slots from ``start[b]``.
    Scores are scaled by ``cfg.attention_scale``.
    """
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    scale = cfg.attention_scale

    q = (x @ params["wq"]).reshape(B, S, H, hd)
    src = kv_source if kv_source is not None else x
    Tsrc = src.shape[1]
    k = (src @ params["wk"]).reshape(B, Tsrc, KV, hd)
    v = (src @ params["wv"]).reshape(B, Tsrc, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)

    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")

    if use_rope and cfg.use_rope and kv_source is None:
        sin, cos = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    new_cache = None
    if cache is not None and kv_source is None:
        # decode: write S (normally 1) new entries at cache_pos
        kc = cache["k"]  # (B, KV, S_max, hd)
        vc = cache["v"]
        k_t = k.transpose(0, 2, 1, 3).astype(kc.dtype)  # (B, KV, S, hd)
        v_t = v.transpose(0, 2, 1, 3).astype(vc.dtype)
        at = (0, 0, cache_pos, 0)
        if rows is not None:
            keep = ~rows["active"][:, None, None, None]
            k_t = jnp.where(keep, jax.lax.dynamic_slice(kc, at, k_t.shape),
                            k_t)
            v_t = jnp.where(keep, jax.lax.dynamic_slice(vc, at, v_t.shape),
                            v_t)
        kc = jax.lax.dynamic_update_slice(kc, k_t, at)
        vc = jax.lax.dynamic_update_slice(vc, v_t, at)
        new_cache = {"k": kc, "v": vc}
        k_idx = jnp.arange(kc.shape[2])
        valid = k_idx <= (cache_pos + S - 1)
        k_pos = k_idx + k_offset  # real positions of cache slots
        q_pos = positions
        mask = _attn_mask(q_pos, k_pos, causal, window) & valid[None, :]
        if rows is not None:
            mask = mask[None] & (k_idx[None, :] >= rows["start"][:, None]
                                 )[:, None, :]
        if q.shape[1] * kc.shape[2] <= _DIRECT_LIMIT or q.shape[1] == 1:
            # native cache layout
            out = _sdpa_grouped(q, kc, vc, mask, scale)
        elif rows is not None:
            raise NotImplementedError("row starts apply to width-1 decode")
        else:
            out = _sdpa_chunked(q, kc.transpose(0, 2, 1, 3),
                                vc.transpose(0, 2, 1, 3), q_pos,
                                jnp.where(valid, k_pos, 10 ** 9),
                                causal, window, scale)
    elif cache is not None and kv_source is not None:
        # decode-time cross-attention: cached K/V (native layout), no update
        kc, vc = cache["k"], cache["v"]
        mask = jnp.ones((S, kc.shape[2]), bool)
        out = _sdpa_grouped(q, kc, vc, mask, scale)
        new_cache = cache
    else:
        k_pos = positions if kv_source is None else jnp.arange(Tsrc)
        q_pos = positions
        if (local_window is not None and kv_source is None and causal
                and S >= 2 * local_window):
            # banded block path: O(S*w) instead of O(S^2)-then-mask
            out = _sdpa_local(q, k, v, local_window, scale)
        elif S * Tsrc <= _DIRECT_LIMIT:
            mask = _attn_mask(q_pos, k_pos, causal and kv_source is None, window)
            out = _sdpa_folded(q, k, v, mask, scale)
        else:
            out = _sdpa_chunked(q, k, v, q_pos, k_pos,
                                causal and kv_source is None, window, scale)

    out = shard(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(B, S, H * hd).astype(dt) @ params["wo"]
    return shard(out, "batch", "seq", None), new_cache


def init_cross_kv(params: dict, cfg: ModelConfig, memory: jax.Array) -> dict:
    """Precompute cross-attention K/V from encoder output (decode path)."""
    B, T, _ = memory.shape
    hd = cfg.resolved_head_dim
    k = (memory @ params["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = (memory @ params["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    return {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    f = d_ff or cfg.d_ff
    kg, ku, kd = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wg": dense_init(kg, cfg.d_model, f, dt),
        "wu": dense_init(ku, cfg.d_model, f, dt),
        "wd": dense_init(kd, f, cfg.d_model, dt),
    }


def ffn(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])
    h = shard(h, "batch", "seq", "ffn")
    return shard(h @ params["wd"], "batch", "seq", None)
