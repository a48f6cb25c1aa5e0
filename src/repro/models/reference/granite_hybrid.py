"""Plain reference of a GraniteMoeHybrid decoder (granite-4.0-h):
the full forward pass over one whole sequence.

    x_0 = embedding_multiplier * E[t]
    each layer:
        x <- x + residual_multiplier * mixer(rms_1(x))
             mixer: Mamba-2 or attention, by the layer's position
        x <- x + residual_multiplier * (MoE(h) + shared(h)),  h = rms_2(x)
    MoE(h) = sum over the top-k experts e of softmax(router logits over
             the top-k)_e * expert_e(h); expert_e(h) = W_d(silu(W_g h) *
             W_u h), and the shared expert likewise
    Mamba-2: out_proj(rms_gated(y * silu(z))), y from the recurrence
             s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T, y_t = s_t C_t +
             D x_t over a causal depthwise conv of (x, B, C)
    attention: causal GQA with no position embedding (NoPE), scores
             scaled by attention_multiplier
    logits = rms_f(x) E^T / logits_scaling

Everything is float32 at ``jax.default_matmul_precision("highest")``:
no cache, no kernels, no batching, no expert capacity, and Mamba-2 as
its plain sequential recurrence.

Departures from the published model, and why:

* the weights are random, from a seed (the system's ``init_params``),
  not the published checkpoint: agreement on seeded weights is what the
  tests need, and no checkpoint is at hand;
* the depth and the experts are a chip's share: the layers the
  configuration holds (``num_layers``, the first layers of the model)
  and, of each expert layer, only the experts whose weights ``params``
  holds (``held``, the global ids ``[first, first + count)``); the
  absent experts' part of each layer's output is left out, as one chip
  of an expert-parallel deployment leaves it to the others;
* no tokenizer: token ids are given.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


def _mamba(p, cfg, h):
    """h (S, D) -> (S, D): the Mamba-2 mixer, one step at a time."""
    S, D = h.shape
    d_inner = cfg.ssm_expand * D
    N, P = cfg.ssm_state, cfg.ssm_head_dim
    H = d_inner // P
    W = cfg.ssm_conv_width
    proj = h @ p["in_proj"]
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:2 * d_inner + 2 * N]
    dt = jax.nn.softplus(proj[:, 2 * d_inner + 2 * N:] + p["dt_bias"])
    # causal depthwise conv over the last W inputs
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
    conv = sum(padded[k:k + S] * p["conv_w"][k] for k in range(W))
    conv = jax.nn.silu(conv + p["conv_b"])
    xs = conv[:, :d_inner].reshape(S, H, P)
    Bm = conv[:, d_inner:d_inner + N]
    Cm = conv[:, d_inner + N:]
    A = -jnp.exp(p["A_log"])

    def step(state, t):
        decay = jnp.exp(dt[t] * A)                                   # (H,)
        state = state * decay[:, None, None] + \
            dt[t][:, None, None] * xs[t][:, :, None] * Bm[t][None, None]
        y = state @ Cm[t] + p["D"][:, None] * xs[t]                  # (H, P)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), jnp.arange(S))
    y = y.reshape(S, d_inner) * jax.nn.silu(z)
    y = _rms(y, p["norm"], cfg.norm_eps)
    return y @ p["out_proj"]


def _attention(p, cfg, h):
    """h (S, D) -> (S, D): causal GQA, no position embedding."""
    if cfg.use_rope:
        raise NotImplementedError("the reference is of a NoPE model")
    S, _ = h.shape
    hd, H, KV = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    q = (h @ p["wq"]).reshape(S, H, hd)
    k = (h @ p["wk"]).reshape(S, KV, hd)
    v = (h @ p["wv"]).reshape(S, KV, hd)
    group = H // KV
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg.attention_scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(S, H * hd) @ p["wo"]


def moe(p, cfg, h, held: Tuple[int, int]):
    """h (S, D) -> the held experts' part of the routed experts' output.
    ``held`` = (first global id, count): the experts ``p``'s tables hold."""
    first, count = held
    probs = jax.nn.softmax(h @ p["router"], axis=-1)      # (S, E) over all
    topv, topi = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = topv / topv.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for j in range(count):
        g = jnp.sum(jnp.where(topi == first + j, gates, 0.0), axis=-1)
        expert = {k: p[k][j] for k in ("wg", "wu", "wd")}
        out = out + g[:, None] * _swiglu(expert, h)
    return out


def layer_params(params, cfg, i: int) -> dict:
    """Layer ``i``'s weights out of the system's stacked parameter tree:
    {"ln_mix", "ln_ffn", "mamba" or "attn", "moe", "shared"}."""
    n = cfg.attn_every
    blk = jax.tree.map(lambda t: t[i // n], params["blocks"])
    pos = i % n
    out = {"ln_mix": blk["ln_mix"][pos], "ln_ffn": blk["ln_ffn"][pos],
           "moe": jax.tree.map(lambda t: t[pos], blk["moe"])}
    if pos == cfg.attn_position:
        out["attn"] = blk["attn"]
    else:
        m = pos - (pos > cfg.attn_position)
        out["mamba"] = jax.tree.map(lambda t: t[m], blk["mamba"])
    if cfg.shared_expert_ff:
        out["shared"] = jax.tree.map(lambda t: t[pos], blk["shared"])
    return out


def forward(params, cfg, tokens, held: Optional[Tuple[int, int]] = None
            ) -> jax.Array:
    """tokens (S,) -> logits (S, vocab_size) of one sequence.
    ``params``: the system's parameter tree for ``cfg`` (a hybrid whose
    every layer is an expert layer); ``held``: the experts its tables
    hold (default: the configuration's share)."""
    if cfg.arch_type != "hybrid" or cfg.moe_every != 1:
        raise ValueError(f"{cfg.name}: not a GraniteMoeHybrid layout")
    held = held or (cfg.expert_first, cfg.local_experts)
    r = cfg.residual_multiplier
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["embed"], jnp.float32)[:cfg.vocab_size]
        x = cfg.embedding_multiplier * emb[np.asarray(tokens)]
        for i in range(cfg.num_layers):
            p = _f32(layer_params(params, cfg, i))
            h = _rms(x, p["ln_mix"], cfg.norm_eps)
            mix = (_attention(p["attn"], cfg, h) if "attn" in p
                   else _mamba(p["mamba"], cfg, h))
            x = x + r * mix
            h = _rms(x, p["ln_ffn"], cfg.norm_eps)
            ffn = moe(p["moe"], cfg, h, held)
            if "shared" in p:
                ffn = ffn + _swiglu(p["shared"], h)
            x = x + r * ffn
        x = _rms(x, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.norm_eps)
        return x @ emb.T / cfg.logits_scaling
