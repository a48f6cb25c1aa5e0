"""Plain reference of the pool's real arms, for the checks of
``arm_logit_err.*`` and ``arm_kv_err.*``: granite-4.0-h-small (GraniteMoeHybrid) at the chip
share the configuration states, and mamba2-130m. Written from the
published layer equations, independent of ``src/``:

    x_0 = embedding_multiplier * E[t]
    each layer:
        x <- x + residual_multiplier * mixer(rms_1(x))
             mixer: Mamba-2 or attention, as ``layer_types`` says
        x <- x + residual_multiplier * (MoE(h) + shared(h)),  h = rms_2(x)
             (granite only; mamba2-130m has no FFN)
    MoE(h) = sum over the top-k experts e, of those held here, of
             softmax(router logits over the top-k)_e * expert_e(h),
             expert_e(h) = W_d(silu(W_g h) * W_u h); the router scores
             all published experts
    Mamba-2: out_proj(rms(y * silu(z)) * w_norm) with the recurrence
             s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T,
             y_t = s_t C_t + D x_t, over a causal depthwise conv (with
             bias) of (x, B, C) through silu; dt = softplus(dt + bias)
    attention: causal GQA, no position embedding (NoPE), scores scaled
             by ``attention_multiplier``
    logits = rms_f(x) E^T / logits_scaling

Everything is float32 at ``Precision.HIGHEST``, computed layer by layer:
each layer's bfloat16 weights are upcast inside that layer's program, so
one layer's float32 copy is live at a time. Mamba-2 runs as its plain
recurrence, one position at a time. Rows are right-padded to one
length: every operation is causal or per position, so a row's real
positions do not see its padding. No cache, no capacity.

The weights are the program's own (random, from the run's seed), read
from its parameter tree: ``blocks`` stacked by period (hybrid) or by
layer (Mamba-2 only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _swiglu(p, h):
    return _mm(jax.nn.silu(_mm(h, p["wg"])) * _mm(h, p["wu"]), p["wd"])


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "state",
                                             "conv", "eps", "res"))
def _mamba_layer(p, ln, x, *, heads, head_dim, state, conv, eps, res):
    p, ln = _f32(p), ln.astype(jnp.float32)
    R, T, D = x.shape
    di, N, H, P = heads * head_dim, state, heads, head_dim
    proj = _mm(_rms(x, ln, eps), p["in_proj"])
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * N]
    dt = jax.nn.softplus(proj[..., 2 * di + 2 * N:] + p["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (conv - 1, 0), (0, 0)))
    c = sum(padded[:, k:k + T] * p["conv_w"][k] for k in range(conv))
    c = jax.nn.silu(c + p["conv_b"])
    xs = c[..., :di].reshape(R, T, H, P)
    Bm, Cm = c[..., di:di + N], c[..., di + N:]
    A = -jnp.exp(p["A_log"])

    def step(s, t):
        s = s * jnp.exp(dt[:, t] * A)[:, :, None, None] + \
            (dt[:, t, :, None] * xs[:, t])[..., None] * Bm[:, t, None, None]
        y = jnp.einsum("rhpn,rn->rhp", s, Cm[:, t], precision=HIGHEST)
        return s, y + p["D"][:, None] * xs[:, t]

    _, y = jax.lax.scan(step, jnp.zeros((R, H, P, N)), jnp.arange(T))
    y = jnp.moveaxis(y, 0, 1).reshape(R, T, di) * jax.nn.silu(z)
    return x + res * _mm(_rms(y, p["norm"], eps), p["out_proj"])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "head_dim", "scale", "eps",
                                             "res"))
def _attention_layer(p, ln, x, *, heads, kv_heads, head_dim, scale, eps,
                     res):
    """-> (x, the keys and values (R, T, kv_heads, head_dim))."""
    p, ln = _f32(p), ln.astype(jnp.float32)
    R, T, _ = x.shape
    h = _rms(x, ln, eps)
    q = _mm(h, p["wq"]).reshape(R, T, heads, head_dim)
    k = _mm(h, p["wk"]).reshape(R, T, kv_heads, head_dim)
    v = _mm(h, p["wv"]).reshape(R, T, kv_heads, head_dim)
    kv = (k, v)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=HIGHEST) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return x + res * _mm(o.reshape(R, T, heads * head_dim), p["wo"]), kv


@functools.partial(jax.jit, static_argnames=("top_k", "first", "eps",
                                             "res"))
def _moe_layer(pm, ps, ln, x, *, top_k, first, eps, res):
    pm, ln = _f32(pm), ln.astype(jnp.float32)
    h = _rms(x, ln, eps)
    probs = jax.nn.softmax(_mm(h, pm["router"]), -1)
    topv, topi = jax.lax.top_k(probs, top_k)
    gates = topv / topv.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for j in range(pm["wg"].shape[0]):
        g = jnp.sum(jnp.where(topi == first + j, gates, 0.0), -1)
        out = out + g[..., None] * _swiglu(
            {k: pm[k][j] for k in ("wg", "wu", "wd")}, h)
    if ps is not None:
        out = out + _swiglu(_f32(ps), h)
    return x + res * out


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "scale"))
def _head(emb, fnorm, x, *, vocab, eps, scale):
    emb = emb[:vocab].astype(jnp.float32)
    x = _rms(x, fnorm.astype(jnp.float32), eps)
    return jnp.einsum("rtd,vd->rtv", x, emb, precision=HIGHEST) / scale


def _take(tree, *idx):
    for i in idx:
        tree = jax.tree.map(lambda t, i=i: t[i], tree)
    return tree


def forward(params, arch: dict, rows: np.ndarray, keep: bool = False):
    """rows (R, T) token ids, right-padded -> logits (R, T, vocab)
    float32 on the host. ``arch``: the arm's description (see
    ``bench.drivers.pool.arm_arch``). ``keep``: return ``(logits,
    extra)``, ``extra`` holding the first attention layer's keys and
    values, ``"k"`` and ``"v"`` (R, T, kv_heads, head_dim), on the host
    (empty for an arm without attention)."""
    eps, res = arch["eps"], arch["residual_multiplier"]
    extra = {}
    emb = params["embed"]
    x = emb[jnp.asarray(rows)].astype(jnp.float32) * \
        arch["embedding_multiplier"]
    m, a, e = arch["mamba"], arch.get("attention"), arch.get("moe")
    period = arch["period"]
    for i, kind in enumerate(arch["layer_types"]):
        blk = _take(params["blocks"], i // period)
        pos = i % period
        if arch["ssm_only"]:
            x = _mamba_layer(blk["mamba"], blk["ln1"], x,
                             heads=m["heads"], head_dim=m["head_dim"],
                             state=m["state"], conv=m["conv"], eps=eps,
                             res=res)
            continue
        ln_mix, ln_ffn = blk["ln_mix"][pos], blk["ln_ffn"][pos]
        if kind == "attention":
            x, kv = _attention_layer(blk["attn"], ln_mix, x,
                                     heads=a["heads"],
                                     kv_heads=a["kv_heads"],
                                     head_dim=a["head_dim"],
                                     scale=a["scale"], eps=eps, res=res)
            if keep and "k" not in extra:
                extra["k"], extra["v"] = (np.asarray(t) for t in kv)
        else:
            j = pos - (pos > arch["attn_position"])
            x = _mamba_layer(_take(blk["mamba"], j), ln_mix, x,
                             heads=m["heads"], head_dim=m["head_dim"],
                             state=m["state"], conv=m["conv"], eps=eps,
                             res=res)
        shared = _take(blk["shared"], pos) if e["shared"] else None
        x = _moe_layer(_take(blk["moe"], pos), shared, ln_ffn, x,
                       top_k=e["top_k"], first=e["first"], eps=eps,
                       res=res)
    out = np.asarray(_head(emb, params["final_norm"], x, vocab=arch["vocab"],
                           eps=eps, scale=arch["logits_scaling"]))
    return (out, extra) if keep else out


def widest_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between two arrays of one shape (a row's logits at
    each position, or its keys), over the widest entry of ``want``."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
