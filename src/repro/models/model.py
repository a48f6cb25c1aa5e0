"""Model zoo assembly: init / train-forward / prefill / decode for all six
architecture families, with ``lax.scan`` over (super-)blocks so HLO size is
independent of depth (compile-time critical at 72-88 layers on this host).

Families
--------
dense   : uniform [attn + SwiGLU] blocks; gemma-style local:global sliding
          window handled with per-layer flags scanned alongside the params.
moe     : uniform [attn + MoE] blocks (granite, qwen3).
ssm     : uniform Mamba2 blocks (mamba2-130m).
hybrid  : super-blocks of one period of `attn_every` layers: mamba mixers
          and one attention mixer at `attn_position` (jamba: 7 mamba + 1
          attention, last; alternating dense/MoE FFNs; granite-4.0-h: 9
          mamba + attention at position 5, MoE plus a shared expert at
          every layer, NoPE, granite's multipliers).
audio   : whisper encoder-decoder backbone; conv/mel frontend is a stub —
          the caller supplies frame embeddings (B, T_frames, d_model).
vlm     : llama-3.2-vision style: decoder super-blocks of 5 layers where the
          5th carries an extra gated cross-attention into patch embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.distributed import shard
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE


def pad_vocab(v: int) -> int:
    return -(-v // 256) * 256


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack_init(fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def _init_dense_block(cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)

    def f(key):
        ka, kf = jax.random.split(key)
        return {
            "attn": L.init_attention(ka, cfg),
            "ffn": L.init_ffn(kf, cfg),
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
            "ln2": L.rmsnorm_init(cfg.d_model, dt),
        }

    return f


def _init_moe_block(cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)

    def f(key):
        ka, kf = jax.random.split(key)
        return {
            "attn": L.init_attention(ka, cfg),
            "moe": MOE.init_moe(kf, cfg),
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
            "ln2": L.rmsnorm_init(cfg.d_model, dt),
        }

    return f


def _init_ssm_block(cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)

    def f(key):
        return {
            "mamba": M.init_mamba(key, cfg),
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
        }

    return f


def _init_hybrid_superblock(cfg: ModelConfig):
    """Hybrid super-block: `attn_every` layers, the mixer at
    `attn_position` is attention, the rest mamba; an MoE FFN every
    `moe_every` layers (with the shared expert, if any), dense FFNs
    between."""
    dt = jnp.dtype(cfg.dtype)
    n = cfg.attn_every
    n_mamba = n - 1
    n_moe = n // cfg.moe_every
    n_dense = n - n_moe

    def f(key):
        km, ka, kd, ke = jax.random.split(key, 4)
        p = {
            "mamba": _stack_init(lambda k: M.init_mamba(k, cfg), km, n_mamba),
            "attn": L.init_attention(ka, cfg),
            "moe": _stack_init(lambda k: MOE.init_moe(k, cfg), ke, n_moe),
            "ln_mix": jnp.ones((n, cfg.d_model), dt),
            "ln_ffn": jnp.ones((n, cfg.d_model), dt),
        }
        if n_dense:
            p["ffn_dense"] = _stack_init(lambda k: L.init_ffn(k, cfg), kd,
                                         n_dense)
        if cfg.shared_expert_ff:
            p["shared"] = _stack_init(
                lambda k: L.init_ffn(k, cfg, cfg.shared_expert_ff),
                jax.random.fold_in(key, 1), n_moe)
        return p

    return f


def _init_whisper(cfg: ModelConfig, key) -> dict:
    dt = jnp.dtype(cfg.dtype)
    ke, kd, kc = jax.random.split(key, 3)

    def enc_block(k):
        ka, kf = jax.random.split(k)
        return {
            "attn": L.init_attention(ka, cfg),
            "ffn": L.init_ffn(kf, cfg),
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
            "ln2": L.rmsnorm_init(cfg.d_model, dt),
        }

    def dec_block(k):
        ka, kx, kf = jax.random.split(k, 3)
        return {
            "attn": L.init_attention(ka, cfg),
            "cross": L.init_attention(kx, cfg),
            "ffn": L.init_ffn(kf, cfg),
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
            "ln2": L.rmsnorm_init(cfg.d_model, dt),
            "ln3": L.rmsnorm_init(cfg.d_model, dt),
        }

    return {
        "enc_blocks": _stack_init(enc_block, ke, cfg.num_encoder_layers),
        "enc_norm": L.rmsnorm_init(cfg.d_model, dt),
        "dec_blocks": _stack_init(dec_block, kd, cfg.num_layers),
    }


def _init_vlm_superblock(cfg: ModelConfig):
    """Super-block of `cross_attn_every` self-attn layers; the last one is
    followed by a gated cross-attention layer into the image tokens."""
    dt = jnp.dtype(cfg.dtype)
    n = cfg.cross_attn_every

    def f(key):
        ks, kx, kf = jax.random.split(key, 3)

        def self_layer(k):
            ka, kff = jax.random.split(k)
            return {
                "attn": L.init_attention(ka, cfg),
                "ffn": L.init_ffn(kff, cfg),
                "ln1": L.rmsnorm_init(cfg.d_model, dt),
                "ln2": L.rmsnorm_init(cfg.d_model, dt),
            }

        return {
            "self": _stack_init(self_layer, ks, n),
            "cross": L.init_attention(kx, cfg),
            "cross_ffn": L.init_ffn(kf, cfg),
            "cross_ln1": L.rmsnorm_init(cfg.d_model, dt),
            "cross_ln2": L.rmsnorm_init(cfg.d_model, dt),
            "gate_attn": jnp.zeros((1,), jnp.float32),
            "gate_ffn": jnp.zeros((1,), jnp.float32),
        }

    return f


def init_params(key: jax.Array, cfg: ModelConfig) -> Dict:
    ke, kb, ku = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    vp = pad_vocab(cfg.vocab_size)
    params: Dict = {
        "embed": L.embed_init(ke, vp, cfg.d_model, dt),
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(ku, cfg.d_model, vp, dt)

    at = cfg.arch_type
    if at == "dense":
        params["blocks"] = _stack_init(_init_dense_block(cfg), kb, cfg.num_layers)
    elif at == "moe":
        params["blocks"] = _stack_init(_init_moe_block(cfg), kb, cfg.num_layers)
    elif at == "ssm":
        params["blocks"] = _stack_init(_init_ssm_block(cfg), kb, cfg.num_layers)
    elif at == "hybrid":
        nsb = cfg.num_layers // cfg.attn_every
        params["blocks"] = _stack_init(_init_hybrid_superblock(cfg), kb, nsb)
    elif at == "audio":
        params.update(_init_whisper(cfg, kb))
    elif at == "vlm":
        nsb = cfg.num_layers // cfg.cross_attn_every
        params["blocks"] = _stack_init(_init_vlm_superblock(cfg), kb, nsb)
    else:
        raise ValueError(f"unknown arch_type {at}")
    return params


# ---------------------------------------------------------------------------
# per-layer attention windows (gemma local:global pattern)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig, decode: bool = False) -> jax.Array:
    """(num_layers,) int32: sliding window per layer; 0 = full attention.

    During long-context decode, "global" layers are capped at
    ``global_attn_cap`` (see DESIGN.md §4)."""
    n = cfg.num_layers
    if cfg.local_global_ratio > 0:
        period = cfg.local_global_ratio + 1
        idx = jnp.arange(n)
        is_global = (idx % period) == (period - 1)
        gwin = cfg.global_attn_cap if decode else 0
        return jnp.where(is_global, gwin, cfg.sliding_window).astype(jnp.int32)
    w = cfg.sliding_window
    return jnp.full((n,), w, jnp.int32)


# ---------------------------------------------------------------------------
# block bodies (shared between train forward and decode)
#
# Device scopes (repro.common.tracing.SCOPES): arm.attention, arm.mamba,
# arm.moe (routed and shared experts) and, in decode_step, arm.head.
# ``rows`` (decode of left-padded rows, see decode_step) goes to every
# mixer that keeps state.
# ---------------------------------------------------------------------------


def _res(cfg, x, y):
    """Residual add, the branch scaled by ``residual_multiplier``."""
    if cfg.residual_multiplier == 1.0:
        return x + y
    return x + cfg.residual_multiplier * y


def _self_attention(p, cfg, h, positions, window, cache_l, cache_pos,
                    k_offset=0, rows=None):
    with jax.named_scope("arm.attention"):
        return L.attention(p, cfg, h, positions=positions, causal=True,
                           window=window, cache=cache_l, cache_pos=cache_pos,
                           k_offset=k_offset, rows=rows)


def _mamba(p, cfg, h, cache_l, rows):
    with jax.named_scope("arm.mamba"):
        return M.mamba_block(p, cfg, h, cache=cache_l, rows=rows)


def _dense_body(p, cfg, x, positions, window, cache_l=None, cache_pos=None,
                k_offset=0, rows=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    a, new_c = _self_attention(p["attn"], cfg, h, positions, window, cache_l,
                               cache_pos, k_offset, rows)
    x = _res(cfg, x, a)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = _res(cfg, x, L.ffn(p["ffn"], h))
    return x, new_c


def _moe_body(p, cfg, x, positions, window, cache_l=None, cache_pos=None,
              k_offset=0, rows=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    a, new_c = _self_attention(p["attn"], cfg, h, positions, window, cache_l,
                               cache_pos, k_offset, rows)
    x = _res(cfg, x, a)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("arm.moe"):
        mo, aux = MOE.moe_ffn(p["moe"], cfg, h)
    return _res(cfg, x, mo), new_c, aux


def _ssm_body(p, cfg, x, cache_l=None, rows=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    m, new_c = _mamba(p["mamba"], cfg, h, cache_l, rows)
    return _res(cfg, x, m), new_c


def _hybrid_body(p, cfg, x, positions, cache_l=None, cache_pos=None,
                 rows=None):
    """One hybrid super-block, unrolled over its `attn_every` positions."""
    n = cfg.attn_every
    aux_total = jnp.float32(0.0)
    new_cache = {"mamba": [], "attn": None} if cache_l is not None else None
    i_mamba = i_dense = i_moe = 0
    for pos in range(n):
        ln_mix = p["ln_mix"][pos]
        ln_ffn = p["ln_ffn"][pos]
        h = L.rmsnorm(ln_mix, x, cfg.norm_eps)
        if pos == cfg.attn_position:
            c = cache_l["attn"] if cache_l is not None else None
            a, nc = _self_attention(p["attn"], cfg, h, positions,
                                    cfg.sliding_window, c, cache_pos,
                                    rows=rows)
            if new_cache is not None:
                new_cache["attn"] = nc
            x = _res(cfg, x, a)
        else:
            mp = jax.tree.map(lambda t: t[i_mamba], p["mamba"])
            c = (jax.tree.map(lambda t: t[i_mamba], cache_l["mamba"])
                 if cache_l is not None else None)
            m, nc = _mamba(mp, cfg, h, c, rows)
            if new_cache is not None:
                new_cache["mamba"].append(nc)
            x = _res(cfg, x, m)
            i_mamba += 1
        h = L.rmsnorm(ln_ffn, x, cfg.norm_eps)
        if (pos % cfg.moe_every) == (cfg.moe_every - 1):
            ep = jax.tree.map(lambda t: t[i_moe], p["moe"])
            with jax.named_scope("arm.moe"):
                mo, aux = MOE.moe_ffn(ep, cfg, h)
                if cfg.shared_expert_ff:
                    sp = jax.tree.map(lambda t: t[i_moe], p["shared"])
                    mo = mo + L.ffn(sp, h)
            x = _res(cfg, x, mo)
            aux_total = aux_total + aux
            i_moe += 1
        else:
            fp = jax.tree.map(lambda t: t[i_dense], p["ffn_dense"])
            x = _res(cfg, x, L.ffn(fp, h))
            i_dense += 1
    if new_cache is not None:
        new_cache["mamba"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *new_cache["mamba"])
    return x, new_cache, aux_total


def _vlm_superblock_body(p, cfg, x, positions, image_embed, window,
                         cache_l=None, cache_pos=None, cross_cache=None,
                         rows=None):
    n = cfg.cross_attn_every
    new_self = [] if cache_l is not None else None
    for i in range(n):
        sp = jax.tree.map(lambda t: t[i], p["self"])
        c = (jax.tree.map(lambda t: t[i], cache_l) if cache_l is not None else None)
        x, nc = _dense_body(sp, cfg, x, positions, window, c, cache_pos,
                            rows=rows)
        if new_self is not None:
            new_self.append(nc)
    # gated cross-attention into image tokens
    h = L.rmsnorm(p["cross_ln1"], x, cfg.norm_eps)
    ca, _ = L.attention(p["cross"], cfg, h, positions=positions, causal=False,
                        kv_source=image_embed, cache=cross_cache,
                        use_rope=False)
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * ca
    h = L.rmsnorm(p["cross_ln2"], x, cfg.norm_eps)
    x = x + jnp.tanh(p["gate_ffn"]).astype(x.dtype) * L.ffn(p["cross_ffn"], h)
    new_cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_self)
                 if new_self is not None else None)
    return x, new_cache


# ---------------------------------------------------------------------------
# train / prefill forward
# ---------------------------------------------------------------------------


def forward_hidden(params: Dict, cfg: ModelConfig, batch: Dict
                   ) -> Tuple[jax.Array, jax.Array]:
    """Backbone forward up to (and including) the final norm.

    Returns (hidden (B, S, D), moe_aux_loss scalar).
    batch: {"tokens": (B,S)} plus, per family:
      audio: {"audio_embed": (B, T_frames, D)}
      vlm:   {"image_embed": (B, N_patches, D)}
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = shard(_embed(params, cfg, tokens), "batch", "seq", None)
    positions = jnp.arange(S)
    aux = jnp.float32(0.0)
    at = cfg.arch_type
    ckpt = (jax.checkpoint if cfg.remat == "layer" else (lambda f: f))

    if (at == "dense" and cfg.local_global_ratio > 0
            and S >= 2 * cfg.sliding_window):
        # gemma local:global interleave with STATIC structure: scan over
        # super-blocks of (ratio local + 1 global) layers so the banded
        # O(S*w) kernel is hard-wired for local layers (no per-layer cond;
        # the roofline accounts each branch exactly). Layout holds because
        # globals sit at index (period-1) mod period.
        period = cfg.local_global_ratio + 1
        n_full = (cfg.num_layers // period) * period
        w = cfg.sliding_window

        def local_layer(p, x):
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, _ = L.attention(p["attn"], cfg, h, positions=positions,
                               causal=True, local_window=w)
            x = x + a
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            return x + L.ffn(p["ffn"], h)

        @ckpt
        def group_body(x, g):
            for j in range(period - 1):
                x = local_layer(jax.tree.map(lambda t, j=j: t[j], g), x)
            gp = jax.tree.map(lambda t: t[period - 1], g)
            x, _ = _dense_body(gp, cfg, x, positions, 0)
            return x, None

        groups = jax.tree.map(
            lambda t: t[:n_full].reshape((n_full // period, period)
                                         + t.shape[1:]), params["blocks"])
        x, _ = jax.lax.scan(group_body, x, groups)
        if n_full < cfg.num_layers:
            @ckpt
            def tail_body(x, p):
                return local_layer(p, x), None

            tail = jax.tree.map(lambda t: t[n_full:], params["blocks"])
            x, _ = jax.lax.scan(tail_body, x, tail)
    elif at in ("dense", "moe"):
        @ckpt
        def body(carry, xs):
            x, aux = carry
            p, w = xs
            if at == "dense":
                x, _ = _dense_body(p, cfg, x, positions, w)
            else:
                x, _, a = _moe_body(p, cfg, x, positions, w)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(body, (x, aux),
                                   (params["blocks"], layer_windows(cfg)))
    elif at == "ssm":
        @ckpt
        def body(x, p):
            x, _ = _ssm_body(p, cfg, x)
            return x, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
    elif at == "hybrid":
        @ckpt
        def body(carry, p):
            x, aux = carry
            x, _, a = _hybrid_body(p, cfg, x, positions)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
    elif at == "audio":
        x = _whisper_forward(params, cfg, batch, tokens, positions)
    elif at == "vlm":
        img = batch["image_embed"].astype(x.dtype)
        w0 = int(cfg.sliding_window)

        @ckpt
        def body(x, p):
            x, _ = _vlm_superblock_body(p, cfg, x, positions, img, w0)
            return x, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
    else:
        raise ValueError(at)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def unembed_matrix(params: Dict) -> jax.Array:
    unembed = params.get("unembed")
    return unembed if unembed is not None else params["embed"].T


def _embed(params: Dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _logits(params: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final-norm output -> logits, divided by ``logits_scaling``."""
    logits = x @ unembed_matrix(params)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def forward_train(params: Dict, cfg: ModelConfig, batch: Dict
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S, V_pad), moe_aux_loss scalar)."""
    x, aux = forward_hidden(params, cfg, batch)
    logits = shard(_logits(params, cfg, x), "batch", "seq", "vocab")
    return logits, aux


def encode_audio(params, cfg, audio_embed):
    """Whisper encoder over stub frame embeddings (B, T_frames, d_model) ->
    memory for decoder cross-attention."""
    Ta = audio_embed.shape[1]
    pe = L.sinusoidal_positions(Ta, cfg.d_model).astype(audio_embed.dtype)
    h_enc = shard(audio_embed + pe[None], "batch", "frames", None)
    ckpt = (jax.checkpoint if cfg.remat == "layer" else (lambda f: f))

    @ckpt
    def enc_body(h, p):
        z = L.rmsnorm(p["ln1"], h, cfg.norm_eps)
        a, _ = L.attention(p["attn"], cfg, z, positions=jnp.arange(Ta),
                           causal=False, use_rope=False)
        h = h + a
        z = L.rmsnorm(p["ln2"], h, cfg.norm_eps)
        return h + L.ffn(p["ffn"], z), None

    h_enc, _ = jax.lax.scan(enc_body, h_enc, params["enc_blocks"])
    return L.rmsnorm(params["enc_norm"], h_enc, cfg.norm_eps)


def _whisper_forward(params, cfg, batch, tokens, positions):
    memory = encode_audio(params, cfg, batch["audio_embed"])
    ckpt = (jax.checkpoint if cfg.remat == "layer" else (lambda f: f))

    x = params["embed"][tokens]
    x = shard(x, "batch", "seq", None)

    @ckpt
    def dec_body(x, p):
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, _ = L.attention(p["attn"], cfg, h, positions=positions, causal=True)
        x = x + a
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        ca, _ = L.attention(p["cross"], cfg, h, positions=positions,
                            causal=False, kv_source=memory, use_rope=False)
        x = x + ca
        h = L.rmsnorm(p["ln3"], x, cfg.norm_eps)
        return x + L.ffn(p["ffn"], h), None

    x, _ = jax.lax.scan(dec_body, x, params["dec_blocks"])
    return x


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(logits: jax.Array, labels: jax.Array, vocab_size: int,
            aux: jax.Array = 0.0, aux_weight: float = 0.01) -> jax.Array:
    """Next-token cross entropy over the *unpadded* vocabulary."""
    vp = logits.shape[-1]
    logits = logits[:, :-1].astype(jnp.float32)
    labels = labels[:, 1:]
    if vp > vocab_size:
        neg = jnp.full((vp,), -1e30, jnp.float32)
        mask = jnp.where(jnp.arange(vp) < vocab_size, 0.0, neg)
        logits = logits + mask
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + aux_weight * aux


def lm_loss_chunked(hidden: jax.Array, unembed: jax.Array, labels: jax.Array,
                    vocab_size: int, aux: jax.Array = 0.0,
                    aux_weight: float = 0.01, chunk: int = 512) -> jax.Array:
    """Fused final-projection + next-token cross entropy, scanned over
    sequence chunks so the f32 logits of only ``chunk`` positions are ever
    live (materializing (B, S, V_pad) f32 logits dominated train-step temp
    memory for the 128k-262k-vocab archs — §Perf iteration 5).

    hidden: (B, S, D) final-norm output; unembed: (D, V_pad)."""
    B, S, D = hidden.shape
    vp = unembed.shape[-1]
    h = hidden[:, :-1]
    y = labels[:, 1:]
    Sm = S - 1
    nc = -(-Sm // chunk)
    pad = nc * chunk - Sm
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    valid = (jnp.arange(nc * chunk) < Sm)
    hc = h.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    yc = y.reshape(B, nc, chunk).transpose(1, 0, 2)
    vc = valid.reshape(nc, chunk)
    vmask = jnp.where(jnp.arange(vp) < vocab_size, 0.0, -1e30)

    def chunk_loss(acc, inp):
        hb, yb, vb = inp
        logits = (hb @ unembed).astype(jnp.float32) + vmask
        logits = shard(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((lse - gold) * vb[None].astype(jnp.float32)), None

    total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0), (hc, yc, vc))
    return total / (B * Sm) + aux_weight * aux


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------


def _attn_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, cache_len, hd), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, cache_len, hd), dtype),
    }


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    """Attention-cache length: full seq, or the sliding/global cap when the
    arch is sub-quadratic (long_500k path)."""
    if cfg.local_global_ratio > 0 or cfg.sliding_window > 0:
        cap = max(cfg.sliding_window, cfg.global_attn_cap
                  if cfg.local_global_ratio > 0 else cfg.sliding_window)
        return min(seq_len, cap)
    if cfg.arch_type == "hybrid":
        return min(seq_len, cfg.global_attn_cap)
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               memory: Optional[jax.Array] = None,
               params: Optional[Dict] = None) -> Dict:
    """Decode-state pytree. ``seq_len`` is the context length the cache must
    hold. For whisper, ``memory``+``params`` precompute cross K/V."""
    dt = jnp.dtype(cfg.dtype)
    at = cfg.arch_type
    W = cache_len_for(cfg, seq_len)
    # "start": each row's first real position (see decode_step)
    cache: Dict = {"pos": jnp.zeros((), jnp.int32),
                   "offset": jnp.zeros((), jnp.int32),
                   "start": jnp.zeros((batch,), jnp.int32)}

    if at in ("dense", "moe"):
        cache["layers"] = jax.vmap(
            lambda _: _attn_cache(cfg, batch, W, dt))(jnp.arange(cfg.num_layers))
    elif at == "ssm":
        cache["layers"] = jax.vmap(
            lambda _: M.init_mamba_cache(cfg, batch, dt))(jnp.arange(cfg.num_layers))
    elif at == "hybrid":
        nsb = cfg.num_layers // cfg.attn_every

        def one(_):
            return {
                "attn": _attn_cache(cfg, batch, W, dt),
                "mamba": jax.vmap(lambda __: M.init_mamba_cache(cfg, batch, dt))(
                    jnp.arange(cfg.attn_every - 1)),
            }

        cache["layers"] = jax.vmap(one)(jnp.arange(nsb))
    elif at == "audio":
        cache["layers"] = jax.vmap(
            lambda _: _attn_cache(cfg, batch, W, dt))(jnp.arange(cfg.num_layers))
        if memory is not None and params is not None:
            cache["cross"] = jax.vmap(
                lambda p: L.init_cross_kv(p["cross"], cfg, memory)
            )(params["dec_blocks"])
    elif at == "vlm":
        nsb = cfg.num_layers // cfg.cross_attn_every

        def one(_):
            return jax.vmap(lambda __: _attn_cache(cfg, batch, W, dt))(
                jnp.arange(cfg.cross_attn_every))

        cache["layers"] = jax.vmap(one)(jnp.arange(nsb))
        if memory is not None:
            cache["image_embed"] = memory.astype(dt)
    return cache


def _scan_decode(body, x, blocks, cache_layers, extra_xs=None):
    """Scan over layers with the FULL stacked cache as a loop CARRY,
    sliced/updated per layer with dynamic(-update)-index. Carries alias
    in-place under XLA, so the multi-GB cache is never copied per step —
    passing the cache as scan xs/ys instead reallocates (and on this
    backend, copies) the whole stack every decode step."""
    xs = (blocks, extra_xs) if extra_xs is not None else blocks

    def f(carry, layer_xs):
        x, cache_all, i = carry
        c = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            cache_all)
        x, nc = body(x, layer_xs, c)
        cache_all = jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_index_in_dim(t, u, i, 0),
            cache_all, nc)
        return (x, cache_all, i + 1), None

    (x, new_cache, _), _ = jax.lax.scan(
        f, (x, cache_layers, jnp.int32(0)), xs)
    return x, new_cache


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict,
                tokens: jax.Array) -> Tuple[jax.Array, Dict]:
    """One serving step: tokens (B, 1) -> (logits (B, 1, V_pad), new cache).

    Rows of different prompt lengths decode together left-padded: row b's
    prompt starts at step ``cache["start"][b]``, so every prompt ends at
    the same step. A step before a row's start is a pad step: it leaves
    the row's state (Mamba2 conv and SSM state, attention K/V) as it was,
    and the row's attention never reads the slots before its start. The
    position of every real token then shifts by its row's start, which
    relative position embeddings (RoPE) and NoPE do not see."""
    B, S = tokens.shape
    pos = cache["pos"]
    real_pos = (cache["offset"] + pos)[None]
    x = shard(_embed(params, cfg, tokens), "batch", None, None)
    rows = {"active": pos >= cache["start"], "start": cache["start"]}
    at = cfg.arch_type

    if at in ("dense", "moe"):
        def body(x, layer_xs, c):
            (p, w) = layer_xs
            if at == "dense":
                return _dense_body(p, cfg, x, real_pos, w, c, pos,
                                   k_offset=cache["offset"], rows=rows)
            x, nc, _ = _moe_body(p, cfg, x, real_pos, w, c, pos,
                                 k_offset=cache["offset"], rows=rows)
            return x, nc

        x, new_layers = _scan_decode(
            lambda x, xs, c: body(x, xs, c), x, params["blocks"],
            cache["layers"], extra_xs=layer_windows(cfg, decode=True))
    elif at == "ssm":
        x, new_layers = _scan_decode(
            lambda x, p, c: _ssm_body(p, cfg, x, c, rows), x,
            params["blocks"], cache["layers"])
    elif at == "hybrid":
        def body(x, p, c):
            x, nc, _ = _hybrid_body(p, cfg, x, real_pos, c, pos, rows)
            return x, nc

        x, new_layers = _scan_decode(body, x, params["blocks"],
                                     cache["layers"])
    elif at == "audio":
        def body(x, layer_xs, c):
            (p, cx) = layer_xs
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, nc = L.attention(p["attn"], cfg, h, positions=real_pos,
                                causal=True, cache=c, cache_pos=pos,
                                rows=rows)
            x = x + a
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            ca, _ = L.attention(p["cross"], cfg, h, positions=real_pos,
                                causal=False, cache=cx, cache_pos=pos,
                                kv_source=jnp.zeros((B, 1, cfg.d_model), x.dtype),
                                use_rope=False)
            x = x + ca
            h = L.rmsnorm(p["ln3"], x, cfg.norm_eps)
            return x + L.ffn(p["ffn"], h), nc

        x, new_layers = _scan_decode(body, x, params["dec_blocks"],
                                     cache["layers"], extra_xs=cache["cross"])
    elif at == "vlm":
        img = cache["image_embed"]
        w0 = int(cfg.sliding_window)

        def body(x, p, c):
            return _vlm_superblock_body(p, cfg, x, real_pos, img, w0,
                                        cache_l=c, cache_pos=pos, rows=rows)

        x, new_layers = _scan_decode(body, x, params["blocks"],
                                     cache["layers"])
    else:
        raise ValueError(at)

    with jax.named_scope("arm.head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = shard(_logits(params, cfg, x), "batch", None, "vocab")

    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = pos + S
    return logits, new_cache
