"""Mixture-of-Experts FFN: top-k routing with sort/gather-based dispatch
and shard_map expert parallelism.

An expert layer may hold a share of the experts
(``cfg.experts_held`` from ``cfg.expert_first``): it routes over all
``cfg.num_experts``, takes the top-k, and computes only its own
experts' part of the output, as one chip of an expert-parallel
deployment does before the exchange. With ``moe_capacity_factor`` 0 the
layer is dropless: each expert's capacity is the token count, which no
routing can exceed (a token picks k distinct experts).

Why not the classic GShard one-hot dispatch/combine einsums: their cost is
T x E x C x D_model, which at production shapes (qwen3-moe train_4k:
T=1M, E=128, C=100k) is ~630x the useful expert FLOPs — the §Perf roofline
baseline measured exactly that. Instead tokens are ROUTED BY SORTING
(argsort by expert id, rank-within-expert for capacity, scatter into
(E_local, C, D) buffers), which is O(Tk log(Tk)) scalar work + O(TkD)
gather/scatter traffic, and the expert GEMMs run at their natural
E x C x D x F cost.

Expert parallelism: experts are sharded over the "model" mesh axis;
activations arrive replicated across that axis (they are batch-sharded
over "data"), so each model rank gathers the tokens destined to ITS
experts, runs the GEMMs, scatters back a partial output, and a psum over
"model" combines — the collective cost is one (B, S, D) all-reduce per
MoE layer, the same shape as the dense-TP pattern.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig
from repro.distributed import current_mesh, current_rules
from repro.models.layers import dense_init

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    E, D, F = cfg.local_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(D)

    def ew(k, a, b):
        return (jax.random.normal(k, (E, a, b), jnp.float32) * scale).astype(dt)

    return {
        "router": dense_init(kr, D, cfg.num_experts, jnp.float32),
        "wg": ew(kg, D, F),
        "wu": ew(ku, D, F),
        "wd": (jax.random.normal(kd, (E, F, D), jnp.float32) / math.sqrt(F)).astype(dt),
    }


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    factor: float = CAPACITY_FACTOR) -> int:
    """Slots per expert; ``factor`` 0: dropless (``num_tokens``)."""
    if factor <= 0:
        return num_tokens
    cap = int(math.ceil(num_tokens * top_k * factor / num_experts))
    return max(cap, 4)


def _moe_local(router_w, wg, wu, wd, cfg: ModelConfig, xt,
               e_lo, E_l: int, C: int) -> Tuple[jax.Array, jax.Array]:
    """Sort-based dispatch on tokens xt (T, D) for the E_l experts whose
    GLOBAL ids start at ``e_lo`` (wg/wu/wd are the local tables).
    Returns (partial output (T, D), aux load-balance loss over full E)."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.experts_per_token

    logits = xt.astype(jnp.float32) @ router_w          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    density = jnp.mean(probs, axis=0)
    topv, topi = jax.lax.top_k(probs, K)                # (T, K)
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    frac = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), 0) / K
    aux = E * jnp.sum(frac * density)

    flat_e = topi.reshape(T * K)
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gates.reshape(T * K)

    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    stok = flat_tok[order]
    sg = flat_g[order]

    # rank within expert = sorted position - start of that expert's run
    counts = jnp.bincount(flat_e, length=E)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    rank = jnp.arange(T * K, dtype=jnp.int32) - starts[se]

    local = (se >= e_lo) & (se < e_lo + E_l) & (rank < C)
    e_local = jnp.where(local, se - e_lo, 0).astype(jnp.int32)
    slot = jnp.where(local, e_local * C + rank, E_l * C)  # last bin = dropped

    buf = jnp.zeros((E_l * C + 1, D), xt.dtype)
    buf = buf.at[slot].set(xt[stok])
    expert_in = buf[:-1].reshape(E_l, C, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, wu)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_l * C, D)

    safe_slot = jnp.where(local, slot, 0)
    contrib = expert_out[safe_slot] * (sg * local).astype(xt.dtype)[:, None]
    out = jnp.zeros((T, D), xt.dtype).at[stok].add(contrib)
    return out, aux


def _axis_size(mesh, spec) -> int:
    if spec is None:
        return 1
    axes = spec if isinstance(spec, tuple) else (spec,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss). Expert-parallel over the "model"
    mesh axis when a mesh is active; plain local execution otherwise."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    cf = getattr(cfg, "moe_capacity_factor", CAPACITY_FACTOR)

    mesh = current_mesh()
    rules = current_rules() or {}
    ep = (mesh is not None and "model" in getattr(mesh, "axis_names", ())
          and rules.get("experts") == "model" and not cfg.experts_held
          and E % mesh.shape["model"] == 0)

    if not ep:
        out, aux = _moe_local(params["router"], params["wg"], params["wu"],
                              params["wd"], cfg, x.reshape(B * S, D),
                              cfg.expert_first, cfg.local_experts,
                              expert_capacity(B * S, E, K, cf))
        return out.reshape(B, S, D), aux

    mp = mesh.shape["model"]
    E_l = E // mp
    bspec = rules.get("batch")
    x_spec = P(bspec, None, None)
    T_local = (B // _axis_size(mesh, bspec)) * S
    C = expert_capacity(T_local, E, K, cf)
    pspec = {
        "router": P(None, None),
        "wg": P("model", None, None),
        "wu": P("model", None, None),
        "wd": P("model", None, None),
    }
    batch_axes = bspec if isinstance(bspec, tuple) else (
        (bspec,) if bspec else ())

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    def run(p, xb):
        Bl, Sl, _ = xb.shape
        r = jax.lax.axis_index("model")
        out, aux = _moe_local(p["router"], p["wg"], p["wu"], p["wd"], cfg,
                              xb.reshape(Bl * Sl, D), r * E_l, E_l, C)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(Bl, Sl, D), aux

    return run(params, x)
