"""LM training step used by the example driver and the multi-pod dry-run."""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import model as MODEL
from repro.training.optim import adamw_init, adamw_update, clip_by_global_norm

# remat policy applied to the per-layer scan body via jax.checkpoint on the
# forward; 'none' lowers without remat (more memory, fewer FLOPs).


def make_train_state(key, cfg: ModelConfig):
    params = MODEL.init_params(key, cfg)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def loss_fn(params, cfg: ModelConfig, batch: Dict) -> jax.Array:
    hidden, aux = MODEL.forward_hidden(params, cfg, batch)
    if cfg.logits_scaling != 1.0:   # (x / s) @ U == (x @ U) / s
        hidden = hidden / cfg.logits_scaling
    return MODEL.lm_loss_chunked(hidden, MODEL.unembed_matrix(params),
                                 batch["labels"], cfg.vocab_size, aux)


def train_step(state, batch, *, cfg: ModelConfig, lr: float = 3e-4,
               max_grad_norm: float = 1.0, weight_decay: float = 0.01,
               accum_steps: int = 1) -> Tuple[Any, Dict]:
    """One optimizer step. ``accum_steps`` > 1 splits the global batch into
    microbatches processed sequentially with f32 gradient accumulation —
    per-layer remat bounds the per-LAYER working set, but the saved
    residual stream is still L x (B_local, S, D); at train_4k scale
    (1M tokens) that alone exceeds v5e HBM for the big dense archs, so
    microbatching is what makes the fit proof hold (EXPERIMENTS.md §Perf).
    """
    params = state["params"]
    if accum_steps <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
    else:
        def micro(carry, mb):
            gacc, lacc = carry
            l, g = jax.value_and_grad(loss_fn)(params, cfg, mb)
            gacc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / accum_steps,
                gacc, g)
            return (gacc, lacc + l / accum_steps), None

        micro_batches = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                + x.shape[1:]), batch)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = jax.lax.scan(
            micro, (zeros, jnp.float32(0.0)), micro_batches)
    grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
    params, opt = adamw_update(grads, state["opt"], params, lr=lr,
                               weight_decay=weight_decay)
    new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
    return new_state, {"loss": loss, "grad_norm": gnorm}


def default_accum_steps(cfg: ModelConfig, global_batch: int, seq: int,
                        data_shards: int, budget_bytes: float = 6e9) -> int:
    """Pick the smallest power-of-two microbatch count so the saved
    per-layer residual stream fits the activation budget."""
    b_local = max(global_batch // data_shards, 1)
    per_mb = cfg.num_layers * b_local * seq * cfg.d_model * 2  # bf16
    m = 1
    while per_mb / m > budget_bytes and m < b_local:
        m *= 2
    return m


def jit_train_step(cfg: ModelConfig, **kw):
    return jax.jit(functools.partial(train_step, cfg=cfg, **kw))
