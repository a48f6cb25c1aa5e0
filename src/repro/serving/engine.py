"""Single-model serving engine: prefill + jit'd decode loop over the KV/state
cache, greedy or temperature sampling. CPU-runnable with reduced configs;
the same step functions are what the dry-run lowers at production shapes.

Rows of different prompt lengths are passed left-padded with each row's
first prompt column (``start``; ``serving.batcher.left_align`` makes
them): a pad step leaves the row's cache as it was, and every row's first
new token comes from its own last prompt token.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.common.tracing import span
from repro.models import model as MODEL

# on_step(step, logits (B, V_pad)): every step's logits, the prompt's
# steps first
StepHook = Optional[Callable[[int, jax.Array], None]]
# on_cache(cache): the cache after a call's last step
CacheHook = Optional[Callable[[Dict], None]]

# decode steps on the device at once. A step writes a new cache and holds
# the one it read until it ends, so an unbounded queue holds a cache per
# step; one at a time holds two (the host's dispatch between steps is the
# price: tenths of a millisecond against steps of milliseconds)
IN_FLIGHT = 1


def serve_step(params, cfg, cache, tokens):
    """The decode program: one step of ``models.model.decode_step``."""
    return MODEL.decode_step(params, cfg, cache, tokens)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Optional[Dict] = None,
                 seed: int = 0, max_seq: int = 256):
        self.cfg = cfg
        self.max_seq = max_seq
        if params is None:
            # one program: no float32 copy of a weight outlives its cast
            params = jax.jit(MODEL.init_params, static_argnums=1)(
                jax.random.PRNGKey(seed), cfg)
        self.params = params
        self._decode = jax.jit(serve_step, static_argnums=1)
        self._flight: list = []
        self._forward = jax.jit(
            lambda p, b: MODEL.forward_train(p, cfg, b)[0])

    def prefill(self, tokens: jnp.ndarray, memory: Optional[jnp.ndarray] = None,
                start=None, on_step: StepHook = None
                ) -> Tuple[jnp.ndarray, Dict]:
        """tokens (B, S) -> (next-token logits (B, V), cache primed to S).
        ``start`` (B,): each row's first prompt column, the rows
        left-padded (None: every row fills all S columns).

        Prefill writes the prompt K/V into the cache by replaying the prompt
        through decode steps of width 1 (correct, if not the fast path; the
        fused prefill kernel is the flash_attention op on TPU)."""
        B, S = tokens.shape
        if self.cfg.arch_type == "audio":
            if memory is None:
                memory = jnp.zeros((B, self.cfg.num_audio_frames,
                                    self.cfg.d_model),
                                   jnp.dtype(self.cfg.dtype))
            memory = MODEL.encode_audio(self.params, self.cfg, memory)
        if self.cfg.arch_type == "vlm" and memory is None:
            memory = jnp.zeros((B, self.cfg.num_image_tokens,
                                self.cfg.d_model), jnp.dtype(self.cfg.dtype))
        cache = MODEL.init_cache(self.cfg, B, self.max_seq, memory=memory,
                                 params=self.params)
        if start is not None:
            cache["start"] = jnp.asarray(np.asarray(start, np.int32))
        logits = None
        self._flight = []
        with span("arm.prefill", steps=S):
            for i in range(S):
                logits, cache = self._step(cache, tokens[:, i:i + 1])
                if on_step is not None:
                    on_step(i, logits[:, -1])
        return logits[:, -1], cache

    def _step(self, cache, tokens):
        """One decode step, with at most ``IN_FLIGHT`` on the device."""
        logits, cache = self._decode(self.params, self.cfg, cache, tokens)
        self._flight.append(logits)
        if len(self._flight) >= IN_FLIGHT:
            self._flight.pop(0).block_until_ready()
        return logits, cache

    def generate(self, tokens: jnp.ndarray, max_new: int = 16,
                 memory: Optional[jnp.ndarray] = None,
                 temperature: float = 0.0, seed: int = 0, start=None,
                 on_step: StepHook = None, on_cache: CacheHook = None
                 ) -> Tuple[jnp.ndarray, int]:
        """Greedy/temperature generation. Returns (B, max_new) new tokens and
        the number of decode steps executed. ``start``: as ``prefill``.
        ``on_cache``: called with the cache after the last step (the
        prompt and every new token but the last fed)."""
        B, S = tokens.shape
        logits, cache = self.prefill(tokens, memory=memory, start=start,
                                     on_step=on_step)
        key = jax.random.PRNGKey(seed)
        out = []
        cur = None
        steps = 0
        vocab = self.cfg.vocab_size
        with span("arm.decode", steps=max_new - 1):
            for i in range(max_new):
                if cur is None:
                    nxt_logits = logits
                else:
                    nxt, cache = self._step(cache, cur)
                    nxt_logits = nxt[:, -1]
                    steps += 1
                    if on_step is not None:
                        on_step(S + steps - 1, nxt_logits)
                nxt_logits = jnp.where(
                    jnp.arange(nxt_logits.shape[-1]) < vocab, nxt_logits,
                    -1e30)
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    cur = jax.random.categorical(
                        sub, nxt_logits / temperature, axis=-1)[:, None]
                else:
                    cur = jnp.argmax(nxt_logits, axis=-1)[:, None]
                out.append(cur)
        if on_cache is not None:
            on_cache(cache)
        return jnp.concatenate(out, axis=1), steps
