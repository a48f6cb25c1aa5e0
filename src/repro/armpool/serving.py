"""Semi-real serving over the physical pool (DESIGN.md §16.4).

``AsyncRouterEngine`` serves through per-arm engine objects; this
module builds the pool's engine list:

* arms at or below ``serve_real_max_params`` get a :class:`DecodeArmEngine`
  — REAL jitted decode steps through ``repro.serving.engine.ServingEngine``
  (with ``reduced_decode=True`` the config's CPU-runnable ``reduced()``
  variant: still the real decode program, smoke-test weights);
* an arm named in ``ArmPoolSpec.local`` gets a :class:`DecodeArmEngine`
  at its chip share (``ModelConfig.chip_share``: the layers and experts
  one chip of its deployment holds, every width as published), whatever
  its size;
* every other arm gets a :class:`RooflineArmEngine` — a clocked sleep of
  the pool's roofline step time per decode step, so the storm's wall
  and per-arm service times reflect the declared hardware without
  materializing 100B-scale weights.

Both expose the engine protocol the async engine expects:
``generate(tokens, max_new, lengths) -> (new_tokens (B, max_new), steps)``,
``tokens`` right-padded and ``lengths`` each row's prompt length.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from repro.armpool.compile import CompiledArmPool
from repro.armpool.pool import canonical_arm
from repro.common.tracing import span
from repro.configs import get_config
from repro.serving.batcher import left_align


class RooflineArmEngine:
    """Roofline-clocked stand-in for a large pool member: each generate
    call sleeps ``step_s * latency_scale`` per decode step and returns
    stub tokens. ``decode_steps`` counts CLOCKED steps (kept separate
    from the real-decode counter)."""

    def __init__(self, name: str, step_s: float, *,
                 latency_scale: float = 1.0, max_seq: int = 4096):
        self.name = name
        self.step_s = float(step_s)
        self.latency_scale = float(latency_scale)
        self.max_seq = max_seq
        self.decode_steps = 0
        self.real_decode = False

    def generate(self, tokens, max_new: int = 8, lengths=None
                 ) -> Tuple[np.ndarray, int]:
        B = np.asarray(tokens).shape[0]
        steps = int(max_new)
        wait = self.step_s * self.latency_scale * steps
        if wait > 0:
            time.sleep(wait)
        self.decode_steps += steps
        return np.ones((B, max_new), np.int32), steps


class DecodeArmEngine:
    """A real pool member: greedy decode through the jitted serving
    engine. ``decode_steps`` counts REAL decode-step dispatches (the
    acceptance criterion's ">= 1 arm executes real jitted decode
    steps" evidence, surfaced in the storm metrics).

    Every call runs at one pinned batch width, ``max_batch`` (the serve
    stage's batch cap): rows are padded up to it, so the decode step
    compiles once, in the warm-up, whatever batch the batcher forms —
    not once per batch size on the request path. Rows decode
    independently, so padding never changes a real row's tokens, and
    rows of different prompt lengths decode left-padded (see
    ``repro.serving.engine``). ``index``: the arm's place in its pool,
    the stat ``arm`` of the span ``arm.generate``."""

    def __init__(self, name: str, cfg, *, max_batch: int, max_seq: int = 64,
                 seed: int = 0, warm: bool = True, index: int = -1):
        from repro.serving.engine import ServingEngine

        self.name = name
        self.index = int(index)
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.max_seq = max_seq
        self.engine = ServingEngine(cfg, seed=seed, max_seq=max_seq)
        self.decode_steps = 0
        self.real_decode = True
        if warm:   # keep the one-off jit compile out of the storm wall
            self.generate(np.ones((1, 1), np.int32), max_new=2)
            self.decode_steps = 0

    def generate(self, tokens, max_new: int = 8, lengths=None,
                 on_step=None, on_cache=None) -> Tuple[np.ndarray, int]:
        """``tokens`` (B, S) right-padded, ``lengths`` each row's prompt
        length (None: all S). ``on_step``, ``on_cache``: as
        ``ServingEngine.generate``, over all ``max_batch`` rows, the rows
        left-padded."""
        toks = np.asarray(tokens, np.int64)
        B, S = toks.shape
        if B > self.max_batch:
            raise ValueError(f"{self.name}: batch of {B} rows exceeds the "
                             f"engine's pinned width {self.max_batch}")
        # clamp into the (possibly reduced) vocab and cache budget
        toks = np.clip(toks, 0, self.cfg.vocab_size - 1)
        toks, start = left_align(toks, S if lengths is None else lengths)
        keep = max(1, self.max_seq - max_new - 1)
        if S > keep:
            toks, start = toks[:, -keep:], np.maximum(start - (S - keep), 0)
        padded = np.zeros((self.max_batch, toks.shape[1]), np.int32)
        padded[:B] = toks
        starts = np.zeros(self.max_batch, np.int32)
        starts[:B] = start
        with span("arm.generate", arm=self.index, rows=B,
                  prompt_max=toks.shape[1]):
            # host tokens: prefill slices them per step on the host, so
            # no eager device slice compiles per prompt position
            new, steps = self.engine.generate(padded, max_new=max_new,
                                              start=starts, on_step=on_step,
                                              on_cache=on_cache)
            new = np.asarray(new)[:B]
        # prefill replays the prompt through width-1 decode steps, so
        # the real dispatch count per call is prompt + (max_new - 1)
        self.decode_steps += toks.shape[1] + steps
        return new, steps


def build_arm_engines(pool: CompiledArmPool, aspec, *, max_batch: int,
                      seed: int = None) -> Tuple[List, Dict[str, object]]:
    """Pool -> per-arm engine list (+ an info block for the artifact).
    ``max_batch`` is the largest batch the serve stage hands an engine
    (the real-decode engines pin their batch width to it). ``seed``: the
    real-decode engines' weights (None: from the pool's checksum).

    Raises on a pool/spec K disagreement (the engines MUST line up
    with the pool's arm order — the router's arm ids index this list).
    """
    pool.validate_against(len(pool.arms), what="engine list")
    engines: List = []
    real, clocked = [], []
    local = {canonical_arm(a): cut for a, cut in aspec.local}
    if seed is None:
        seed = int(pool.checksum % 997)
    for a, name in enumerate(pool.arms):
        params = float(pool.params_b[a]) * 1e9
        if name in local or params <= aspec.serve_real_max_params:
            cfg = get_config(name)
            if name in local:
                cfg = cfg.chip_share(**dict(local[name]))
            elif aspec.reduced_decode:
                cfg = cfg.reduced()
            engines.append(DecodeArmEngine(name, cfg, max_batch=max_batch,
                                           seed=seed, index=a))
            real.append(name)
        else:
            engines.append(RooflineArmEngine(
                name, float(pool.step_s[a]),
                latency_scale=aspec.latency_scale))
            clocked.append(name)
    if not real and not clocked:
        raise ValueError("arm pool produced no engines")
    info = {"real_decode_arms": real, "roofline_clocked_arms": clocked,
            "reduced_decode": bool(aspec.reduced_decode),
            "real_decode_widths": {
                e.name: {"num_layers": e.cfg.num_layers,
                         "d_model": e.cfg.d_model,
                         "vocab_size": e.cfg.vocab_size,
                         "ssm_state": e.cfg.ssm_state,
                         "experts_held": e.cfg.local_experts,
                         "max_batch": e.max_batch}
                for e in engines if e.real_decode},
            "latency_scale": float(aspec.latency_scale)}
    return engines, info


def engine_decode_steps(engines) -> Dict[str, int]:
    """Post-storm accounting: arm name -> decode steps executed,
    split by real vs clocked."""
    out = {"real": {}, "clocked": {}}
    for e in engines:
        bucket = "real" if getattr(e, "real_decode", False) else "clocked"
        out[bucket][e.name] = int(getattr(e, "decode_steps", 0))
    return out
