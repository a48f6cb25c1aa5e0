"""Faults the real arms' decode path can have, planted in the program
underneath the harness: the arms' checks (``arm_logit_err.*``,
``arm_state_rounded.*``, ``arm_kv_err.*``) have to come out not correct
under each. The tests plant them at a size a test run holds;

    python3 bench/arm_faults.py --config nucb_pool6 \
        --traffic chat_bursty --seed 7 [--seed 8 ...] [--fault NAME ...]

reads them on the chip at the configuration's own size: one arm batch
of the traffic's prompts through each real arm's engine, at the serve
batch, with the weights a run of that seed draws, sound and then under
each fault, all in one process (one process holds the chip), one JSON
line each with the check's numbers and the limits. Not used by a
benchmark run.

Planting swaps functions of the program for faulty ones and clears
JAX's caches, so the next programs trace the fault in (and the sound
ones after it). The bfloat16 state is held in a bfloat16 cache: a round
trip through bfloat16 inside one program is one XLA may drop (it allows
itself excess precision), and on the chip it did, in granite's step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import os
import sys


def _bf16_state(sound):
    """The SSM state held in bfloat16 between decode steps: the cache
    holds it so, and each step writes it so."""
    import jax.numpy as jnp

    def step(*a):
        y, state = sound(*a)
        return y, state.astype(jnp.bfloat16)
    return step


def _bf16_state_cache(sound):
    def init(*a, **kw):
        cache = sound(*a, **kw)
        return dict(cache, ssm=cache["ssm"].astype("bfloat16"))
    return init


def _no_gated_norm(sound):
    """Mamba-2's gated RMSNorm left out: y * silu(z) goes to out_proj."""
    return lambda w, y, eps=1e-5: y


def _no_shared_expert(sound):
    """The dense SwiGLU (the hybrid's shared expert) returns zeros."""
    import jax.numpy as jnp
    return lambda params, x: jnp.zeros_like(x)


def _capacity(sound):
    """Expert capacity 1.25 in place of dropless routing."""
    return lambda T, E, K, factor=1.25: sound(T, E, K, 1.25)


def _rope(sound):
    """RoPE applied to the NoPE attention."""
    def attention(params, cfg, x, **kw):
        return sound(params, dataclasses.replace(cfg, use_rope=True), x,
                     **kw)
    return attention


def _pads_replayed(sound):
    """The engine drops the rows' lengths: pads decode as tokens."""
    def generate(self, tokens, max_new=8, lengths=None, **hooks):
        return sound(self, tokens, max_new=max_new, **hooks)
    return generate


# name: (module, attribute it replaces, how), or a tuple of such
FAULTS = {
    "ssm_state_in_bfloat16": (("repro.models.mamba", "ssd_decode_step",
                               _bf16_state),
                              ("repro.models.mamba", "init_mamba_cache",
                               _bf16_state_cache)),
    "gated_norm_left_out": ("repro.models.mamba", "rmsnorm",
                            _no_gated_norm),
    "shared_expert_left_out": ("repro.models.layers", "ffn",
                               _no_shared_expert),
    "capacity_1_25_drops": ("repro.models.moe", "expert_capacity",
                            _capacity),
    "rope_applied": ("repro.models.layers", "attention", _rope),
    "pads_replayed_as_tokens": ("repro.armpool.serving:DecodeArmEngine",
                                "generate", _pads_replayed),
}


@contextlib.contextmanager
def plant(name: str):
    """The program with fault ``name`` in it, for the ``with`` block."""
    import jax
    parts = FAULTS[name]
    if isinstance(parts[0], str):
        parts = (parts,)
    undo = []
    for where, attr, how in parts:
        mod, _, cls = where.partition(":")
        owner = importlib.import_module(mod)
        owner = getattr(owner, cls) if cls else owner
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, how(undo[-1][2]))
    jax.clear_caches()
    try:
        yield
    finally:
        for owner, attr, sound in reversed(undo):
            setattr(owner, attr, sound)
        jax.clear_caches()


def batch_errs(cfg: dict, prompts: list, *, max_new: int, seed: int = 0,
               engines=None) -> dict:
    """The arms' checks of one arm batch of ``prompts`` through each
    real arm of ``cfg`` (the pool driver's, every row compared).
    ``engines``: {arm: DecodeArmEngine} to reuse (built here if None)."""
    import numpy as np

    from bench.common import Reservoir
    from bench.drivers import pool as POOL

    if engines is None:
        engines = build_engines(cfg, len(prompts), seed)
    rows = len(prompts)
    toks = np.zeros((rows, max(len(p) for p in prompts)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = np.array([len(p) for p in prompts])
    rng = np.random.default_rng(seed)
    out = {}
    for name, eng in engines.items():
        arch = POOL.arm_arch(cfg, name)
        proxy = POOL.ArmProxy(eng, Reservoir(1, rng), rows, rng, arch)
        proxy.recording = True
        proxy.generate(toks, max_new=max_new, lengths=lengths)
        w = type("Arms", (), {"arms": {name: proxy},
                              "arch": {name: arch}})
        out.update(POOL.arm_numbers(w, {name: eng.engine.params}))
    return out


def build_engines(cfg: dict, rows: int, seed: int) -> dict:
    """Each real arm's engine at the size the configuration states."""
    from repro.armpool.serving import DecodeArmEngine, get_config
    out = {}
    for name in cfg["pool"]["real"]:
        mcfg = get_config(name)
        if name == cfg["pool"]["local"]:
            mcfg = mcfg.chip_share(len(cfg["layer_types"]),
                                   cfg["num_local_experts"],
                                   cfg["expert_first"])
        out[name] = DecodeArmEngine(name, mcfg, max_batch=rows, seed=seed,
                                    warm=False)
    return out


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench.common import BENCH, load_json, split_seed
    from bench.drivers.pool import prompt_pool
    from bench.harness import check_device, enable_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True,
                    help="repeat for more seeds, each with its weights")
    ap.add_argument("--fault", action="append", default=None)
    args = ap.parse_args(argv)
    check_device(1)
    enable_cache()
    cfg = load_json(BENCH, "configs", args.config + ".json")
    traffic = load_json(BENCH, "traffic", args.traffic + ".json")
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cfg.get("matmul_precision"))
    rows = int(traffic["serve_batch"])
    for seed in args.seed:
        prompts = prompt_pool(traffic, np.random.default_rng(seed))[:rows]
        engines = build_engines(cfg, rows, split_seed(seed)["weights"])
        for name in [None] + (args.fault or sorted(FAULTS)):
            with plant(name) if name else contextlib.nullcontext():
                errs = batch_errs(cfg, prompts,
                                  max_new=int(traffic["max_new"]),
                                  seed=seed, engines=engines)
            print(json.dumps({"seed": seed, "fault": name, "numbers": errs,
                              "limits": {k: cfg["limits"][k]
                                         for k in errs}}), flush=True)
        del engines
    return 0


if __name__ == "__main__":
    sys.exit(main())
