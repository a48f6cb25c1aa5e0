"""Kernel microbenchmarks.

On this CPU-only host the Pallas kernels execute in interpret mode (Python
— correctness, not speed), so the wall-times below are NOT TPU numbers.
What IS meaningful here: the pure-jnp reference path timings (the XLA-CPU
fallback the models use) and the kernels' analytic FLOPs/bytes, which the
roofline analysis uses for the TPU projections."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import cached, timeit_us
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.ucb_score.ref import ucb_score_ref


def _run():
    out = {}
    key = jax.random.PRNGKey(0)

    # flash attention ref (XLA path) — prefill-like tile
    B, H, KV, S, D = 1, 8, 4, 1024, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    f = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us = timeit_us(f, q, k, v)
    flops = 4.0 * B * H * S * S * D
    out["attention_ref_1k"] = {"us_per_call": us, "flops": flops,
                               "gflops_s": flops / us / 1e3}

    # decode attention ref — 32k cache row
    S = 32768
    k2 = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v2 = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    qd = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    fd = jax.jit(lambda q, k, v: decode_attention_ref(q, k, v, S - 1))
    us = timeit_us(fd, qd, k2, v2)
    bytes_moved = 2 * B * KV * S * D * 4
    out["decode_ref_32k"] = {"us_per_call": us, "bytes": bytes_moved,
                             "gb_s": bytes_moved / us / 1e3}

    # ssd ref — mamba2-130m-like block
    B2, L, Hm, P, N = 1, 2048, 24, 64, 128
    ks2 = jax.random.split(key, 5)
    x = jax.random.normal(ks2[0], (B2, L, Hm, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks2[1], (B2, L, Hm)))
    A = -jnp.exp(jax.random.normal(ks2[2], (Hm,)) * 0.5)
    Bm = jax.random.normal(ks2[3], (B2, L, N))
    Cm = jax.random.normal(ks2[4], (B2, L, N))
    fs = jax.jit(lambda *a: ssd_ref(*a)[0])
    us = timeit_us(fs, x, dt, A, Bm, Cm)
    out["ssd_ref_2k"] = {"us_per_call": us}

    # ucb score ref — the paper's serving hot loop at production batch
    T, K, F = 1024, 11, 129
    g = jax.random.normal(ks2[0], (T, K, F), jnp.float32)
    ainv = jnp.eye(F)
    mu = jax.random.normal(ks2[1], (T, K))
    fu = jax.jit(lambda g, a, m: ucb_score_ref(g, a, m, 1.0))
    us = timeit_us(fu, g, ainv, mu)
    flops = 2.0 * T * K * F * F
    out["ucb_score_ref_1k"] = {"us_per_call": us, "flops": flops,
                               "gflops_s": flops / us / 1e3,
                               "us_per_request": us / T}
    return out


def run(refresh: bool = False):
    out = cached("kernel_micro", _run, refresh)
    rows = [("bench_kernels/name", "us_per_call", "derived")]
    for name, s in out.items():
        derived = s.get("gflops_s") or s.get("gb_s") or ""
        rows.append((name, round(s["us_per_call"], 1),
                     round(derived, 2) if derived else ""))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit_csv
    from repro.common.compilation import enable_compile_cache

    enable_compile_cache()
    emit_csv(run())
