"""JAX compilation plumbing shared by the entry points.

:func:`enable_compile_cache` turns on JAX's persistent compilation
cache. Every command-line entry point (``chip_smoke.py``,
``scripts/run_paper_experiments.py``, the benchmarks) calls it first in
``main``; nothing calls it on import, and tests never turn the cache
on. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself and
no other is set here. Unset, the cache lives at one fixed path inside
the checkout (``<repo>/.jax_cache``, gitignored): the path is part of
what a later run must find, so it is never derived from a temp name, a
pid or the time.

:class:`CompileCount` counts the XLA compiles that happen while it is
active — the "no compiles inside a measured window" check, and the
compile seconds a cold run spends.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCount:
    """Context manager: ``compiles`` is the number of XLA backend
    compiles while active and ``seconds`` their summed duration (a
    persistent-cache hit is not a backend compile)."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += secs

    def __enter__(self) -> "CompileCount":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
