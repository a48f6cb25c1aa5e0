"""Production mesh builders (deliverable (e) step 1).

Target: TPU v5e pods; 256 chips per pod in a 16x16 (data, model) layout,
and 2 pods = 512 chips with a leading "pod" axis (pure data parallelism
across pods — ICI within a pod, DCN across pods).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, which
    # with_sharding_constraint (distributed.api.constrain) refuses; the
    # sharding rules here annotate activations, so the axes stay Auto
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over the actually-available local devices (tests/examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return _auto_mesh((n // mp, mp), ("data", "model"))


def make_sweep_mesh(n_grid: int = 1, n_seeds: int = 1,
                    n_devices=None, *, span: str = "local"):
    """("grid", "seed") mesh for the protocol-engine lane sweeps
    (DESIGN.md §14.3, §15.3).

    The flattened (grid x seed) lane axis is sharded over BOTH axes —
    ``P(("grid", "seed"))`` — so the factorization only steers locality:
    the grid axis takes the largest device factor that divides the
    caller's hyper-grid size (lanes of one grid point then land on one
    grid row of devices, seed-major), and the seed axis absorbs the
    rest. The policy axis of the zoo sweep stays a static program axis
    (heterogeneous state pytrees can't share one mesh dim); every
    policy's lane tree is laid out over this same mesh. Degenerates to a
    1x1 mesh on a single device (CPU CI), so callers need no gating.

    ``span`` picks the device pool: ``"local"`` (default) spans this
    process's devices — the EXECUTION mesh; ``"global"`` spans every
    ``jax.distributed`` process's devices in process order — the
    TOPOLOGY mesh multi-host sweeps describe their layout with
    (`distributed.api.run_sweep_multihost` slices the grid per process
    and executes each slice on the local mesh, since sweep lanes are
    fully independent)."""
    if span == "local":
        devs = jax.local_devices()
    elif span == "global":
        devs = list(jax.devices())
    else:
        raise ValueError(f"make_sweep_mesh: unknown span {span!r} "
                         f"(use 'local' or 'global')")
    nd = len(devs) if n_devices is None else max(
        1, min(int(n_devices), len(devs)))
    g = math.gcd(nd, max(1, int(n_grid)))
    return jax.sharding.Mesh(
        np.asarray(devs[:nd]).reshape(g, nd // g), ("grid", "seed"))
