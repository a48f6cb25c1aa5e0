"""Per-architecture sharding rules (DESIGN.md §7).

Two halves:

* ``activation_rules(cfg, shape, mesh)`` — logical-axis -> mesh-axis map
  consumed by the ``shard()`` constraints inside the model code. Chosen
  per arch so every sharded dim divides the mesh axis (e.g. llama3.2-3b
  has 24 heads, not divisible by 16-way model parallelism, so its TP axis
  is head_dim instead of heads).
* ``param_partition_specs(cfg, params)`` — PartitionSpec pytree for the
  weights: column/row tensor parallelism over "model", FSDP over
  ("pod","data"), expert parallelism over "model" for MoE tables.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig, InputShape

Axis = Union[str, Tuple[str, ...], None]


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _div(n: int, k: int) -> bool:
    return n % k == 0 and n > 0


def activation_rules(cfg: ModelConfig, shape: InputShape, mesh,
                     decode: bool = False) -> Dict[str, Axis]:
    sizes = _mesh_axis_sizes(mesh)
    mp = sizes.get("model", 1)
    baxes = _batch_axes(mesh)
    bsize = int(np.prod([sizes[a] for a in baxes]))

    rules: Dict[str, Axis] = {}
    rules["batch"] = baxes if _div(shape.global_batch, bsize) else None
    rules["seq"] = None
    rules["frames"] = None
    rules["patches"] = None
    rules["vocab"] = "model"  # vocab is padded to a /256 multiple
    rules["ffn"] = "model" if _div(cfg.d_ff, mp) else None
    rules["experts"] = "model" if _div(cfg.num_experts, mp) else None

    hd = cfg.resolved_head_dim
    rules["attn_q_seq"] = None
    if not decode and _div(cfg.num_heads, mp):
        rules["heads"] = "model"
        rules["kv_heads"] = "model" if _div(cfg.num_kv_heads, mp) else None
        rules["head_dim"] = None
    elif not decode:
        # head count does not divide the model axis (llama3.2-3b: 24 heads,
        # gemma3: 8 heads): context-parallel attention — the score tensor
        # is sharded over the QUERY-sequence dim instead of heads, the QKV
        # projections stay TP over head_dim.
        rules["heads"] = None
        rules["kv_heads"] = None
        rules["head_dim"] = "model" if _div(hd, mp) else None
        rules["attn_q_seq"] = "model" if _div(shape.seq_len, mp) else None
    else:
        # decode: single-token queries; TP over head_dim keeps the KV cache
        # sharded without head-divisibility constraints
        rules["heads"] = None
        rules["kv_heads"] = None
        rules["head_dim"] = "model" if _div(hd, mp) else None

    if cfg.ssm_state:
        d_inner = cfg.ssm_expand * cfg.d_model
        nheads = d_inner // cfg.ssm_head_dim
        if _div(nheads, mp):
            rules["ssm_heads"] = "model"
            rules["ssm_pdim"] = None
        else:
            rules["ssm_heads"] = None
            rules["ssm_pdim"] = "model" if _div(cfg.ssm_head_dim, mp) else None
    return rules


# ---------------------------------------------------------------------------
# protocol-engine sweep sharding
# ---------------------------------------------------------------------------


def sweep_lane_sharding(n_items: int):
    """NamedSharding for an ``n_items``-wide sweep lane axis, or None
    when sharding buys nothing (single device, or no device count > 1
    divides the axis). Picks the largest local-device count that divides
    the axis so no grid shape is rejected. Factored out of
    :func:`shard_sweep_axis` so the policy-zoo sweep (DESIGN.md §10) can
    lay out EVERY policy's lane tree with one consistent rule even when
    their grid sizes differ."""
    devs = jax.local_devices()
    nd = len(devs)
    while nd > 1 and n_items % nd:
        nd -= 1
    if nd <= 1:
        return None
    mesh = jax.sharding.Mesh(np.asarray(devs[:nd]), ("sweep",))
    return jax.sharding.NamedSharding(mesh, P("sweep"))


def shard_sweep_axis(tree, n_items: Optional[int] = None):
    """Shard the leading (sweep) axis of every leaf across local devices.

    Legacy path (kept for external callers): when no device count > 1
    divides the axis this silently degrades toward 1 device. The engine's
    sweep runner now pads the lane axis instead — see
    :func:`sweep_lane_layout` / :func:`pad_sweep_lanes` — so every local
    device always carries an equal lane shard. Identity on a single
    device (CPU CI) so callers need no gating.
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree
    n = n_items if n_items is not None else int(leaves[0].shape[0])
    sharding = sweep_lane_sharding(n)
    if sharding is None:
        return tree
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


class SweepLaneLayout(NamedTuple):
    """How a flattened (grid x seed) lane axis maps onto a sweep mesh:
    ``n_lanes`` real lanes + ``pad`` dead lanes = a multiple of the
    ``grid * seed`` device count, so the lane shard per device is always
    equal-sized (no silent degrade to fewer devices). Dead lanes replay
    lane 0 and are sliced off before any result leaves the runner.

    ``hosts`` records how many ``jax.distributed`` processes contribute
    devices to the mesh (1 off-cluster) — host TOPOLOGY only, never
    which host produced the artifact, so manifests from every worker of
    a multi-host sweep and from an equivalent single-host run with the
    same mesh are byte-identical (DESIGN.md §15.3)."""
    n_lanes: int
    pad: int
    grid: int
    seed: int
    hosts: int = 1

    @property
    def total(self) -> int:
        return self.n_lanes + self.pad

    @property
    def n_devices(self) -> int:
        return self.grid * self.seed

    def manifest(self) -> Dict[str, object]:
        """JSON-ready layout record for sweep result manifests."""
        return {"n_lanes": int(self.n_lanes), "pad": int(self.pad),
                "n_devices": int(self.n_devices),
                "mesh": {"grid": int(self.grid), "seed": int(self.seed)},
                "hosts": {"n_hosts": int(self.hosts),
                          "devices_per_host":
                              int(self.n_devices) // int(self.hosts)}}


def sweep_lane_layout(n_lanes: int, mesh=None) -> SweepLaneLayout:
    """Layout for ``n_lanes`` sweep lanes on ``mesh`` (a ("grid","seed")
    mesh from :func:`repro.launch.mesh.make_sweep_mesh`; None = all
    local devices on a 1 x nd seed row). Host topology is read off the
    mesh's device set, so a ``span="global"`` mesh yields a multi-host
    layout and a local mesh always yields ``hosts=1``."""
    if mesh is not None:
        g, s = (int(d) for d in mesh.devices.shape)
        hosts = len({d.process_index for d in mesh.devices.flat})
    else:
        g, s = 1, len(jax.local_devices())
        hosts = 1
    nd = g * s
    return SweepLaneLayout(n_lanes=int(n_lanes), pad=(-int(n_lanes)) % nd,
                           grid=g, seed=s, hosts=max(1, hosts))


def process_lane_slice(n_grid: int, n_seeds: int, n_procs: int,
                       proc: int) -> Tuple[int, int, int, int]:
    """Contiguous work span owned by one process of a multi-host sweep.

    Returns ``(g_start, g_stop, lane_start, lane_stop)``: process ``p``
    of ``h`` owns grid points ``[p*G//h, (p+1)*G//h)`` — whole grid
    points, never split seeds, so every process's slice is a clean
    (g, n_seeds, ...) block — which in the seed-major flattened lane
    axis is lanes ``[g_start*n_seeds, g_stop*n_seeds)``. Spans are
    contiguous, disjoint, cover the grid exactly, and are empty (start
    == stop) for trailing processes when ``n_grid < n_procs``."""
    if not 0 <= proc < n_procs:
        raise ValueError(f"process_lane_slice: proc {proc} outside "
                         f"[0, {n_procs})")
    gs = proc * n_grid // n_procs
    ge = (proc + 1) * n_grid // n_procs
    return gs, ge, gs * n_seeds, ge * n_seeds


def pad_sweep_lanes(tree, pad: int):
    """Append ``pad`` dead lanes to every leaf's leading axis (each a
    broadcast copy of lane 0, so the padded program computes real —
    discarded — work instead of tracing a second shape)."""
    if pad <= 0:
        return tree

    def one(x):
        x = jnp.asarray(x)
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])], axis=0)
    return jax.tree.map(one, tree)


def shard_sweep_lanes(tree, mesh):
    """Shard every leaf's (padded) leading lane axis over both mesh axes
    (``P(("grid", "seed"))``). Identity on a 1-device mesh."""
    if mesh is None or int(np.prod(mesh.devices.shape)) <= 1:
        return tree
    sh = jax.sharding.NamedSharding(mesh, P(("grid", "seed")))
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


# ---------------------------------------------------------------------------
# parameter partition specs
# ---------------------------------------------------------------------------


def param_partition_specs(cfg: ModelConfig, params, mesh) -> Dict:
    """PartitionSpec pytree matching ``params``' structure, keyed on the
    conventional parameter names used across repro.models."""
    sizes = _mesh_axis_sizes(mesh)
    mp = sizes.get("model", 1)
    fsdp = _batch_axes(mesh)
    fsdp_size = int(np.prod([sizes[a] for a in fsdp])) if fsdp else 1

    def spec_for(path: Tuple[str, ...], leaf) -> P:
        name = path[-1]
        shp = leaf.shape
        nlead = _num_stack_dims(path, shp, name)
        lead = (None,) * nlead
        core = shp[nlead:]

        def fs(dim_idx: int) -> Axis:
            return fsdp if fsdp and _div(core[dim_idx], fsdp_size) else None

        def tp(dim_idx: int) -> Axis:
            return "model" if _div(core[dim_idx], mp) else None

        # ---- embeddings / heads -----------------------------------------
        if name == "embed":
            return P(tp(0), None)           # vocab-parallel embedding
        if name == "unembed":
            return P(fs(0), tp(1))          # column-parallel logits
        # ---- attention ----------------------------------------------------
        if name in ("wq", "wk", "wv"):
            return P(*lead, fs(0), tp(1))
        if name == "wo":
            return P(*lead, tp(0), fs(1))
        # ---- dense FFN ------------------------------------------------------
        if name in ("wg", "wu") and len(core) == 2:
            return P(*lead, fs(0), tp(1))
        if name == "wd" and len(core) == 2:
            return P(*lead, tp(0), fs(1))
        # ---- MoE expert tables (E, D, F) / (E, F, D) -----------------------
        if name in ("wg", "wu") and len(core) == 3:
            return P(*lead, tp(0), fs(1), None)
        if name == "wd" and len(core) == 3:
            return P(*lead, tp(0), None, fs(2))
        if name == "router":
            return P(*lead, fs(0), None)
        # ---- mamba ----------------------------------------------------------
        if name == "in_proj":
            return P(*lead, fs(0), tp(1))
        if name == "out_proj":
            return P(*lead, tp(0), fs(1))
        if name in ("conv_w", "conv_b"):
            return P(*lead, *((None,) * len(core)))
        # ---- everything else (norms, gates, A_log, D, dt_bias, scalars) ----
        return P(*lead, *((None,) * len(core)))

    return _map_with_path(spec_for, params)


def _num_stack_dims(path: Tuple[str, ...], shp, name: str) -> int:
    """Count leading layer-stacking dims: any dict level named blocks /
    enc_blocks / dec_blocks / mamba / moe / ffn_dense / self adds one."""
    stacking = {"blocks", "enc_blocks", "dec_blocks"}
    inner_stacking = {"mamba", "moe", "ffn_dense", "self"}
    n = 0
    for p in path[:-1]:
        if p in stacking:
            n += 1
        elif p in inner_stacking:
            n += 1
    # guard against miscount: never exceed ndim - 2 for matrices
    core_nd = 2 if name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd",
                            "in_proj", "out_proj", "router", "w") else None
    if name in ("wg", "wu", "wd") and len(shp) - n == 3:
        core_nd = 3
    if core_nd is not None:
        n = len(shp) - core_nd
    return max(n, 0)


def _map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _map_with_path(fn, v, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


# ---------------------------------------------------------------------------
# input / state specs
# ---------------------------------------------------------------------------


def batch_partition_specs(cfg: ModelConfig, shape: InputShape, mesh,
                          batch_spec_tree) -> Dict:
    baxes = _batch_axes(mesh)
    sizes = _mesh_axis_sizes(mesh)
    bsize = int(np.prod([sizes[a] for a in baxes])) if baxes else 1
    b = baxes if _div(shape.global_batch, bsize) else None

    def spec_for(path, leaf):
        return P(b, *((None,) * (len(leaf.shape) - 1)))

    return _map_with_path(spec_for, batch_spec_tree)


def cache_partition_specs(cfg: ModelConfig, shape: InputShape, mesh,
                          cache_spec_tree) -> Dict:
    """KV/state cache: batch over (pod, data) when divisible; the head_dim
    (attention) / P dim (mamba) over "model"."""
    sizes = _mesh_axis_sizes(mesh)
    mp = sizes.get("model", 1)
    baxes = _batch_axes(mesh)
    bsize = int(np.prod([sizes[a] for a in baxes])) if baxes else 1
    b = baxes if _div(shape.global_batch, bsize) else None
    hd = cfg.resolved_head_dim
    tp_hd = "model" if _div(hd, mp) else None
    tp_p = "model" if _div(cfg.ssm_head_dim, mp) else None

    def spec_for(path, leaf):
        name = path[-1]
        nd = len(leaf.shape)
        if name in ("pos", "offset"):
            return P()
        if name == "start":
            return P(b)
        if name in ("k", "v"):
            # (L?, B, KV, S, hd)
            lead = (None,) * (nd - 4)
            return P(*lead, b, None, None, tp_hd)
        if name == "ssm":
            # (L?, B, H, P, N)
            lead = (None,) * (nd - 4)
            return P(*lead, b, None, tp_p, None)
        if name == "conv":
            lead = (None,) * (nd - 3)
            return P(*lead, b, None, None)
        if name == "image_embed":
            return P(b, None, None)
        return P(*((None,) * nd))

    return _map_with_path(spec_for, cache_spec_tree)
