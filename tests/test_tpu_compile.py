"""The main-path kernels compile for a TPU v5e at real sizes.

Interpret-mode parity tests (tests/test_nucb_kernels.py,
tests/test_nucb_update.py) run the kernel bodies on CPU; they cannot see
what only the TPU compiler refuses (layouts Mosaic cannot form, tiling,
VMEM). These tests compile — never run — each kernel for one chip of a
*described* ``v5e:2x2`` topology, at the batch and row counts the
serving router and the protocol sweep produce, and assert that the
compiled program holds the kernel (``tpu_custom_call``). The β sweep's
whole program is compiled too, its lanes vmapped on one chip and
sharded over four: a kernel under ``vmap`` tiles differently, and the
compiler cannot partition a kernel across chips by itself.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and xdist workers all
import this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import utilitynet as UN
from repro.kernels.ainv_rebuild import ainv_rebuild
from repro.kernels.nucb_decide import nucb_decide
from repro.kernels.nucb_update import nucb_update
from repro.kernels.ucb_score import ucb_score

CFG = UN.UtilityNetConfig()            # paper widths: F = 129, K = 11


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The sweep's ("grid", "seed") mesh over the four described chips,
    laid out as ``launch.mesh.make_sweep_mesh`` lays out 4 beta points."""
    return Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("grid", "seed"))


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding)
        if hasattr(x, "dtype") else x, tree)


def _compiled_text(fn, sharding, *args) -> str:
    return jax.jit(fn).lower(*_shapes(args, sharding)).compile().as_text()


@pytest.mark.parametrize("B", [512, 4096])
def test_nucb_decide_compiles(one_chip, B):
    params = jax.eval_shape(lambda k: UN.init_utilitynet(k, CFG),
                            jax.random.PRNGKey(0))
    F = CFG.ucb_feature_dim
    text = _compiled_text(
        lambda p, e, f, d, A: nucb_decide(p, CFG, e, f, d, A,
                                          jnp.float32(1.0),
                                          jnp.float32(0.5),
                                          interpret=False),
        one_chip, params,
        jax.ShapeDtypeStruct((B, CFG.emb_dim), jnp.float32),
        jax.ShapeDtypeStruct((B, CFG.feat_dim), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((F, F), jnp.float32))
    assert "tpu_custom_call" in text


def test_nucb_update_compiles(one_chip):
    F = CFG.ucb_feature_dim
    text = _compiled_text(
        lambda A, g: nucb_update(A, g, interpret=False), one_chip,
        jax.ShapeDtypeStruct((F, F), jnp.float32),
        jax.ShapeDtypeStruct((4096, F), jnp.float32))
    assert "tpu_custom_call" in text


def test_ainv_rebuild_compiles(one_chip):
    F = CFG.ucb_feature_dim
    text = _compiled_text(
        lambda g, w: ainv_rebuild(g, 1.0, weights=w, interpret=False),
        one_chip,
        jax.ShapeDtypeStruct((8192, F), jnp.float32),
        jax.ShapeDtypeStruct((8192,), jnp.float32))
    assert "tpu_custom_call" in text


def test_ucb_score_compiles(one_chip):
    """NeuralTS's bonus: all (request, arm) pairs of a 512-request batch."""
    F = CFG.ucb_feature_dim
    text = _compiled_text(
        lambda g, A, mu: ucb_score(g, A, mu, 1.0, interpret=False),
        one_chip,
        jax.ShapeDtypeStruct((512, CFG.num_actions, F), jnp.float32),
        jax.ShapeDtypeStruct((F, F), jnp.float32),
        jax.ShapeDtypeStruct((512, CFG.num_actions), jnp.float32))
    assert "tpu_custom_call" in text


def test_serving_router_decide_compiles(one_chip, monkeypatch):
    """The serving router's jitted decide program (``_srv_decide``) at
    decide_batch 512, neuralucb on the Pallas backend: the platform gate
    is steered to "TPU" here, since this process runs on CPU."""
    from repro.data.routerbench import RouterBenchSim
    from repro.kernels import backend as KB
    from repro.serving.policy_router import _srv_decide, _srv_init
    from repro.sim import DeviceReplayEnv, make_policy
    from repro.sim.engine import _tables

    env = DeviceReplayEnv.from_host(
        RouterBenchSim(seed=0, n_samples=600, n_slices=2))
    cfg = UN.UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K)
    pol, hyp = make_policy("neuralucb", env, cfg, ucb_backend="pallas")
    S = 512
    state, key, tables = _srv_init(pol, jax.random.PRNGKey(0), _tables(env),
                                   hyp, jnp.zeros((4, S), jnp.int32))
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(KB, "on_tpu", lambda: True)
    # the call's one int32 input: the ids padded to S, then the step t
    args = _shapes((state, key, tables, hyp,
                    jnp.zeros((S + 1,), jnp.int32)), one_chip)
    text = _srv_decide.lower(pol, *args, None).compile().as_text()
    assert "tpu_custom_call" in text


def _beta_sweep_text(sharding, mesh=None) -> str:
    """``fig2_beta_sweep`` (4 beta x 2 seeds = 8 lanes, Pallas backend)
    lowered as ``sim.engine.run_policy_sweep`` lowers it, lane inputs
    laid out by ``sharding`` (the lane axis of a 4-chip mesh, or one
    chip)."""
    from repro.distributed.sharding import pad_sweep_lanes, sweep_lane_layout
    from repro.experiments import compile_spec, make_preset
    from repro.sim import engine as E

    spec = make_preset("fig2_beta_sweep", {
        "ucb_backend": "pallas", "seeds": [0, 1], "data.n_samples": 600,
        "data.n_slices": 2, "train.train_steps": 16})
    plan = compile_spec(spec)
    ((pol, grid),) = plan.calls[0].policies.values()
    env = plan.env
    G, ns = E._grid_size(grid), len(spec.seeds)
    keys = jnp.tile(jnp.stack([jax.random.PRNGKey(s) for s in spec.seeds]),
                    (G, 1))
    lanes = pad_sweep_lanes((keys, E._flatten_lanes(grid, G, ns)),
                            sweep_lane_layout(G * ns, mesh).pad)
    rep = sharding if mesh is None else NamedSharding(mesh, P())
    keys, hyp = _shapes(lanes, sharding)
    tabs = _shapes((E._tables(env), env.slice_xs(), env.idx,
                    E._cum_valid(env)), rep)
    chunks = -(-int(plan.train_steps) // E.TRAIN_CHUNK)
    return E._policy_zoo_scan.lower(
        *tabs, (keys,), (hyp,), (pol,), None, 0, E.VANILLA_FORGETTING,
        chunks, spec.train.batch_size, mesh=mesh).compile().as_text()


def test_beta_sweep_lanes_compile(one_chip, monkeypatch):
    """Per-lane beta reaches the decide kernel's scalar operands, so
    every kernel runs batched over lanes: its blocks must still tile."""
    from repro.kernels import backend as KB
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(KB, "on_tpu", lambda: True)
    assert "tpu_custom_call" in _beta_sweep_text(one_chip)


def test_beta_sweep_sharded_over_four_chips_compiles(four_chips,
                                                     monkeypatch):
    """The 8 lanes over 4 chips: each chip scans its own lanes, so the
    program holds the kernels and no collective."""
    from repro.kernels import backend as KB
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(KB, "on_tpu", lambda: True)
    text = _beta_sweep_text(
        NamedSharding(four_chips, P(("grid", "seed"))), four_chips)
    assert "tpu_custom_call" in text
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
