"""Distribution-layer tests.

Structural checks run in-process on a 1-device mesh; a REAL multi-device
lowering test runs in a subprocess with 8 forced host devices (the same
mechanism the 512-device dry-run uses — conftest keeps this process at 1
device so smoke tests see realistic defaults)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.config import INPUT_SHAPES
from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import (
    activation_rules,
    cache_partition_specs,
    param_partition_specs,
)
from repro.launch import specs as SPECS
from repro.launch.mesh import make_host_mesh


def _tiny_mesh():
    return make_host_mesh()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_structure(arch):
    cfg = get_config(arch)
    mesh = _tiny_mesh()
    ptree = SPECS.param_specs(cfg)
    parts = param_partition_specs(cfg, ptree, mesh)
    flat_p = jax.tree.leaves(ptree)
    flat_s = jax.tree.leaves(parts, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", ["decode_32k"])
def test_cache_specs_structure(arch, shape):
    cfg = get_config(arch)
    mesh = _tiny_mesh()
    cache, _ = SPECS.decode_input_specs(cfg, INPUT_SHAPES[shape])
    parts = cache_partition_specs(cfg, INPUT_SHAPES[shape], mesh, cache)
    flat_c = jax.tree.leaves(cache)
    flat_s = jax.tree.leaves(parts, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_c) == len(flat_s)


def test_activation_rules_divisibility():
    """Every rule maps a dim that divides the mesh axis size (the reason
    llama3.2-3b with 24 heads must NOT use head-parallel TP at 16-way)."""
    import numpy as np

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), dtype=object)

    mesh = FakeMesh()
    cfg = get_config("llama3.2-3b")  # 24 heads: not divisible by 16
    rules = activation_rules(cfg, INPUT_SHAPES["train_4k"], mesh)
    assert rules["heads"] is None
    assert rules["head_dim"] == "model"  # 128 divides 16

    cfg2 = get_config("mistral-large-123b")  # 96 heads: divisible
    rules2 = activation_rules(cfg2, INPUT_SHAPES["train_4k"], mesh)
    assert rules2["heads"] == "model"

    # long_500k batch=1 cannot be data-sharded
    rules3 = activation_rules(get_config("mamba2-130m"),
                              INPUT_SHAPES["long_500k"], mesh, decode=True)
    assert rules3["batch"] is None


SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.common.config import InputShape
from repro.configs import get_config
from repro.distributed import logical_axis_rules
from repro.distributed.sharding import (activation_rules,
    batch_partition_specs, param_partition_specs)
from repro.launch import specs as SPECS
from repro.launch.mesh import make_host_mesh
from repro.training import train_step as TS
import functools, numpy as np

mesh = make_host_mesh(model_parallel=4)
assert dict(mesh.shape) == {"data": 2, "model": 4}, mesh.shape
cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                          dtype="float32", num_experts=4,
                          num_heads=4, num_kv_heads=4)
shape = InputShape("t", 32, 4, "train")
rules = activation_rules(cfg, shape, mesh)
ptree = SPECS.param_specs(cfg)
pparts = param_partition_specs(cfg, ptree, mesh)
named = jax.tree.map(lambda s: NamedSharding(mesh, s), pparts,
                     is_leaf=lambda x: isinstance(x, P))
batch = SPECS.train_input_specs(cfg, shape)
bparts = batch_partition_specs(cfg, shape, mesh, batch)
bnamed = jax.tree.map(lambda s: NamedSharding(mesh, s), bparts,
                      is_leaf=lambda x: isinstance(x, P))
state = jax.eval_shape(lambda: TS.make_train_state(jax.random.PRNGKey(0), cfg))
sparts = {"params": named, "opt": {"mu": named, "nu": named,
          "count": NamedSharding(mesh, P())}, "step": NamedSharding(mesh, P())}
fn = functools.partial(TS.train_step, cfg=cfg)
with logical_axis_rules(rules, mesh):
    lowered = jax.jit(fn, in_shardings=(sparts, bnamed)).lower(state, batch)
    compiled = lowered.compile()
# ALSO execute for real on the 8 fake devices: numerics under SPMD
state_r = jax.jit(lambda k: TS.make_train_state(k, cfg),
                  out_shardings=sparts)(jax.random.PRNGKey(0))
rngb = np.random.default_rng(0)
real_batch = {"tokens": jnp.asarray(rngb.integers(0, cfg.vocab_size, (4, 32))),
              "labels": jnp.asarray(rngb.integers(0, cfg.vocab_size, (4, 32)))}
with logical_axis_rules(rules, mesh):
    new_state, m = jax.jit(fn, in_shardings=(sparts, bnamed))(state_r, real_batch)
loss = float(m["loss"])
assert loss == loss and loss < 20, loss
print("SUBPROC_OK", loss)
"""


def test_spmd_lowering_and_execution_8dev():
    """Real SPMD check: an MoE train step lowers AND executes on a forced
    8-device host mesh with the production sharding rules."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SUBPROC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SUBPROC_OK" in out.stdout


def test_shard_sweep_axis_single_device_identity():
    """On a single device (this process — see conftest) the sweep-shard
    helper must be a no-op so engine callers need no gating."""
    from repro.distributed.sharding import shard_sweep_axis

    x = jax.numpy.arange(8.0)
    tree = shard_sweep_axis({"a": x})
    assert tree["a"] is x


SWEEP_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.data.routerbench import RouterBenchSim
from repro.distributed.sharding import shard_sweep_axis
from repro.sim import DeviceReplayEnv, random_policy, run_baseline_sweep
assert len(jax.local_devices()) == 2
keys = jnp.stack([jax.random.PRNGKey(s) for s in range(4)])
sk = shard_sweep_axis(keys)
assert len(sk.sharding.device_set) == 2, sk.sharding
odd = shard_sweep_axis(jnp.arange(3.0))        # 3 lanes on 2 devices
assert len(odd.sharding.device_set) == 1       # legacy path falls back
# the engine's sweep runner now PADS instead of degrading: 3 lanes on 2
# devices get one dead lane and still shard 2-ways
from repro.distributed.sharding import pad_sweep_lanes, sweep_lane_layout
from repro.launch.mesh import make_sweep_mesh
mesh = make_sweep_mesh(1, 3)
lay = sweep_lane_layout(3, mesh)
assert (lay.pad, lay.n_devices, lay.total) == (1, 2, 4), lay
padded = pad_sweep_lanes(jnp.arange(1.0, 4.0), lay.pad)
assert padded.shape == (4,) and float(padded[3]) == 1.0  # lane-0 copy
henv = RouterBenchSim(seed=0, n_samples=600, n_slices=3)
denv = DeviceReplayEnv.from_host(henv)
out = run_baseline_sweep(denv, random_policy(denv.K), seeds=range(4))
assert out["avg_reward"].shape == (1, 4, 3)     # annotated (G, seeds, T)
assert out["layout"] == {"n_lanes": 4, "pad": 0, "n_devices": 2,
                         "mesh": {"grid": 1, "seed": 2},
                         "hosts": {"n_hosts": 1, "devices_per_host": 2}}
# non-dividing lane count: dead lane dropped from results, layout says so
out3 = run_baseline_sweep(denv, random_policy(denv.K), seeds=range(3))
assert out3["avg_reward"].shape == (1, 3, 3)
assert out3["layout"]["pad"] == 1 and out3["layout"]["n_devices"] == 2
# the policy AXIS shares the same lane sharding: a 2-policy zoo sweep
# executes as one dispatch with each policy's 4 lanes split 2-ways
from repro.sim import make_policy, run_policy_sweep
zoo = {n: make_policy(n, denv, None) for n in ("greedy", "dyn_min_cost")}
sw = run_policy_sweep(denv, zoo, seeds=range(4))
assert set(sw) == {"greedy", "dyn_min_cost"}
for d in sw.values():
    assert d["avg_reward"].shape == (1, 4, 3)
# the lanes sharded over 2 devices are bit-identical to the same sweep
# on one device
import numpy as np
one = run_policy_sweep(denv, zoo, seeds=range(4), n_devices=1)
for n, d in one.items():
    assert d["layout"]["n_devices"] == 1
    for k in ("avg_reward", "avg_cost", "action_hist"):
        np.testing.assert_array_equal(d[k], sw[n][k], err_msg=k)
print("SWEEP_SUBPROC_OK")
"""


def test_process_lane_slice_partition():
    """Per-process grid spans partition [0, G) contiguously, disjointly
    and completely for any (G, hosts) shape, with seed-major lane spans
    scaled by n_seeds; out-of-range process indices raise."""
    from repro.distributed.sharding import process_lane_slice

    for G, h, S in [(4, 2, 3), (5, 2, 1), (1, 4, 2), (7, 3, 2)]:
        spans = [process_lane_slice(G, S, h, p) for p in range(h)]
        assert spans[0][0] == 0 and spans[-1][1] == G
        for (gs, ge, ls, le), nxt in zip(spans, spans[1:]):
            assert ge == nxt[0]                 # contiguous + disjoint
        for gs, ge, ls, le in spans:
            assert (ls, le) == (gs * S, ge * S)
    with pytest.raises(ValueError):
        process_lane_slice(4, 1, 2, 2)


def test_run_sweep_multihost_single_process_degenerate():
    """Single-process `run_sweep_multihost` == plain `run_policy_sweep`
    on the metrics, plus the multi-host annotations (full grid span,
    1-host topology manifest)."""
    import numpy as np

    from repro.data.routerbench import RouterBenchSim
    from repro.distributed import run_sweep_multihost
    from repro.sim import DeviceReplayEnv, make_policy, run_policy_sweep
    from repro.sim.policies import LinUCBHypers

    env = DeviceReplayEnv.from_host(
        RouterBenchSim(seed=0, n_samples=300, n_slices=3))
    pol, _ = make_policy("linucb", env)
    hyp = LinUCBHypers(alpha=jax.numpy.asarray([0.5, 1.5]),
                       ridge=jax.numpy.ones(2))
    zoo = {"linucb": (pol, hyp)}
    ref = run_policy_sweep(env, zoo, seeds=range(2))["linucb"]
    got = run_sweep_multihost(env, zoo, seeds=range(2))["linucb"]
    for k in ("avg_reward", "avg_cost", "action_hist"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k]), err_msg=k)
    assert got["grid_span"] == [0, 2] and got["n_grid_total"] == 2
    assert got["lane_span"] == [0, 4]
    assert got["layout"]["hosts"]["n_hosts"] == 1


def test_sweep_sharding_multi_device_subprocess():
    """The protocol sweep's lane axis really shards across forced host
    devices and the sharded sweep executes (DESIGN.md §8.4)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SWEEP_SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SWEEP_SUBPROC_OK" in out.stdout
