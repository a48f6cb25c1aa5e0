"""Named device scopes of the policy stages (``repro.common.tracing``):
the compiled serving programs and the protocol scan carry the scope
names in their operations' op_name metadata, which is what a device
trace shows for each operation."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.tracing import SCOPES, SPANS, span
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.serving import policy_router as pr
from repro.sim import DeviceReplayEnv, make_policy
from repro.sim.engine import _cum_valid, _policy_scan, _tables


@pytest.fixture(scope="module")
def router():
    henv = RouterBenchSim(seed=0, n_samples=120, n_slices=3)
    env = DeviceReplayEnv.from_host(henv)
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=env.K,
                           d_text=16, d_hidden=16, d_last=8)
    pol, hyp = make_policy("neuralucb", env, cfg, ucb_backend="jnp")
    return env, pr.DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                      slice_width=16, capacity_slices=4,
                                      batch_size=8, train_chunks=1)


def _scopes_in(compiled) -> set:
    names = "|".join(re.escape(s) for s in SCOPES)
    return set(re.findall(rf'op_name="[^"]*?({names})[/"]',
                          compiled.as_text()))


def _programs(env, r):
    key = jax.random.PRNGKey(1)
    host = jnp.zeros(r.S + 1, jnp.int32)    # the ids, then t
    st = dict(fcfg=r.fcfg, train_chunks=r.train_chunks,
              batch_size=r.batch_size)
    cum0 = jnp.zeros(r.T + 1, jnp.int32)
    return {
        "decide": (pr._srv_decide.lower(
            r.policy, r.state, key, r.tables, r.hyp, host, None, **st),
            {"router.decide"}),
        "train": (pr._srv_train.lower(
            r.policy, r.state, key, r.tables, r.hyp, r._env_idx, cum0,
            jnp.int32(0), **st), {"router.train_sgd", "router.rebuild"}),
        "scan": (_policy_scan.lower(
            _tables(env), env.slice_xs(), env.idx, _cum_valid(env), key,
            r.hyp, r.policy, None, 0, r.fcfg, 1, 8, None, False),
            {s for s in SCOPES if s.startswith("router.")}),
    }


@pytest.mark.parametrize("program", ["decide", "train", "scan"])
def test_compiled_programs_carry_the_stage_scopes(router, program):
    env, r = router
    lowered, want = _programs(env, r)[program]
    assert _scopes_in(lowered.compile()) == want


def test_update_program_carries_its_scope(router):
    env, r = router
    dec = r.decide(sample_idx=np.arange(r.S))
    n = r.S
    z = np.zeros(n, np.int32)
    host = jnp.asarray(pr._pack_update(0, z, z, np.zeros(n), np.ones(n),
                                       np.arange(n, dtype=np.int32)))
    lowered = pr._srv_update.lower(
        r.policy, r.state, r._env_idx, r.tables, r.hyp, host, dec["aux"],
        fcfg=r.fcfg, train_chunks=r.train_chunks, batch_size=r.batch_size)
    assert _scopes_in(lowered.compile()) == {"router.update"}


def test_span_helper_runs_without_a_trace():
    # no profiler running: a span is a plain context manager
    with span("engine.pump"):
        with span("engine.decide", gid=1, rows=2, wait_us=0.5):
            pass
    assert all(name.startswith(("engine.", "router.", "arm."))
               for name in SPANS)
    assert set(SCOPES) == {"router.decide", "router.update",
                           "router.train_sgd", "router.rebuild",
                           "arm.mamba", "arm.attention", "arm.moe",
                           "arm.head"}


def test_decode_program_carries_the_arm_scopes():
    """The hybrid arm's decode step: every mixer kind, the expert layer
    and the head under their scopes."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as MODEL
    from repro.serving.engine import serve_step
    cfg = dataclasses.replace(get_config("granite_4_0_h_small").reduced(),
                              dtype="float32")
    params = jax.eval_shape(lambda: MODEL.init_params(
        jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: MODEL.init_cache(cfg, 2, 8))
    lowered = jax.jit(serve_step, static_argnums=1).lower(
        params, cfg, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))
    assert _scopes_in(lowered.compile()) == {
        "arm.mamba", "arm.attention", "arm.moe", "arm.head"}
