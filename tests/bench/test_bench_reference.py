"""The benchmark's plain references against the program's own paths, at
small sizes on the CPU (where both compute in float32)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import router as R  # noqa: E402
from repro.core import neuralucb as NU  # noqa: E402
from repro.core import utilitynet as UN  # noqa: E402
from repro.sim.policies import _decide_ucb  # noqa: E402


_init = jax.jit(UN.init_utilitynet, static_argnums=1)
_program_decide = jax.jit(_decide_ucb, static_argnums=(5, 6))
_program_apply = jax.jit(UN.utilitynet_apply)
_program_rebuild = jax.jit(lambda h, w: NU.rebuild_ainv(NU.augment(h), 1.0,
                                                        weights=w))


def _world(B, K, seed):
    cfg = UN.UtilityNetConfig(emb_dim=48, num_actions=K, num_domains=7,
                              d_text=32, d_feat=16, d_hidden=32, d_last=16,
                              d_gate=8)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    params = _init(ks[0], cfg)
    F = cfg.ucb_feature_dim
    Lm = jax.random.normal(ks[1], (F, F)) * 0.1
    ainv = Lm @ Lm.T + jnp.eye(F) * 0.5
    x_emb = np.asarray(jax.random.normal(ks[2], (B, cfg.emb_dim)))
    x_feat = np.asarray(jax.random.normal(ks[3], (B, cfg.feat_dim)))
    domain = np.asarray(jax.random.randint(ks[4], (B,), 0,
                                           cfg.num_domains))
    return cfg, params, ainv, x_emb, x_feat, domain


@pytest.mark.parametrize("B,K,seed", [(16, 3, 0), (64, 11, 1), (33, 5, 2)])
def test_decide_matches_program(B, K, seed):
    cfg, params, ainv, x_emb, x_feat, domain = _world(B, K, seed)
    batch = {"x_emb": jnp.asarray(x_emb), "x_feat": jnp.asarray(x_feat),
             "domain": jnp.asarray(domain)}
    a, _, g, mu_safe, _ = _program_decide(params, ainv, batch, 1.0, 0.5,
                                          cfg, "jnp")
    ref = R.decide(params, ainv, x_emb, x_feat, domain, 1.0, 0.5)
    got = R.decide_numbers(ref, 0.5, 2e-3, np.asarray(a), np.asarray(g),
                           np.asarray(mu_safe))
    assert got["decide_gap"] <= 1e-5
    assert got["feature_err"] <= 1e-5
    assert got["mu_safe_err"] <= 1e-5
    # the reference's own choice scores no gap against itself
    a_r, g_r, mu_r = R.choose(ref, 0.5)
    assert R.decide_numbers(ref, 0.5, 0.0, a_r, g_r, mu_r) == {
        "decide_gap": 0.0, "feature_err": 0.0, "mu_safe_err": 0.0}


@pytest.mark.parametrize("n", [1, 40, 300])
def test_woodbury_matches_program(n):
    cfg, params, ainv, x_emb, *_ = _world(4, 3, n)
    gs = NU.augment(jax.random.normal(jax.random.PRNGKey(n),
                                      (n, cfg.d_last)))
    got = NU.woodbury_update(ainv, gs)
    assert R.matrix_err(got, R.woodbury(ainv, gs)) <= 1e-5


@pytest.mark.parametrize("rows,block", [(50, 16384), (200, 64)])
def test_rebuild_matches_program(rows, block):
    cfg, params, _, x_emb, x_feat, domain = _world(rows, 4, rows)
    action = np.arange(rows) % 4
    w = (np.arange(rows) % 3 != 0).astype(np.float32)
    _, h, _ = _program_apply(params, jnp.asarray(x_emb),
                             jnp.asarray(x_feat), jnp.asarray(domain),
                             jnp.asarray(action))
    got = _program_rebuild(h, jnp.asarray(w))
    want = R.rebuild(params, x_emb, x_feat, domain, action, w, 1.0,
                     block=block)
    assert R.matrix_err(got, want) <= 1e-5


def test_bfloat16_control_departs():
    """The control (the reference in bfloat16) reads well above what the
    float32 program reads, on every leg."""
    cfg, params, ainv, x_emb, x_feat, domain = _world(256, 11, 7)
    args = (params, ainv, x_emb, x_feat, domain, 1.0, 0.5)
    ref = R.decide(*args)
    a, g, mu = R.choose(R.decide(*args, prec=R.BF16), 0.5)
    got = R.decide_numbers(ref, 0.5, 2e-3, a, g, mu)
    assert got["feature_err"] > 1e-3 and got["mu_safe_err"] > 1e-3
    gs = NU.augment(jax.random.normal(jax.random.PRNGKey(3), (300, 16)))
    assert R.matrix_err(R.woodbury(ainv, gs, R.BF16),
                        R.woodbury(ainv, gs)) > 1e-3


def _ring(seed, T=4, S=32, K=5, n=200):
    rng = np.random.default_rng(seed)
    counts = np.array([S, 20, S, 7])[:T]
    w = (np.arange(S)[None] < counts[:, None]).astype(np.float32)
    bufs = {"action": rng.integers(0, K, (T, S)).astype(np.int32),
            "reward": rng.random((T, S)).astype(np.float32),
            "gate_label": (rng.random((T, S)) < 0.3).astype(np.float32),
            "w": w, "gate_w": w * (rng.random((T, S)) < 0.8)}
    env_idx = rng.integers(0, n, (T, S)).astype(np.int32)
    cum0 = np.concatenate([[0], np.cumsum(counts)])
    return bufs, env_idx, cum0


@pytest.mark.parametrize("seed,chunks", [(0, 1), (1, 2)])
def test_train_matches_program(seed, chunks):
    """The reference draws the program's minibatch rows from its key and
    trains to the same weights and AdamW state (float32 on the CPU),
    with the training settings the configuration file states."""
    from types import SimpleNamespace

    from bench.common import load_json
    from repro.serving.policy_router import _ctx
    from repro.sim.policies import TRAIN_CHUNK, _neural_train
    from repro.training.optim import adamw_init

    hp = load_json(ROOT, "bench", "configs", "nucb_rb11.json")["train"]
    hp = {k: v for k, v in hp.items() if k != "optimizer"}
    cfg, params, _, x_emb, x_feat, domain = _world(200, 5, seed)
    bufs, env_idx, cum0 = _ring(seed)
    t, bs = 3, 16
    tables = {"x_emb": jnp.asarray(x_emb), "x_feat": jnp.asarray(x_feat),
              "domain": jnp.asarray(domain)}
    ctx = _ctx(tables, SimpleNamespace(lr=hp["lr"]),
               env_idx=jnp.asarray(env_idx),
               cum0=jnp.asarray(cum0, jnp.int32), t=jnp.int32(t),
               train_chunks=chunks, batch_size=bs)
    train = _neural_train(cfg)
    state = {"params": params, "opt": adamw_init(params),
             "bufs": {k: jnp.asarray(v) for k, v in bufs.items()}}
    # one slice first, so the optimizer state carries history
    state, key = train(state, jax.random.PRNGKey(seed + 10), ctx)
    before = jax.tree.map(np.asarray, {"params": state["params"],
                                       "opt": state["opt"]})
    got, _ = train(state, key, ctx)
    rows, cols = R.train_rows(np.asarray(key), cum0, t, chunks,
                              TRAIN_CHUNK, bs)
    assert rows.shape == (chunks * TRAIN_CHUNK, bs)
    assert (cols < (cum0[1:] - cum0[:-1])[rows]).all()
    batches = [{"x_emb": x_emb[env_idx[r, c]], "x_feat": x_feat[env_idx[r, c]],
                "domain": domain[env_idx[r, c]],
                **{k: bufs[k][r, c] for k in bufs}}
               for r, c in zip(rows, cols)]
    p_ref, opt_ref, norms = R.train(before["params"], before["opt"],
                                    batches, hp)
    assert int(opt_ref["count"]) == int(got["opt"]["count"])
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(got["params"])):
        np.testing.assert_allclose(a, np.asarray(b), atol=2e-6, rtol=1e-4)
    nums = R.train_numbers(before, {"params": got["params"],
                                    "opt": got["opt"]},
                           {"params": p_ref, "opt": opt_ref}, norms,
                           hp["b2"], len(batches))
    assert nums["train_step_err"] <= 1e-4
    assert nums["train_grad_err"] <= 1e-4
    # a slice trained on other rows reads far off
    other = R.train(before["params"], before["opt"], batches[1:] +
                    batches[:1], dict(hp, lr=2 * hp["lr"]))
    far = R.train_numbers(before, {"params": other[0], "opt": other[1]},
                          {"params": p_ref, "opt": opt_ref}, norms,
                          hp["b2"], len(batches))
    assert far["train_step_err"] > 0.5
