"""Golden regression: fixed-seed `run_neuralucb_device` on a tiny env
against a committed metrics snapshot (tests/golden/neuralucb_tiny.json),
so engine refactors can't silently shift the Figures 2-4 numbers — plus
a baselines snapshot (tests/golden/baselines_tiny.json, generated from
the pre-unification `_baseline_scan`) that pins the unified
`BanditPolicy` runner to the exact trajectories of the scan it replaced
(stationary AND scenario paths, deterministic AND PRNG policies).

The run executes in a subprocess with PYTHONHASHSEED pinned: the whole
pipeline (dataset, encoder, protocol scan) is then a deterministic
function of (platform, jax version) — see the encoders crc32 fix — and
per-slice curves must match the snapshot tightly (2e-4). The snapshots
record the jax version they were made under; a jax upgrade that moves
the PRNG stream or XLA codegen is a reason to regenerate them.

Regenerate (after an INTENTIONAL semantics change only):

    PYTHONPATH=src python tests/test_golden.py --regen
"""
import json
import os
import subprocess
import sys

import numpy as np

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "neuralucb_tiny.json")
GOLDEN_BASE = os.path.join(os.path.dirname(__file__), "golden",
                           "baselines_tiny.json")
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_RUN_SRC = """
import json
import jax
import numpy as np
from repro.core.protocol import summarize
from repro.core.utilitynet import UtilityNetConfig
from repro.data.routerbench import RouterBenchSim
from repro.sim import DeviceReplayEnv, run_neuralucb_device

henv = RouterBenchSim(seed=0, n_samples=600, n_slices=3)
denv = DeviceReplayEnv.from_host(henv)
cfg = UtilityNetConfig(emb_dim=henv.x_emb.shape[1], num_actions=henv.K)
res = run_neuralucb_device(denv, cfg, seed=0, train_steps=32,
                           batch_size=64, ucb_backend="jnp")
summ = summarize({"neuralucb": res})["neuralucb"]
out = {
    "jax": jax.__version__,
    "config": {"n_samples": 600, "n_slices": 3, "seed": 0,
               "train_steps": 32, "batch_size": 64,
               "ucb_backend": "jnp"},
    "avg_reward": res["avg_reward"],
    "cum_reward": res["cum_reward"],
    "avg_cost": res["avg_cost"],
    "avg_quality": res["avg_quality"],
    "oracle_avg_reward": res["oracle_avg_reward"],
    "action_hist": np.asarray(res["action_hist"]).tolist(),
    "summary": summ,
}
print("GOLDEN=" + json.dumps(out))
"""


_BASE_SRC = """
import json
import jax
import numpy as np
from repro.data.routerbench import RouterBenchSim
from repro.sim import (DeviceReplayEnv, fixed_policy, greedy_policy,
                       random_policy, run_baseline_device)

henv = RouterBenchSim(seed=0, n_samples=600, n_slices=3)
denv = DeviceReplayEnv.from_host(henv)
out = {"jax": jax.__version__,
       "config": {"n_samples": 600, "n_slices": 3, "seed": 0}}
runs = {
    "greedy": (greedy_policy(denv.K), None),
    "min-cost": (fixed_policy(denv.min_cost_action(), "min-cost"), None),
    "random": (random_policy(denv.K), None),
    "greedy@price_shock": (greedy_policy(denv.K), "price_shock"),
    "random@arm_arrival": (random_policy(denv.K), "arm_arrival"),
}
for name, (pol, scen) in runs.items():
    res = run_baseline_device(denv, pol, seed=0, scenario=scen)
    rec = {k: [float(v) for v in res[k]]
           for k in ("avg_reward", "avg_cost", "avg_quality",
                     "oracle_avg_reward")}
    rec["action_hist"] = np.asarray(res["action_hist"]).tolist()
    out[name] = rec
print("BASEGOLDEN=" + json.dumps(out))
"""


def _run_subprocess(src: str, tag: str) -> dict:
    env = dict(os.environ, PYTHONHASHSEED="0", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith(tag + "=")][-1]
    return json.loads(line.split("=", 1)[1])


def _run_golden() -> dict:
    return _run_subprocess(_RUN_SRC, "GOLDEN")


def _run_base_golden() -> dict:
    return _run_subprocess(_BASE_SRC, "BASEGOLDEN")


def test_baselines_match_pre_unification_scan_snapshot():
    """The unified BanditPolicy runner must replay the committed
    trajectories of the pre-refactor `_baseline_scan` exactly — the
    deterministic policies bit-wise, the PRNG policy through the
    preserved one-split-per-slice key discipline, and the scenario path
    (effective tables + availability fallback) included."""
    with open(GOLDEN_BASE) as f:
        golden = json.load(f)
    got = _run_base_golden()
    assert got["config"] == golden["config"]
    names = [k for k in golden if k not in ("jax", "config")]
    for name in names:
        g0, g1 = golden[name], got[name]
        for key in ("avg_reward", "avg_cost", "avg_quality",
                    "oracle_avg_reward"):
            np.testing.assert_allclose(
                g1[key], g0[key], rtol=2e-5, atol=1e-6,
                err_msg=f"{name}/{key} drifted from tests/golden/"
                        f"baselines_tiny.json")
        np.testing.assert_array_equal(
            np.asarray(g1["action_hist"]),
            np.asarray(g0["action_hist"]), err_msg=name)


def test_neuralucb_tiny_matches_golden_snapshot():
    with open(GOLDEN) as f:
        golden = json.load(f)
    got = _run_golden()
    assert got["config"] == golden["config"]
    curves = ("avg_reward", "cum_reward", "avg_cost", "avg_quality",
              "oracle_avg_reward")
    for key in curves:
        np.testing.assert_allclose(
            got[key], golden[key], rtol=2e-4, atol=2e-4,
            err_msg=f"{key} drifted from tests/golden/"
                    f"neuralucb_tiny.json — if the change is an "
                    f"INTENDED semantics change, regenerate via "
                    f"`python tests/test_golden.py --regen`")
    # decisions: histograms may differ by a handful of argmax flips
    h0 = np.asarray(golden["action_hist"], np.float64)
    h1 = np.asarray(got["action_hist"], np.float64)
    assert np.abs(h0 - h1).sum() <= 0.02 * h0.sum()
    # structure is held unconditionally
    assert np.asarray(got["action_hist"]).shape == \
        np.asarray(golden["action_hist"]).shape
    np.testing.assert_allclose(
        np.asarray(got["action_hist"]).sum(axis=1),
        np.asarray(golden["action_hist"]).sum(axis=1))


if __name__ == "__main__":
    if "--regen" not in sys.argv and "--regen-baselines" not in sys.argv:
        sys.exit("usage: python tests/test_golden.py "
                 "--regen | --regen-baselines")
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    if "--regen" in sys.argv:
        snap = _run_golden()
        with open(GOLDEN, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"wrote {GOLDEN} (jax {snap['jax']})")
    if "--regen-baselines" in sys.argv:
        snap = _run_base_golden()
        with open(GOLDEN_BASE, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"wrote {GOLDEN_BASE} (jax {snap['jax']})")
