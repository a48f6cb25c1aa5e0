"""Physical arm pool (DESIGN.md §16): loud mapping validation, the
analytic decode-step cost model, pool compilation (bit-identical
across processes, pinned by crc32 — not ``hash()``), the
RouterBench-cost parity contract against the replay sweep, the
ArmPoolSpec codec (pre-PR-10 spec hashes must be untouched), and a
tiny end-to-end ``physical_pool`` run whose serve stage executes REAL
jitted decode steps for the small arm."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.armpool import (
    DEFAULT_RB_MAPPING,
    arm_roofline,
    build_pool_env,
    compile_pool,
    get_hardware_target,
    resolve_arms,
    resolve_mapping,
)
from repro.configs import get_config
from repro.data.routerbench import (
    RouterBenchSim,
    generate_routerbench,
    model_prices,
)
from repro.experiments import (
    ArmPoolSpec,
    DataSpec,
    make_preset,
    run_spec,
    spec_from_json,
    spec_hash,
    spec_to_json,
)
from repro.roofline import decode_step_costs
from repro.sim import DeviceReplayEnv, greedy_policy, run_baseline_device

ARMS4 = ("mamba2_130m", "llama3_2_3b", "mistral_nemo_12b",
         "jamba_1_5_large_398b")

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir))


@pytest.fixture(scope="module")
def data():
    return generate_routerbench(0, 400)


# -------------------------------------------------- loud validation --
def test_unknown_arch_raises_with_name():
    with pytest.raises(ValueError, match="no_such_model"):
        resolve_arms(("mamba2_130m", "no_such_model"))


def test_duplicate_arm_raises_with_name():
    with pytest.raises(ValueError, match="mamba2_130m"):
        resolve_arms(("mamba2_130m", "mamba2-130m"))  # alias == same arm


def test_empty_pool_raises():
    with pytest.raises(ValueError, match="empty"):
        resolve_arms(())


def test_mapping_override_for_absent_arm_raises():
    with pytest.raises(ValueError, match="gemma3_4b"):
        resolve_mapping(["mamba2_130m"], ["zephyr-7b"],
                        overrides=(("gemma3_4b", "gpt-4"),))


def test_unmapped_arm_raises():
    # an arm with no mapping entry must not pair positionally
    assert "custom_ft_7b" not in DEFAULT_RB_MAPPING
    with pytest.raises(ValueError, match="custom_ft_7b"):
        resolve_mapping(["custom_ft_7b"], ["gpt-4"])


def test_mapped_model_missing_from_tables_raises():
    with pytest.raises(ValueError, match="zephyr-7b"):
        resolve_mapping(["mamba2_130m"], ["gpt-4", "claude-v2"])


def test_pool_env_k_mismatch_raises(data):
    pool = compile_pool(ArmPoolSpec(arms=ARMS4), data)
    with pytest.raises(ValueError, match="K mismatch"):
        pool.validate_against(11, what="device env")


def test_unknown_hardware_target_raises():
    with pytest.raises(ValueError, match="moonbase"):
        get_hardware_target("moonbase")


# ------------------------------------------- decode-step cost model --
def test_decode_step_costs_scale_with_batch_and_params():
    small = get_config("mamba2_130m")
    big = get_config("mistral_nemo_12b")
    c1 = decode_step_costs(small, 4, 2048)
    c8 = decode_step_costs(small, 8, 2048)
    cb = decode_step_costs(big, 4, 2048)
    for k in ("flops", "hbm_bytes", "weight_bytes"):
        assert c1[k] > 0
    # flops scale ~linearly with batch; weight traffic does not
    assert c8["flops"] > 1.8 * c1["flops"]
    assert c8["weight_bytes"] == c1["weight_bytes"]
    # a 95x-params model costs far more per step
    assert cb["flops"] > 20 * c1["flops"]
    assert cb["weight_bytes"] > 20 * c1["weight_bytes"]


def test_attention_costs_grow_with_context_mamba_does_not():
    attn = get_config("mistral_nemo_12b")
    mamba = get_config("mamba2_130m")
    assert decode_step_costs(attn, 4, 4096)["kv_bytes"] \
        > decode_step_costs(attn, 4, 512)["kv_bytes"]
    assert decode_step_costs(mamba, 4, 4096)["kv_bytes"] \
        == decode_step_costs(mamba, 4, 512)["kv_bytes"]


def test_arm_roofline_economics():
    target = get_hardware_target("tpu-v5e")
    small = arm_roofline(get_config("mamba2_130m"), target,
                         batch=8, context=2048)
    big = arm_roofline(get_config("jamba_1_5_large_398b"), target,
                       batch=8, context=2048)
    assert small["chips"] == 1
    assert big["chips"] > 1              # 398B cannot fit one v5e HBM
    assert big["usd_per_token"] > 50 * small["usd_per_token"]
    assert small["step_s"] > 0 and big["step_s"] > small["step_s"]


# ------------------------------------------------- pool compilation --
def test_compiled_tables_shape_and_finiteness(data):
    aspec = ArmPoolSpec(arms=ARMS4)
    pool = compile_pool(aspec, data)
    n, K = 400, len(ARMS4)
    assert pool.K == K and pool.arms == ARMS4
    for t in (pool.quality, pool.cost, pool.latency_s):
        assert t.shape == (n, K) and t.dtype == np.float32
        assert np.isfinite(t).all()
    assert (pool.cost > 0).all() and (pool.latency_s > 0).all()
    # per-arm scalars follow the declared hardware, not the table order
    order = np.argsort(pool.params_b)
    assert list(order) == sorted(order, key=lambda i: pool.params_b[i])
    assert pool.cost_source == "roofline"


def test_compile_is_deterministic_in_process(data):
    aspec = ArmPoolSpec(arms=ARMS4)
    p1 = compile_pool(aspec, data)
    p2 = compile_pool(aspec, data)
    assert p1.checksum == p2.checksum
    np.testing.assert_array_equal(p1.cost, p2.cost)
    np.testing.assert_array_equal(p1.quality, p2.quality)


_CHILD = """
import json, sys
from repro.armpool import compile_pool
from repro.data.routerbench import generate_routerbench
from repro.experiments import ArmPoolSpec
pool = compile_pool(ArmPoolSpec(arms={arms!r}), generate_routerbench(0, 400))
print(json.dumps({{"checksum": pool.checksum}}))
"""


def test_compile_is_deterministic_cross_process(data):
    """crc32 over table bytes + arm names must agree across processes
    (``hash()`` would not: PYTHONHASHSEED)."""
    here = compile_pool(ArmPoolSpec(arms=ARMS4), data).checksum
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO_ROOT, "src"),
                    env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(arms=ARMS4)],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout)["checksum"] == here


def test_calibration_scales_small_arms_only(data):
    aspec = ArmPoolSpec(arms=ARMS4, calibrate=True,
                        calibrate_max_params=2_000_000_000)
    calls = []

    def fake_ratio(cfg, batch):
        calls.append(cfg.name)
        return {"ratio": 3.0, "step_s": 0.0, "analytic_step_s": 0.0}

    base = compile_pool(ArmPoolSpec(arms=ARMS4), data)
    pool = compile_pool(aspec, data, calibrate_fn=fake_ratio)
    # only the <=2B arms get measured; their step time is de-rated 3x
    assert calls == ["mamba2-130m"]
    np.testing.assert_allclose(pool.step_s[0], 3.0 * base.step_s[0])
    np.testing.assert_allclose(pool.step_s[1:], base.step_s[1:])
    assert pool.calibration is not None
    assert "mamba2_130m" in pool.calibration


def test_completion_backout_uses_name_keyed_prices(data):
    """cost = price * (prompt + completion)/1000 backed out per mapped
    column — keyed by model NAME so a reordered table cannot re-price."""
    pool = compile_pool(ArmPoolSpec(arms=ARMS4), data)
    prices = model_prices()
    for rb in pool.rb_models:
        assert rb in prices


# ------------------------------------------------------- parity leg --
def test_routerbench_cost_pool_reproduces_replay_sweep():
    """A pool whose costs are forced back to the RouterBench tables
    must reproduce the replay-table run bit-exactly over its mapped
    columns — proof the pool path adds no hidden transform."""
    dspec = DataSpec(n_samples=600, n_slices=3)
    aspec = ArmPoolSpec(arms=ARMS4, cost_source="routerbench")
    henv_pool, pool = build_pool_env(aspec, dspec)

    base = generate_routerbench(0, 600)
    ref = dict(base)
    cols = list(pool.cols)
    ref["quality"] = base["quality"][:, cols]
    ref["cost"] = base["cost"][:, cols]
    ref["model_names"] = np.asarray(
        [base["model_names"][c] for c in cols])
    henv_ref = RouterBenchSim(seed=0, n_slices=3, data=ref)

    d_pool = DeviceReplayEnv.from_host(henv_pool)
    d_ref = DeviceReplayEnv.from_host(henv_ref)
    r_pool = run_baseline_device(d_pool, greedy_policy(d_pool.K), seed=0)
    r_ref = run_baseline_device(d_ref, greedy_policy(d_ref.K), seed=0)
    np.testing.assert_array_equal(np.asarray(r_pool["avg_reward"]),
                                  np.asarray(r_ref["avg_reward"]))
    np.testing.assert_array_equal(np.asarray(r_pool["avg_cost"]),
                                  np.asarray(r_ref["avg_cost"]))


# -------------------------------------------------------- spec codec --
# pre-PR-10 spec hashes, computed BEFORE ArmPoolSpec existed: adding
# the optional section must leave every old preset's canonical JSON —
# and therefore its hash — untouched (emit-only-when-set).
PRE_PR10_HASHES = {
    "paper_table1": "85591add0e29de38",
    "fig2_beta_sweep": "c3b573e341919152",
    "scenario_suite": "a6fd36f2cf38743a",
    "policy_zoo": "28847c5d8d6024a4",
    "ci_smoke": "0a5b4d08377d8795",
    "serving_storm": "fcb9e3941b5490a9",
    "offline_online": "fb6613d2a8e0ce88",
    "ope_selection": "4a23fdba263fc2eb",
    "bench_nucb_sweep": "17f16e06becc5aea",
    "bench_zoo_sweep": "ec1669407b3efafd",
}


def test_pre_pr10_spec_hashes_unchanged():
    for name, want in PRE_PR10_HASHES.items():
        assert spec_hash(make_preset(name)) == want, name


def test_armpool_section_emitted_only_when_set():
    assert "armpool" not in spec_to_json(make_preset("paper_table1"))
    doc = spec_to_json(make_preset("physical_pool"))
    assert doc["armpool"]["arms"][0] == "mamba2_130m"
    rt = spec_from_json(json.loads(json.dumps(doc)))
    assert rt == make_preset("physical_pool")


def test_armpool_spec_validation():
    with pytest.raises(ValueError, match="no arms"):
        ArmPoolSpec(arms=())
    with pytest.raises(ValueError, match="cost_source"):
        ArmPoolSpec(arms=ARMS4, cost_source="vibes")
    with pytest.raises(ValueError, match="max_new"):
        ArmPoolSpec(arms=ARMS4, max_new=0)
    with pytest.raises(ValueError):
        ArmPoolSpec(arms=ARMS4,
                    mapping=(("mamba2_130m", "gpt-4"),
                             ("mamba2_130m", "claude-v2")))


def test_armpool_set_overrides():
    spec = make_preset("physical_pool", {
        "armpool.decode_batch": 4,
        "armpool.arms": list(ARMS4),
        "armpool.cost_source": "routerbench"})
    assert spec.armpool.decode_batch == 4
    assert spec.armpool.arms == ARMS4
    assert spec.armpool.cost_source == "routerbench"
    with pytest.raises((KeyError, ValueError)):
        make_preset("physical_pool", {"armpool.decode_bacth": 4})


def test_armpool_spec_rejects_env_injection():
    from repro.experiments import compile_spec
    henv = RouterBenchSim(seed=0, n_samples=400, n_slices=2)
    denv = DeviceReplayEnv.from_host(henv)
    spec = make_preset("physical_pool", {"data.n_samples": 400})
    with pytest.raises(ValueError, match="armpool"):
        compile_spec(spec, env=denv, host_env=henv)


# ------------------------------------------------------- end to end --
def test_physical_pool_preset_tiny_end_to_end():
    """Shrunk ``--preset physical_pool``: BOTH the replay sweep and the
    semi-real storm run from one spec; the small arm must report real
    decode-step dispatches; the artifact carries pool provenance."""
    spec = make_preset("physical_pool", {
        "data.n_samples": 600, "data.n_slices": 2,
        "train.train_steps": 4,
        "armpool.arms": list(ARMS4),
        "serving.requests": 200, "serving.waves": 4,
        "serving.decide_batch": 32, "serving.serve_batch": 32,
        "serving.train_every": 0})
    res = run_spec(spec)
    assert res.ok
    scen = res.scenario_names()
    assert "stationary" in scen
    assert any(s.startswith("serving:") for s in scen)
    srv = next(c for c in res.cells if c["scenario"].startswith("serving"))
    steps = srv["serving"]["decode_steps"]
    assert steps["real"].get("mamba2_130m", 0) > 0
    assert set(steps["clocked"]) == set(ARMS4) - {"mamba2_130m"}
    assert srv["armpool_engines"]["real_decode_arms"] == ["mamba2_130m"]
    mani = res.manifest["armpool"]
    assert mani["arms"] == list(ARMS4)
    assert mani["checksum"] == compile_pool(
        ArmPoolSpec(arms=ARMS4),
        generate_routerbench(0, 600)).checksum


def test_decode_arm_engine_pins_batch_width():
    """Rows are padded to the engine's pinned width: after the warm-up no
    batch size up to it compiles anything, padding never changes a real
    row's tokens, and a wider batch is refused."""
    from repro.armpool import DecodeArmEngine
    from repro.common.compilation import CompileCount

    eng = DecodeArmEngine("mamba2_130m",
                          get_config("mamba2_130m").reduced(), max_batch=4)
    toks = np.arange(1, 5, dtype=np.int32).reshape(4, 1)
    with CompileCount() as cc:
        full, _ = eng.generate(toks, max_new=2)
        for b in (1, 3):
            part, _ = eng.generate(toks[:b], max_new=2)
            np.testing.assert_array_equal(part, full[:b])
    assert cc.compiles == 0
    assert full.shape == (4, 2)
    with pytest.raises(ValueError, match="pinned width"):
        eng.generate(np.ones((5, 1), np.int32), max_new=2)
