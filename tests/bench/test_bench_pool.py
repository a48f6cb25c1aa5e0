"""The pool cell at a size a test run holds: its configuration and
files, the arm operation counts against hand counts of one step at the
cell's chip share, a whole run (the check passes), the per-layer
metrics of a traced run, and each fault the arms' decode path can have,
planted underneath the harness, coming out not correct.

The arms run at their ``reduced()`` widths in float32 here, so the
comparison with the float32 reference is exact to rounding and a fault
of any size shows; the chip's readings at the published widths are in
PERF.md."""
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import arm_flops, arm_trace  # noqa: E402
from bench.arm_faults import FAULTS, batch_errs, plant  # noqa: E402
from bench.drivers import pool as POOL  # noqa: E402
from bench.flops import least_time, peaks  # noqa: E402
from bench.harness import run_cell  # noqa: E402

CELL = "pool6.chat_bursty"
# the file's keys at the reduced widths the fixture serves
SMALL = {"config": {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 512, "shared_intermediate_size": 512,
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 64,
    "vocab_size": 512, "num_experts_per_tok": 2,
    "layer_types": ["mamba", "attention", "mamba"],
    "num_local_experts": 2, "expert_first": 1,
    "published": {"layer_types": ["mamba", "attention", "mamba"] * 2,
                  "num_local_experts": 4},
    "arms": {"mamba2_130m": {"config": {
        "d_model": 256, "n_layer": 2, "vocab_size": 512, "d_state": 64}}},
    "fleet": {"n_samples": 1500, "n_slices": 3}},
    "traffic": {"population": 48, "decide_batch": 16, "serve_batch": 16,
                "ring_slices": 6, "warmup_decides": 8, "train_batch": 16,
                "queue_capacity": 96, "token_vocab": 500,
                "prompt_len": {"min": 2, "max": 12, "median": 6,
                               "sigma": 0.6}}}


@pytest.fixture
def small_arms(monkeypatch):
    """The pool's real arms at their reduced widths, in float32."""
    import repro.armpool.serving as AS
    from repro.configs import get_config

    def small(name):
        return dataclasses.replace(get_config(name).reduced(),
                                   dtype="float32")
    monkeypatch.setattr(AS, "get_config", small)


def _run(seed=2 ** 35 + 11, trace=False, seconds=0.5):
    return run_cell(CELL, seed, seconds, trace, t_start=time.perf_counter(),
                    overrides=SMALL, need_chip=False)


def _arch_full():
    with open(os.path.join(ROOT, "bench", "configs",
                           "nucb_pool6.json")) as f:
        return POOL.arm_arch(json.load(f), "granite_4_0_h_small")


def test_configuration_states_the_published_model_and_its_cut():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["nucb_pool6"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    pub = cfg["published"]
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert (pub["num_hidden_layers"], pub["num_local_experts"]) == (40, 72)
    assert cfg["num_hidden_layers"] == 40 and cfg["num_local_experts"] == 9
    assert set(entry["reduced"]) == {k for k in pub
                                     if cfg[k] != pub[k]}
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["chips"]) == ("nucb_pool6", 1)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["requests_per_s"]["workloads"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    # the cell's own, and the router's readers it shares with rb11
    own = {"granite4h_step_ms", "granite4h_step_roofline",
           "granite4h_mamba_ms_per_step", "granite4h_moe_ms_per_step",
           "arm_serve_share", "mfu.pool", "granite4h_route_share"}
    shared = {"decide_ms_p50", "train_stall_share",
              "nucb_decide_roofline.serve", "idle_share.serve"}
    assert {m["name"] for m in mine} == own | shared
    for m in mine:
        assert m["workloads"] == ([CELL] if m["name"] in own else
                                  ["rb11.serve_closed", CELL])
        assert m["moves"] == "requests_per_s"
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        assert json.load(f)["driver"] == "pool"
    assert {"arm_logit_err.granite4h", "arm_logit_err.mamba2",
            "arm_state_rounded.granite4h", "arm_state_rounded.mamba2",
            "arm_kv_err.granite4h"} <= set(cfg["limits"])


def test_arm_flops_against_hand_counts_of_one_step():
    """One decode step of the chip share at 64 rows: 2.415 B parameters
    (4.83 GB in bfloat16), 4.83 GB of float32 SSM state read and
    written, about 218 GFLOP; the least time is the bytes' (11.9 ms at
    819 GB/s)."""
    arch = _arch_full()
    mamba = 68_681_728 + 4 * 8448 + 8448 + 33_554_432 + 8192 + 384
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    moe = 4096 * 72 + 9 * 3 * 4096 * 768 + 3 * 4096 * 1536
    params = 100_352 * 4096 + 4096 + 9 * mamba + attn + 10 * (
        moe + 2 * 4096)
    assert arm_flops.weight_params(arch) == params
    assert params == pytest.approx(2.415e9, rel=1e-3)
    state = 9 * 64 * 2 * 128 * 64 * 128 * 4
    conv = 9 * 64 * 2 * 3 * 8448 * 2
    kv = 64 * 2 * 1024 * 33 * 2
    assert arm_flops.step_bytes(arch, 64, 32) == 2 * params + state + \
        conv + kv
    assert state == pytest.approx(4.83e9, rel=1e-3)
    per_token = (2 * 100_352 * 4096
                 + 9 * (2 * (4096 * 16_768 + 8192 * 4096) + 2 * 4 * 8448
                        + 4 * 8192 * 128)
                 + 2 * attn + 4 * 32 * 128 * 32
                 + 10 * (2 * 4096 * 72 + 6 * 4096 * (10 * 9 / 72 * 768
                                                     + 1536)))
    assert arm_flops.step_flops(arch, 64, 32) == pytest.approx(
        64 * per_token, rel=1e-12)
    assert 64 * per_token == pytest.approx(218e9, rel=0.01)
    secs, bound = least_time(arm_flops.step_flops(arch, 64, 32),
                             arm_flops.step_bytes(arch, 64, 32),
                             peaks("TPU v5 lite"))
    assert bound == "memory" and secs == pytest.approx(11.9e-3, rel=0.01)
    assert list(arm_flops.call_steps(24, 8)) == list(range(1, 32))


def test_pool_program_passes(small_arms):
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"requests_per_s", "setup_s"}
    checks = out["checks"]
    for arm in ("granite4h", "mamba2"):
        assert checks[f"arm_logit_err.{arm}"]["value"] < F32_TOL
        assert checks[f"arm_state_rounded.{arm}"]["value"] < 0.02
    assert checks["arm_kv_err.granite4h"]["value"] < F32_TOL
    assert out["compiles_in_window"] == 0


def test_traced_pool_run_reports_its_metrics(small_arms, monkeypatch):
    """``--trace 1`` on the CPU: the window is profiled and the driver
    reduces the arms' spans itself. A CPU trace has no TPU plane, so the
    committed small trace stands in for the harness's reduction, and
    the arms' device metrics, the rooflines and MFU stay out (no peak);
    the router's host metrics read as in rb11."""
    import bench.trace
    small = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "small.xplane.pb")
    real = bench.trace.reduce
    monkeypatch.setattr(bench.trace, "reduce", lambda path: real(small))
    out = _run(seed=91, trace=True, seconds=3.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {
        "arm_serve_share", "idle_share.serve", "granite4h_route_share",
        "decide_ms_p50", "train_stall_share"}
    for name in ("arm_serve_share", "granite4h_route_share"):
        assert 0 < out["metrics"][name]["value"] <= 100


def _arm_errs(rows=16):
    """The arms' checks of one batch of each real arm, every row
    compared (``bench.arm_faults.batch_errs``)."""
    from bench.harness import _merge
    with open(os.path.join(ROOT, "bench", "configs",
                           "nucb_pool6.json")) as f:
        cfg = _merge(json.load(f), SMALL["config"])
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 500, n).astype(np.int32)
               for n in rng.integers(2, 13, rows)]
    return batch_errs(cfg, prompts, max_new=4, seed=5), cfg["limits"]


# float32 arms against the float32 reference: rounding only
F32_TOL = 1e-4


def test_sound_arms_agree_with_the_reference(small_arms):
    errs, limits = _arm_errs()
    gaps = {k: v for k, v in errs.items() if "_err." in k}
    assert set(gaps) == {"arm_logit_err.granite4h", "arm_logit_err.mamba2",
                         "arm_kv_err.granite4h"}
    assert max(gaps.values()) < F32_TOL, errs
    for arm in ("granite4h", "mamba2"):
        assert errs[f"arm_state_rounded.{arm}"] < 0.02, errs


# the arms each fault reaches: the hybrid's expert layers and attention
# are its own; Mamba-2 and the engine are both arms'
REACHES = {"capacity_1_25_drops": ("granite4h",),
           "shared_expert_left_out": ("granite4h",),
           "rope_applied": ("granite4h",)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_arm_fault_is_caught(small_arms, fault):
    """Each fault, planted in the program, comes out not correct in every
    arm it reaches: some check of the arm's sampled rows passes the
    cell's limit."""
    with plant(fault):
        errs, limits = _arm_errs()
    for arm in REACHES.get(fault, ("granite4h", "mamba2")):
        assert any(v > limits[k] for k, v in errs.items()
                   if k.endswith("." + arm)), (fault, arm, errs)


def test_rounded_share_tells_a_float32_state_from_a_narrower_one():
    """float32 arithmetic's results end in 8 zero mantissa bits 1 time
    in 256; a state held in bfloat16 or float16 always does. Zeros do
    not count."""
    import jax.numpy as jnp
    from bench.drivers.pool import rounded_share
    x = np.random.default_rng(0).normal(size=(64, 64, 32)).astype(
        np.float32)
    assert rounded_share(x) == pytest.approx(1 / 256, abs=2e-3)
    for dt in (jnp.bfloat16, jnp.float16):
        assert rounded_share(np.asarray(x.astype(dt), np.float32)) == 1.0
    x[:, :, :16] = 0.0
    assert rounded_share(x) == pytest.approx(1 / 256, abs=3e-3)


def test_arm_trace_reads_the_arms_spans(tmp_path):
    """A CPU trace of one arm batch inside the window span: the arm's
    call, its steps and its time (no device plane on the CPU)."""
    import jax

    from repro.armpool.serving import DecodeArmEngine
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("mamba2_130m").reduced(),
                              dtype="float32")
    eng = DecodeArmEngine("mamba2_130m", cfg, max_batch=2, index=3)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        eng.generate(np.ones((2, 5), np.int32), max_new=3,
                     lengths=[5, 2])
    jax.profiler.stop_trace()
    got = arm_trace.reduce(next(str(p) for p in tmp_path.rglob(
        "*.xplane.pb")))
    arm = got["arms"][3]
    assert arm["calls"] == [(2, 5)] and arm["steps"] == 5 + 3 - 1
    assert arm["generate_s"] > 0 and got["chips"] == 0
