"""The serve cell's comparison at a size a test run holds: the program
passes its limits, the control (the reference in bfloat16 in the
program's place) fails them, and so does every fault the timed path can
have, planted underneath the harness. The harness's look for a chip is
skipped; the rest of a run is the benchmark's own."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.faults import FAULTS, plant  # noqa: E402
from bench.harness import run_cell  # noqa: E402

SMALL = {"config": {"fleet": {"n_samples": 2000, "n_slices": 3}},
         "traffic": {"population": 64, "decide_batch": 32,
                     "serve_batch": 32, "ring_slices": 16,
                     "warmup_decides": 20, "train_batch": 16,
                     "queue_capacity": 128}}


def _run(seed=2 ** 33 + 5, control=False, config=None):
    over = {"config": dict(SMALL["config"], **(config or {})),
            "traffic": SMALL["traffic"]}
    return run_cell("rb11.serve_closed", seed, 1.0, False,
                    t_start=time.perf_counter(), overrides=over,
                    need_chip=False, control=control)


def _failed(out):
    return sorted(k for k, c in out["checks"].items()
                  if c["value"] > c["limit"])


def test_program_passes():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"requests_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_fails():
    """The control is the reference at the configuration's
    ``control_precision`` (three-pass ``high``) in the program's place.
    The CPU runs ``high`` as full float32, so here the control takes the
    next step down, bfloat16, through the same path."""
    out = _run(control=True, config={"control_precision": "bfloat16"})
    assert not out["correct"]
    assert _failed(out), out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault):
    with plant(fault):
        out = _run()
    assert not out["correct"], (fault, out["checks"])


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """``--trace 1`` on the CPU: the window is profiled, and the line
    carries the per-layer metrics, the device's busy time and the
    breakdown. A CPU trace has no TPU plane, so the reduction of the
    committed small trace stands in for it; the roofline and MFU need a
    chip's peaks and stay out."""
    import bench.trace
    small = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "small.xplane.pb")
    real = bench.trace.reduce
    monkeypatch.setattr(bench.trace, "reduce", lambda path: real(small))
    out = run_cell("rb11.serve_closed", 77, 1.0, True,
                   t_start=time.perf_counter(), overrides=SMALL,
                   need_chip=False)
    assert out["correct"]
    assert set(out["metrics"]) == {"frontend_us_per_req", "decide_ms_p50",
                                   "train_stall_share", "idle_share.serve"}
    assert out["metrics"]["idle_share.serve"]["value"] == \
        pytest.approx(84.5)
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert list(out)[-1] == "checks"
