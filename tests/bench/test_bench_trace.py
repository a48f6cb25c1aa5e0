"""The trace reduction on a small committed trace whose numbers are
known by construction (``data/make_trace.py`` says what it holds)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(SMALL)


def test_window_and_busy_time(summary):
    # two chips, each keyed by its own plane though both end on %fusion.2
    assert summary["chips"] == 2
    assert summary["window_s"] == pytest.approx(10e-6)
    # TPU:0 from the window's start: 500 + 1200 + 800; TPU:1: 600
    assert summary["busy_s"] == pytest.approx((2.5e-6 + 0.6e-6) / 2)


def test_time_per_operation_and_program(summary):
    assert summary["ops"] == pytest.approx({
        "%fusion.3": 0.5e-6, "%fusion.1": 0.5e-6,
        "%nucb_decide_padded.1": 0.5e-6, "%slice.7": 0.2e-6,
        "%fusion.2": 1.4e-6})
    assert summary["op_stats"]["%fusion.2"]["hlo"].startswith(
        "%fusion.2 = f32[8]")
    assert summary["modules"] == pytest.approx({
        "jit__srv_decide(1)": 1.0e-6, "jit__srv_update(2)": 1.4e-6})
    assert [k for k, _ in summary["device_ops"]][0] == "%fusion.2"


def test_kernel_found_by_its_own_name(summary):
    # the slice that reads the kernel's output names it as an operand
    # and does not count
    assert trace.seconds_matching(summary, ("nucb_decide_padded",)) == \
        pytest.approx(0.5e-6)
    assert trace.seconds_matching(summary, ("tpu_custom_call",)) == 0.0
    assert trace.seconds_matching(summary, ("_srv_update",),
                                  key="modules") == pytest.approx(1.4e-6)
    assert trace.seconds_matching(summary, ("no_such_kernel",)) == 0.0


def test_idle_gaps_go_to_the_innermost_host_span(summary):
    # TPU:0: [1500, 2500] lies in decide, [3700, 6000] in no span,
    # [6800, 11000] in pump (its update ended at 7000, before the
    # midpoint); TPU:1: [1000, 2000] in no span, [2600, 11000] in the
    # update inside pump; each averaged over the two chips
    assert summary["idle_by_host"] == pytest.approx({
        "bench.router.decide": 0.5e-6, "host:other": 1.65e-6,
        "bench.frontend.pump": 2.1e-6, "bench.router.update": 4.2e-6})
    idle = sum(summary["idle_by_host"].values())
    assert idle + summary["busy_s"] == pytest.approx(summary["window_s"])
    assert summary["idle_gaps"][0][0] == "bench.router.update"


def test_no_device_plane_is_an_error(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    data = open(SMALL, "rb").read()
    path.write_bytes(data.replace(b"/device:TPU:", b"/device:XYZ:"))
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce(str(path))
