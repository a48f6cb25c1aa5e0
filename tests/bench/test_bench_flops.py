"""The benchmark's operation counts against hand counts, and its peaks
table."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import flops  # noqa: E402
from bench.common import load_json  # noqa: E402

ROUTER = load_json(ROOT, "bench", "configs", "nucb_rb11.json")["router"]


def test_decide_by_hand():
    # context: text 384x256 + 256x256, features (4+16)x32, gate 288x64 + 64
    ctx = 2 * (384 * 256 + 256 * 256 + 20 * 32 + 288 * 64 + 64)
    assert ctx == 365_952 == flops.context_flops(ROUTER)
    # trunk per action: (256+32+16)x256, 256x128, head 128
    trunk = 2 * (304 * 256 + 256 * 128 + 128)
    assert trunk == 221_440 == flops.trunk_flops(ROUTER)
    # bonus per action: g' A^-1 g with F = 129 (no padding)
    bonus = 2 * (129 * 129 + 129)
    assert flops.decide_flops(ROUTER, 11, 1) == ctx + 11 * (trunk + bonus) \
        == 3_170_732
    assert flops.decide_flops(ROUTER, 11, 512) == 512 * 3_170_732


def test_decide_kernel_by_hand():
    # per row: the context GEMM 288x256 once, then per action 256x128,
    # the head 128 and the bonus 129x129 + 129
    per_row = 2 * 288 * 256 + 11 * 2 * (256 * 128 + 128 + 129 * 129 + 129)
    assert per_row == 1_240_108
    assert flops.decide_kernel_flops(ROUTER, 11, 512) == 512 * per_row
    words = (512 * 288 + 288 * 256 + 11 * 256 + 256 * 128 + 2 * 128
             + 129 * 129 + 512) + 512 * 131
    assert flops.decide_kernel_bytes(ROUTER, 11, 512) == 4 * words \
        == 1_364_996


def test_woodbury_and_rebuild_by_hand():
    F = 129
    k = 128
    block = 4 * k * F * F + 4 * k * k * F + k ** 3 // 3
    assert flops.woodbury_flops(ROUTER, 512) == 4 * block
    assert flops.woodbury_flops(ROUTER, 130) == block + (
        4 * 2 * F * F + 4 * 4 * F + 8 // 3)
    row = 365_952 + 221_440 + 2 * F * F
    assert flops.rebuild_flops(ROUTER, 1000) == 1000 * row + 3 * F ** 3


def test_least_time_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["flops_bf16"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t, bound = flops.least_time(197e12, 1.0, peak)
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = flops.least_time(1.0, 819e9, peak)
    assert t == pytest.approx(1.0) and bound == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
