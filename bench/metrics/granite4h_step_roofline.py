"""The hybrid arm's decode steps against their roofline: the least time
of the traced steps (``bench.arm_flops``: for each step, the larger of
its operations over peak FLOP/s and its bytes over peak bytes/s, at the
engine's pinned batch width and the step's context) over their device
time (``granite4h_step_ms``'s numerator). Memory-bound: the weights and
the SSM state are read and written every step."""
from bench import arm_flops
from bench.arm_trace import traced_arm
from bench.flops import least_time


def read(run):
    found = traced_arm(run, "granite4h")
    if found is None or not run["peak"] or found[1]["device_s"] <= 0:
        return None
    arm, got = found
    lay = run["layer"]
    rows, arch = lay["serve_batch"], arm["arch"]
    least = 0.0
    for _, width in got["calls"]:
        for ctx in arm_flops.call_steps(width, lay["max_new"]):
            least += least_time(arm_flops.step_flops(arch, rows, ctx),
                                arm_flops.step_bytes(arch, rows, ctx),
                                run["peak"])[0]
    return least / got["device_s"] * 100.0
