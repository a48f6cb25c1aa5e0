"""Device time of the hybrid arm's decode steps, per step: the device
operations inside its ``arm.generate`` spans in the traced part of the
window (the router's own operations left out), over the decode steps
those spans dispatched (``bench.arm_trace``)."""
from bench.arm_trace import traced_arm


def read(run):
    found = traced_arm(run, "granite4h")
    if found is None:
        return None
    _, got = found
    return got["device_s"] / got["steps"] * 1e3
