"""Device time of the hybrid arm's scope ``arm.mamba`` per decode
step, inside its ``arm.generate`` spans in the traced part of the
window (``bench.arm_trace``)."""
from bench.arm_trace import traced_arm


def read(run):
    found = traced_arm(run, "granite4h")
    if found is None:
        return None
    _, got = found
    secs = got["scopes"].get("arm.mamba")
    return None if secs is None else secs / got["steps"] * 1e3
