"""Share of the traced part of the window the host spent inside the real
arms' ``arm.generate`` spans (an arm batch through its decode engine,
tokens back on the host)."""


def read(run):
    tr = run["layer"].get("arm_trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return sum(a["generate_s"] for a in tr["arms"].values()) \
        / tr["window_s"] * 100.0
