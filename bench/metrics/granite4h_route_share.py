"""Share of the window's completed requests that the router sent to the
hybrid arm: the rows of its arm batches (the engine's own count, through
the driver's proxy) over the requests completed. What the router learns
to send the local arm sets how many of its batches a window holds."""


def read(run):
    lay = run["layer"]
    arm = next((a for a in lay.get("real_arms", {}).values()
                if a["key"] == "granite4h"), None)
    if arm is None or not lay["completed"]:
        return None
    rows = sum(len(lengths) for _, lengths in arm["calls"])
    return rows / lay["completed"] * 100.0
