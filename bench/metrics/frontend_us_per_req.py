"""Host time of the serving front end per completed request: wall in
the engine's submit / pump / drain calls, less the time inside router
and arm calls (the benchmark's proxies time both), over the window."""


def read(run):
    lay = run["layer"]
    if not lay["completed"]:
        return None
    return lay["frontend_s"] / lay["completed"] * 1e6
