"""The fused decide kernel's share of its roofline on the served path:
the least time of the window's decide calls (``bench.flops``: the
larger of operations over peak FLOP/s and bytes over peak bytes/s, at
the rows each call carries) over the kernel's time in the trace."""
from bench import flops
from bench.trace import seconds_matching

# the Pallas call's name in the trace
KERNEL = ("nucb_decide_padded",)


def read(run):
    tr = run["trace"]
    if not tr or not run["peak"]:
        return None
    secs = seconds_matching(tr, KERNEL)
    if secs <= 0:
        return None
    lay = run["layer"]
    r, K, B = lay["router_widths"], lay["arms"], lay["decide_batch"]
    least, _ = flops.least_time(flops.decide_kernel_flops(r, K, B),
                                flops.decide_kernel_bytes(r, K, B),
                                run["peak"])
    return least * lay["traced"]["decide_calls"] / secs * 100.0
