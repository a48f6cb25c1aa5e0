"""Whole served path's share of the chips' peak: the operations of the
work done in the window (``bench.flops``: every decide over all arms,
the Woodbury updates, the replay SGD and the rebuilds) over the window
times chips times peak FLOP/s."""
from bench import flops


def read(run):
    lay = run["layer"]
    if not run["peak"]:
        return None
    r, K = lay["router_widths"], lay["arms"]
    c = lay["counters"]
    total = flops.decide_flops(r, K, lay["router_rows"]["decide"])
    if c["updates"]:
        per = lay["router_rows"]["update"] / c["updates"]
        total += c["updates"] * flops.woodbury_flops(r, int(round(per)))
    total += lay["slices"] * (
        flops.train_flops(r, lay["train_steps"], lay["train_batch"])
        + flops.rebuild_flops(r, lay["ring_rows"]))
    return total / (lay["window_s"] * run["chips"]
                    * run["peak"]["flops_bf16"]) * 100.0
