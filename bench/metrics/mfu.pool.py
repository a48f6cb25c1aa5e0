"""Whole served path's share of the chips' peak: the operations of the
work done in the window over the window times chips times peak FLOP/s.
The work: the router's, as ``mfu.serve`` counts it (its share is this
one's first term), and the real arms' decode steps
(``bench.arm_flops``), each row's own positions only: its prompt and the
tokens fed back, not the pads or the rows that fill a batch to the
engine's width."""
from bench import arm_flops
from bench.harness import reader

_router_share = reader("mfu.serve")


def _arms(lay) -> float:
    total = 0.0
    for arm in lay["real_arms"].values():
        per_ctx = {}
        for _, lengths in arm["calls"]:
            for n in lengths:
                for ctx in range(1, n + lay["max_new"]):
                    if ctx not in per_ctx:
                        per_ctx[ctx] = arm_flops.token_flops(arm["arch"],
                                                             ctx)
                    total += per_ctx[ctx]
    return total


def read(run):
    lay = run["layer"]
    if not run["peak"]:
        return None
    return _router_share(run) + _arms(lay) / (
        lay["window_s"] * run["chips"] * run["peak"]["flops_bf16"]) * 100.0
