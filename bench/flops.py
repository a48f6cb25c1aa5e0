"""Operations and bytes the algorithm needs, from its shapes.

Counts are of multiply-adds as 2 operations, at the real widths (the
NeuralUCB feature F = d_last + 1 = 129, with no kernel padding), and
leave out elementwise work (activations, norms), which is a few per
cent at these widths. Bytes are what a call must read and write at the
least: its inputs, weights and outputs once each.

``peaks(kind)`` reads ``bench/peaks.json``; a device not in the table is
an error.
"""
from __future__ import annotations

from bench.common import BENCH, load_json

F32 = 4


def peaks(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[kind]


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bytes/s, and which of the two it is."""
    tc = flops / peak["flops_bf16"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# ---------------------------------------------------------- UtilityNet --
def _w(r: dict) -> dict:
    C = r["d_text"] + r["d_feat"]
    return dict(E=r["emb_dim"], Fx=r["feat_dim"], Dd=r["d_domain"],
                Da=r["d_action"], Dt=r["d_text"], Df=r["d_feat"],
                H=r["d_hidden"], D=r["d_last"], G=r["d_gate"], C=C,
                F=r["d_last"] + 1)


def context_flops(r: dict) -> int:
    """Per request: text MLP, feature MLP, gate head."""
    w = _w(r)
    return 2 * (w["E"] * w["Dt"] + w["Dt"] * w["Dt"]
                + (w["Fx"] + w["Dd"]) * w["Df"]
                + w["C"] * w["G"] + w["G"])


def trunk_flops(r: dict) -> int:
    """Per (request, action): trunk1 over [h_text, h_feat, e_a], trunk2,
    utility head."""
    w = _w(r)
    return 2 * ((w["C"] + w["Da"]) * w["H"] + w["H"] * w["D"] + w["D"])


def decide_kernel_flops(r: dict, K: int, B: int) -> int:
    """The fused decide kernel's part of one call of B requests: the
    context half of trunk1 once per request, then per action trunk2, the
    utility head and the bonus g' A^-1 g."""
    w = _w(r)
    per_action = 2 * (w["H"] * w["D"] + w["D"] + w["F"] * w["F"] + w["F"])
    return B * (2 * w["C"] * w["H"] + K * per_action)


def decide_kernel_bytes(r: dict, K: int, B: int) -> int:
    """Inputs, weights and outputs of that call, float32."""
    w = _w(r)
    ins = (B * w["C"] + w["C"] * w["H"] + K * w["H"] + w["H"] * w["D"]
           + 2 * w["D"] + w["F"] * w["F"] + B)
    outs = B * (1 + w["F"] + 1)
    return F32 * (ins + outs)


def decide_flops(r: dict, K: int, B: int) -> int:
    """One whole decide of B requests over K actions."""
    w = _w(r)
    return B * (context_flops(r) + K * (trunk_flops(r)
                                        + 2 * (w["F"] * w["F"] + w["F"])))


def woodbury_flops(r: dict, n: int, block: int = 128) -> int:
    """Rank-n update of A^-1 in blocks of ``block`` rows: per block of k
    rows u = G A^-1, S = I + u G', Cholesky of S, the solve, A^-1 - u'x."""
    F = _w(r)["F"]
    total = 0
    while n > 0:
        k = min(n, block)
        total += 4 * k * F * F + 4 * k * k * F + k ** 3 // 3
        n -= k
    return total


def train_flops(r: dict, steps: int, batch: int) -> int:
    """Replay SGD: forward and backward (3x the forward) of one
    (request, action) row."""
    return 3 * steps * batch * (context_flops(r) + trunk_flops(r))


def rebuild_flops(r: dict, rows: int) -> int:
    """Features of every ring row with the new weights, their Gram
    matrix, and the inverse of the F x F result."""
    F = _w(r)["F"]
    return rows * (context_flops(r) + trunk_flops(r) + 2 * F * F) \
        + 3 * F ** 3
