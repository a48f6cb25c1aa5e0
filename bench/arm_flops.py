"""Operations and bytes of one decode step of a real arm, from its
shapes (the arm's description, ``bench.drivers.pool.arm_arch``).

Operations count multiply-adds as 2 and leave out norms, activations
and softmax. Per token: the Mamba-2 projections, conv and state update
plus readout (2 x 2 x heads x head_dim x state); the attention
projections and QK^T plus attention-V over the ``context`` positions;
the expert router over all experts, the held experts' share of the
top-k (each token reaches ``top_k x held / experts`` of them on
average) and the shared expert; the logits over the vocabulary.

Bytes are what a step must move at the least: every held weight read
once (at a decode batch of 64 rows every held expert is reached), the
float32 SSM state and the bfloat16 conv state read and written, the K/V
of ``context`` positions read and this step's K/V written.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _mamba_params(arch) -> int:
    d, m = arch["d"], arch["mamba"]
    di, n, h = m["heads"] * m["head_dim"], m["state"], m["heads"]
    conv_dim = di + 2 * n
    return (d * (2 * di + 2 * n + h) + (m["conv"] + 1) * conv_dim
            + di * d + di + 3 * h)


def _attention_params(arch) -> int:
    d, a = arch["d"], arch["attention"]
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 2 * d * q + 2 * d * kv


def _moe_params(arch) -> int:
    d, e = arch["d"], arch.get("moe")
    if not e:
        return 0
    return (d * e["experts"] + e["held"] * 3 * d * e["width"]
            + 3 * d * e["shared"])


def weight_params(arch) -> int:
    """Parameters the arm holds (tied embedding counted once)."""
    d = arch["d"]
    total = arch["vocab"] * d + d                   # embedding, final norm
    for kind in arch["layer_types"]:
        total += (_attention_params(arch) if kind == "attention"
                  else _mamba_params(arch))
        total += d if arch["ssm_only"] else 2 * d + _moe_params(arch)
    return total


def token_flops(arch, context: int) -> float:
    """One token's operations in a decode step at ``context`` positions."""
    d = arch["d"]
    total = 2.0 * arch["vocab"] * d                 # logits
    e = arch.get("moe")
    for kind in arch["layer_types"]:
        if kind == "attention":
            a = arch["attention"]
            total += 2.0 * _attention_params(arch)
            total += 2.0 * 2.0 * a["heads"] * a["head_dim"] * context
        else:
            m = arch["mamba"]
            di, n = m["heads"] * m["head_dim"], m["state"]
            total += 2.0 * (d * (2 * di + 2 * n + m["heads"]) + di * d)
            total += 2.0 * m["conv"] * (di + 2 * n)
            total += 2.0 * 2.0 * di * n
        if e:
            reached = e["top_k"] * e["held"] / e["experts"]
            total += 2.0 * d * e["experts"]
            total += 2.0 * 3 * d * (reached * e["width"] + e["shared"])
    return total


def step_flops(arch, rows: int, context: int) -> float:
    return rows * token_flops(arch, context)


def step_bytes(arch, rows: int, context: int) -> float:
    total = float(weight_params(arch)) * BF16
    for kind in arch["layer_types"]:
        if kind == "attention":
            a = arch["attention"]
            kv = a["kv_heads"] * a["head_dim"]
            total += rows * 2.0 * kv * (context + 1) * BF16
        else:
            m = arch["mamba"]
            di, n = m["heads"] * m["head_dim"], m["state"]
            total += rows * 2.0 * di * n * F32               # SSM state
            total += rows * 2.0 * (m["conv"] - 1) * (di + 2 * n) * BF16
    return total


def call_steps(prompt_width: int, max_new: int):
    """The context (positions read) of each step of one generate call:
    the prompt's steps, then ``max_new - 1`` decode steps."""
    return range(1, prompt_width + max_new)
