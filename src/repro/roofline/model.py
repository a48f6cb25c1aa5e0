"""Three-term roofline model (deliverable (g)).

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (values from the task spec).

cost_analysis() of the SPMD-partitioned module reports per-device FLOPs
and bytes, so no further division by chip count is needed; the "chips x
peak" denominators in the spec reduce to per-chip peaks against per-chip
numerators.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float       # FLOP/s (bf16)
    hbm_bw: float           # bytes/s
    link_bw: float          # bytes/s per ICI link
    hbm_bytes: float        # capacity


HW_V5E = Hardware("tpu-v5e", 197e12, 819e9, 50e9, 16e9)

# Order-of-magnitude single-core host model for the measured-vs-analytic
# calibration leg (benchmarks run on CPU runners): ~50 GFLOP/s f32 GEMM,
# ~20 GB/s stream bandwidth. The calibration RATIO is the deliverable,
# so the absolute scale only needs to be the right order.
HW_CPU_HOST = Hardware("cpu-host", 5e10, 2e10, 1e9, 64e9)

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   collective_bytes_per_device: float,
                   hw: Hardware = HW_V5E,
                   model_flops: Optional[float] = None,
                   num_devices: int = 1) -> Dict[str, float]:
    compute_s = flops_per_device / hw.peak_flops
    memory_s = bytes_per_device / hw.hbm_bw
    collective_s = collective_bytes_per_device / hw.link_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=terms.get)
    bound_s = terms[dominant]
    out = dict(terms)
    out["dominant"] = dominant
    out["step_lower_bound_s"] = bound_s
    if model_flops is not None and flops_per_device > 0:
        total_hlo_flops = flops_per_device * num_devices
        out["model_flops"] = model_flops
        out["useful_flop_fraction"] = model_flops / total_hlo_flops
        # MFU-at-roofline: useful FLOPs / (time lower bound x fleet peak)
        out["mfu_upper_bound"] = model_flops / (
            bound_s * hw.peak_flops * num_devices)
    return out


def decode_step_costs(cfg, batch: int, context: int) -> Dict[str, float]:
    """Analytic FLOPs / HBM bytes for ONE greedy decode step of ``batch``
    sequences with ``context`` tokens of history (the armpool's cost
    primitive, DESIGN.md §16).

    Accounting mirrors ``repro.common.config._param_count``'s layer walk
    so every arch family is costed by its actual mixer schedule (a
    hybrid's attention layers by their position, ``layer_is_attn``):

    * GEMMs: ``2 * active_params * batch`` FLOPs, weights read once per
      step (``active_params * dtype_bytes`` — the batch amortizes the
      weight traffic; MoE reads the per-token expert subset and the
      shared expert).
    * attention layers: QK^T + attn·V FLOPs over the layer's EFFECTIVE
      KV length (sliding window / local-global cap bound it) plus the
      KV-cache read+append traffic — the decode-dominant term at scale.
    * mamba/SSD layers: the recurrent state update — state read+write
      bytes and the state-contraction FLOPs, context-independent.
    * cross-attention (VLM / encoder-decoder): KV is precomputed at
      prefill, so decode pays the read traffic + attn FLOPs over the
      fixed memory length (image tokens / audio frames).
    """
    d, hd = cfg.d_model, cfg.resolved_head_dim
    db = _DTYPE_BYTES.get(cfg.dtype, 2)
    q_dim = cfg.num_heads * hd
    kv_dim = cfg.num_kv_heads * hd
    B = float(batch)

    flops = 2.0 * cfg.active_param_count() * B
    weight_bytes = float(cfg.active_param_count()) * db
    kv_bytes = 0.0

    def _attn(kv_len: float):
        """(flops, kv_bytes) of one self/cross-attention mixer at a
        given effective KV length."""
        f = 2.0 * B * cfg.num_heads * hd * kv_len * 2.0   # QK^T + attn.V
        by = B * 2.0 * kv_dim * kv_len * db               # K+V read
        return f, by

    def _ctx_eff(i: int) -> float:
        if cfg.local_global_ratio:
            is_global = (i % (cfg.local_global_ratio + 1)) \
                == cfg.local_global_ratio
            if is_global:
                return float(min(context, cfg.global_attn_cap))
            return float(min(context, cfg.sliding_window or context))
        if cfg.sliding_window:
            return float(min(context, cfg.sliding_window))
        return float(min(context, cfg.global_attn_cap))

    def _mamba():
        d_inner = cfg.ssm_expand * d
        state_elems = d_inner * cfg.ssm_state     # nheads*head_dim*state
        f = 2.0 * B * state_elems * 2.0           # state update + readout
        by = 2.0 * B * state_elems * db           # state read + write
        by += 2.0 * B * d_inner * cfg.ssm_conv_width * db   # conv state
        return f, by

    for i in range(cfg.num_layers):
        if cfg.layer_is_attn(i):
            f, by = _attn(_ctx_eff(i))
            by += B * 2.0 * kv_dim * db       # append this step's K/V
        else:
            f, by = _mamba()
        flops += f
        kv_bytes += by
        if cfg.cross_attn_every and \
                (i % cfg.cross_attn_every) == (cfg.cross_attn_every - 1):
            f, by = _attn(float(cfg.num_image_tokens))
            flops += f
            kv_bytes += by
    if cfg.is_encoder_decoder:
        # decoder cross-attention over the (prefill-encoded) memory
        for _ in range(cfg.num_layers):
            f, by = _attn(float(cfg.num_audio_frames))
            flops += f
            kv_bytes += by

    # activations round-trip once per layer (residual stream read+write)
    act_bytes = 2.0 * B * d * db * max(cfg.num_layers, 1)
    hbm = weight_bytes + kv_bytes + act_bytes
    return {"flops": flops, "hbm_bytes": hbm,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def model_flops_estimate(param_count: int, active_param_count: int,
                         tokens: int, kind: str) -> float:
    """6 N D for training, 2 N D for a forward/prefill/decode pass (per the
    standard transformer FLOPs accounting); MoE uses active params."""
    n = active_param_count
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
