"""Configuration dataclasses shared across the framework.

``ModelConfig`` describes every architecture family in the pool with a
single schema; family-specific fields default to "off" (0 / False).
``InputShape`` describes the assigned benchmark input shapes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Unified architecture description.

    arch_type in {dense, moe, ssm, hybrid, audio, vlm}.
    """

    name: str
    arch_type: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0  # experts the router scores (its output width)
    experts_per_token: int = 0
    moe_every: int = 1  # an MoE FFN every N layers (jamba: 2); dense FFN else
    moe_capacity_factor: float = 1.25  # 0: dropless (no token is dropped)
    shared_expert_ff: int = 0  # width of an always-on shared expert; 0: none
    # expert parallelism's share: this chip holds experts
    # [expert_first, expert_first + experts_held) and computes their part
    # of each expert layer's output (0: it holds all ``num_experts``)
    experts_held: int = 0
    expert_first: int = 0

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv_width: int = 4

    # --- hybrid: one attention layer per `attn_every` layers, at position
    # `attn_index` of each period (-1: the last, as in jamba's superblock;
    # granite-4.0-h: 5 of 10) ---
    attn_every: int = 0
    attn_index: int = -1

    # --- attention pattern ---
    sliding_window: int = 0  # 0 = full attention
    # gemma-style local:global -> layer i is GLOBAL iff (i % (ratio+1)) == ratio
    local_global_ratio: int = 0
    # cap on global-attention KV during long-context decode (see DESIGN.md)
    global_attn_cap: int = 32768

    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # --- VLM: a gated cross-attention layer every N decoder layers ---
    cross_attn_every: int = 0
    num_image_tokens: int = 1601  # llama-3.2-vision: 1601 patch tokens/tile

    # --- audio stub frontend ---
    num_audio_frames: int = 1500  # whisper: 30s -> 1500 frames

    # --- misc ---
    remat: str = "layer"  # activation checkpointing for train: none|layer
    qk_norm: bool = False
    use_rope: bool = True  # False: no position embedding (NoPE)
    rope_theta: float = 10000.0
    # granite's multipliers: x0 = embedding_multiplier * E[t]; each
    # residual branch is scaled by residual_multiplier; attention scores
    # by attention_multiplier (0: 1/sqrt(head_dim)); logits are divided
    # by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    citation: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def attn_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def local_experts(self) -> int:
        """Experts whose weights this configuration holds."""
        return self.experts_held or self.num_experts

    @property
    def attn_position(self) -> int:
        """Position of the attention mixer within a hybrid period."""
        return self.attn_index % self.attn_every

    def layer_is_attn(self, i: int) -> bool:
        """Whether layer ``i``'s mixer is attention (else Mamba2)."""
        if self.arch_type == "ssm":
            return False
        if self.attn_every:
            return i % self.attn_every == self.attn_position
        return True

    def layer_is_moe(self, i: int) -> bool:
        return self.is_moe and i % self.moe_every == self.moe_every - 1

    @property
    def attention_scale(self) -> float:
        return self.attention_multiplier or \
            1.0 / self.resolved_head_dim ** 0.5

    @property
    def supports_long_decode(self) -> bool:
        """True when 524k-token decode is sub-quadratic (see DESIGN.md)."""
        if self.arch_type in ("ssm",):
            return True
        if self.arch_type == "hybrid":
            return True  # attn layers bounded by sliding window / cap
        return self.sliding_window > 0 or self.local_global_ratio > 0

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs decode (whisper via its text decoder)

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs)."""
        return _param_count(self, active_only=False)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 layers, d_model<=256, <=4 experts."""
        d_model = min(self.d_model, 256)
        heads = min(self.num_heads, 4)
        kv = min(self.num_kv_heads, heads)
        upd = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=d_model,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=64,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            ssm_state=min(self.ssm_state, 64) if self.ssm_state else 0,
            ssm_chunk=32,
            num_image_tokens=16,
            num_audio_frames=32,
            global_attn_cap=128,
        )
        if self.num_experts:
            upd["num_experts"] = min(self.num_experts, 4)
            upd["experts_per_token"] = min(self.experts_per_token, 2)
            if self.moe_capacity_factor > 0:
                # no capacity drops at toy scale: keeps decode == forward
                upd["moe_capacity_factor"] = 8.0
        if self.shared_expert_ff:
            upd["shared_expert_ff"] = min(self.shared_expert_ff, 512)
        if self.num_encoder_layers:
            upd["num_encoder_layers"] = 2
        if self.attn_every and self.attn_position != self.attn_every - 1:
            # one period with attention inside it (mamba, attn, mamba)
            upd["attn_every"] = 3
            upd["attn_index"] = 1
            upd["num_layers"] = 3
        elif self.attn_every:
            upd["attn_every"] = 2
            upd["num_layers"] = 4  # two (1 mamba + 1 attn) super-blocks
        if self.cross_attn_every:
            upd["cross_attn_every"] = 2
            upd["num_layers"] = 4
        if self.local_global_ratio:
            upd["local_global_ratio"] = 1
            upd["sliding_window"] = min(self.sliding_window or 128, 128)
        elif self.sliding_window:
            upd["sliding_window"] = 128
        return dataclasses.replace(self, **upd)

    def chip_share(self, num_layers: int, experts_held: int = 0,
                   expert_first: int = 0) -> "ModelConfig":
        """What one chip of an expert-parallel, pipelined deployment
        holds: the first ``num_layers`` layers (a pipeline stage; whole
        periods of a hybrid's pattern) and ``experts_held`` of each
        expert layer's experts from ``expert_first``. Every width, the
        router's ``num_experts`` outputs and its top-k stay as
        published."""
        if not 0 < num_layers <= self.num_layers:
            raise ValueError(f"{self.name}: {num_layers} layers of "
                             f"{self.num_layers}")
        if self.attn_every and num_layers % self.attn_every:
            raise ValueError(f"{self.name}: {num_layers} layers is not a "
                             f"whole number of {self.attn_every}-layer "
                             f"periods")
        if experts_held and not (
                self.is_moe and 0 <= expert_first
                and expert_first + experts_held <= self.num_experts):
            raise ValueError(f"{self.name}: experts [{expert_first}, "
                             f"{expert_first + experts_held}) of "
                             f"{self.num_experts}")
        upd = {"num_layers": num_layers}
        if experts_held:
            upd.update(experts_held=experts_held, expert_first=expert_first)
        return dataclasses.replace(self, **upd)


def _param_count(cfg: ModelConfig, active_only: bool) -> int:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    q = cfg.num_heads * hd
    kv = cfg.num_kv_heads * hd

    def attn_params() -> int:
        return d * q + 2 * d * kv + q * d

    def dense_ffn() -> int:
        return 3 * d * cfg.d_ff  # gate/up/down (SwiGLU)

    def moe_ffn() -> int:
        n = cfg.experts_per_token if active_only else cfg.local_experts
        shared = 3 * d * cfg.shared_expert_ff
        # experts + shared expert + router
        return n * 3 * d * cfg.d_ff + shared + d * cfg.num_experts

    def mamba_params() -> int:
        d_inner = cfg.ssm_expand * d
        nheads = d_inner // cfg.ssm_head_dim
        in_proj = d * (2 * d_inner + 2 * cfg.ssm_state + nheads)
        conv_dim = d_inner + 2 * cfg.ssm_state
        conv = (cfg.ssm_conv_width + 1) * conv_dim       # weights + bias
        out = d_inner * d
        # + A_log, D, dt_bias and the gated norm's weight
        return in_proj + conv + out + 3 * nheads + d_inner

    total = cfg.vocab_size * d  # embed
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d  # unembed
    norms = 2 * d

    if cfg.arch_type == "ssm":
        total += cfg.num_layers * (mamba_params() + d)
        return total

    for i in range(cfg.num_layers):
        total += attn_params() if cfg.layer_is_attn(i) else mamba_params()
        if cfg.layer_is_moe(i):
            total += moe_ffn()
        elif cfg.d_ff:
            total += dense_ffn()
        total += norms
        if cfg.cross_attn_every and (i % cfg.cross_attn_every) == (
            cfg.cross_attn_every - 1
        ):
            total += attn_params() + d  # gated cross-attention block

    if cfg.is_encoder_decoder:
        # encoder self-attn + ffn, plus decoder cross-attention per layer
        total += cfg.num_encoder_layers * (attn_params() + dense_ffn() + norms)
        total += cfg.num_layers * (attn_params() + d)
    return total


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
