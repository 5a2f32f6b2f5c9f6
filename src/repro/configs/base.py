"""Unified architecture configuration for the assigned model pool."""
from __future__ import annotations

from dataclasses import dataclass, field, replace


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | mla_moe | encdec | rwkv | hybrid | mamba_attn
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # common
    head_dim: int = 0  # 0 → d_model // n_heads
    rope_theta: float = 10_000.0
    rotary_pct: float = 1.0  # stablelm uses 0.25
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    tie_embeddings: bool = False
    vocab_pad_multiple: int = 256  # Megatron-style padding for TP divisibility

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    first_k_dense: int = 0  # leading dense layers (deepseek-v3: 3)
    router_aux_weight: float = 0.001

    # MLA (deepseek-v3)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    use_mtp: bool = False  # multi-token prediction head (depth 1)

    # M-RoPE (qwen2-vl)
    use_mrope: bool = False
    mrope_sections: tuple[int, ...] = ()
    vision_embeds: int = 0  # stub frontend: number of precomputed patch embeds

    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_frames: int = 0  # stub frontend: precomputed frame embeddings

    # RWKV6
    rwkv_head_dim: int = 64

    # Mamba2 / Zamba2 hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    shared_attn_every: int = 0  # zamba2: shared block cadence
    ssm_conv_bias: bool = False  # bias on the causal conv (granite-4.0-h)

    # Mamba2 layers with attention at a fixed period (granite-4.0-h): layer
    # i is attention when i % attn_layer_period == attn_layer_offset
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # Granite's scalings: the embedding, each residual branch and the logits
    # (divided by logits_scaling); attention_multiplier is the softmax scale,
    # 0 meaning 1/sqrt(head dim)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    # training
    dtype: str = "bfloat16"
    remat: str = "full"  # none | full | dots (activation-checkpoint policy)
    scan_layers: bool = True
    # beyond-paper perf levers (default off = paper-faithful baseline)
    attn_causal_skip: bool = False  # skip fully-masked KV chunks (≈½ attn FLOPs)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab, self.vocab_pad_multiple)

    @property
    def attn_free(self) -> bool:
        return self.family == "rwkv"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid / linear-attention)."""
        return self.family in ("rwkv", "hybrid")

    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)


@dataclass(frozen=True)
class ShapeCell:
    """One (input-shape) column of the assignment."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}


def cell_applicable(cfg: ArchConfig, shape: ShapeCell) -> tuple[bool, str]:
    """Whether an (arch × shape) cell runs; else the recorded skip reason."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: long_500k needs sub-quadratic attention (skip per assignment)"
    return True, ""
