"""Operations and bytes that each kernel and each whole step needs, counted
from the model's shapes and the live lengths of the traffic, whatever
implements them.  Padding (prompt rows past their length, idle slots, pages
past a sequence's end) is not needed work.

A matrix product of (m, k) by (k, n) is 2mkn operations.  Bytes are those a
kernel must read and write once in the served type (2 bytes, bfloat16).
"""
from __future__ import annotations

DTYPE_BYTES = 2


def _dims(arch: dict):
    E, H = arch["hidden_size"], arch["num_attention_heads"]
    return (E, H, arch["num_key_value_heads"], E // H,
            arch["intermediate_size"], arch["num_hidden_layers"], arch["vocab_size"])


def dense_flops_per_token(arch: dict) -> float:
    """Projections and MLP of every layer, for one token."""
    E, H, Hkv, D, F, L, _ = _dims(arch)
    return 2.0 * L * (E * H * D + 2 * E * Hkv * D + H * D * E + 3 * E * F)


def head_flops(arch: dict) -> float:
    """The output head for one token, over the real vocabulary."""
    E, *_, V = _dims(arch)
    return 2.0 * E * V


def flash_attention(arch: dict, prompt_lens) -> tuple[float, float]:
    """Causal self-attention over each prompt, every layer: (ops, bytes)."""
    E, H, Hkv, D, F, L, V = _dims(arch)
    flops = bytes_ = 0.0
    for s in prompt_lens:
        flops += L * 4.0 * H * D * s * (s + 1) / 2  # QK^T and PV, lower triangle
        bytes_ += L * s * (2 * H + 2 * Hkv) * D * DTYPE_BYTES  # q, k, v in; o out
    return flops, bytes_


def paged_attention(arch: dict, ctx_lens) -> tuple[float, float]:
    """One decode query per sequence over its ``ctx`` live cached tokens,
    every layer: (ops, bytes)."""
    E, H, Hkv, D, F, L, V = _dims(arch)
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += L * 4.0 * H * D * c
        bytes_ += L * (2 * c * Hkv * D + 2 * H * D) * DTYPE_BYTES  # K, V; q, o
    return flops, bytes_


def prefill_flops(arch: dict, prompt_lens) -> float:
    """A prefill of these prompts: every token through every layer, and the
    head at each prompt's last token (the only logits it needs)."""
    attn, _ = flash_attention(arch, prompt_lens)
    return sum(prompt_lens) * dense_flops_per_token(arch) + attn + len(prompt_lens) * head_flops(arch)


def decode_flops(arch: dict, ctx_lens) -> float:
    """Decode tokens, each at its context length (keys it attends)."""
    attn, _ = paged_attention(arch, ctx_lens)
    return len(ctx_lens) * (dense_flops_per_token(arch) + head_flops(arch)) + attn


def least_time(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
