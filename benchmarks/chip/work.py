"""Operations and bytes that each kernel and each whole step needs, counted
from the model's shapes and the live lengths of the traffic, whatever
implements them.  Padding (prompt rows past their length, idle slots, pages
past a sequence's end) is not needed work.

A matrix product of (m, k) by (k, n) is 2mkn operations.  Bytes are those a
kernel must read and write once in the served type (2 bytes, bfloat16).

The per-architecture counts live in ``archs/<model_type>.py``:
``dense_flops_per_token(arch)`` and ``head_flops(arch)`` for one token,
``flash_attention(arch, prompt_lens)`` and ``paged_attention(arch,
ctx_lens)`` as (ops, bytes).  ``Work`` sums them into whole steps.
"""
from __future__ import annotations

DTYPE_BYTES = 2


class Work:
    """An architecture's counts and the sums built on them, under the names
    the per-layer readers call (``Window.work``)."""

    def __init__(self, model):
        self.dense_flops_per_token = model.dense_flops_per_token
        self.head_flops = model.head_flops
        self.flash_attention = model.flash_attention
        self.paged_attention = model.paged_attention
        self.least_time = least_time

    def prefill_flops(self, arch: dict, prompt_lens) -> float:
        """A prefill of these prompts: every token through every layer, and
        the head at each prompt's last token (the only logits it needs)."""
        attn, _ = self.flash_attention(arch, prompt_lens)
        return (sum(prompt_lens) * self.dense_flops_per_token(arch) + attn
                + len(prompt_lens) * self.head_flops(arch))

    def decode_flops(self, arch: dict, ctx_lens) -> float:
        """Decode tokens, each at its context length (keys it attends)."""
        attn, _ = self.paged_attention(arch, ctx_lens)
        return len(ctx_lens) * (self.dense_flops_per_token(arch) + self.head_flops(arch)) + attn


def least_time(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
