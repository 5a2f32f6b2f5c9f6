"""Pallas TPU kernels for the model zoo's compute hot spots.

- flash_attention: GQA causal flash attention (dense/MoE/VLM families)
- wkv6: RWKV6 chunked data-dependent-decay recurrence
- ssd: Mamba2 state-space-dual chunked recurrence

Each has a pure-jnp oracle in ref.py and a jit'd dispatch wrapper in ops.py
(pallas on TPU, interpret=True for CPU validation, jnp fallback).
"""
from repro.kernels import ops, ref  # noqa: F401
