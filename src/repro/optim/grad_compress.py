"""Gradient compression for cross-pod (DCN) all-reduce.

int8 symmetric quantization with error feedback (1-bit-Adam-style residual
carry).  On the multi-pod mesh the ``pod`` axis crosses DCN — its gradient
all-reduce is the slowest collective — so compressing that hop 4×
(bf16→int8 including scales) is the standard distributed-optimization
trick.  ``compressed_psum`` is a shard_map building block: quantize →
psum(int32) → dequantize, with the quantization error fed back into the
next step's gradient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8: returns (q, scale)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(g: jax.Array, err: jax.Array):
    """Quantize (g + carried error); return (q, scale, new_error)."""
    g32 = g.astype(jnp.float32) + err
    q, scale = quantize_int8(g32)
    new_err = g32 - dequantize_int8(q, scale)
    return q, scale, new_err


def compressed_psum(g: jax.Array, err: jax.Array, axis: str):
    """int8 all-reduce over ``axis`` with error feedback.

    Must be called inside shard_map with ``axis`` in scope.  The wire format
    is int32 (XLA psum of int8 accumulates exactly in int32 for ≤ 2^23
    shards) + one f32 scale per shard (psum'd — equivalent to max-scale
    broadcast for symmetric quant when combined linearly per-shard).
    """
    # Quantize directly at the SHARED scale s_max = max_i s_i (one pmax of a
    # scalar), so the error feedback carries exactly what this shard failed
    # to contribute — quantizing at a local scale and re-rescaling would
    # leave the re-rescale error out of the residual.
    g32 = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    s_max = jax.lax.pmax(scale, axis)
    q = jnp.clip(jnp.round(g32 / s_max), -127, 127).astype(jnp.int8)
    new_err = g32 - q.astype(jnp.float32) * s_max
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    mean = total.astype(jnp.float32) * s_max / n
    return mean.astype(g.dtype), new_err


def tree_compressed_pmean(grads, errs, axis: str):
    """Apply compressed_psum leaf-wise over a gradient pytree."""
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(errs)
    out, new_errs = [], []
    for g, e in zip(flat_g, flat_e):
        m, ne = compressed_psum(g, e, axis)
        out.append(m)
        new_errs.append(ne)
    return tdef.unflatten(out), tdef.unflatten(new_errs)
