"""jit'd public wrappers around the Pallas kernels.

Each op reshapes model-layout tensors into kernel layout, dispatches to the
Pallas kernel on TPU (or ``interpret=True`` for CPU validation), and falls
back to the pure-jnp blockwise/chunked implementations otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.paged_attention import paged_attention_grouped
from repro.kernels.ssd import ssd_bh
from repro.kernels.wkv6 import wkv6_bh


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "impl", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    impl: str = "auto",  # auto | pallas | interpret | jnp
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Model-layout flash attention."""
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = H // Hkv
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        from repro.models.layers import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, q_offset=Sk - Sq)
    qbh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kbh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vbh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    obh = flash_attention_bh(
        qbh, kbh, vbh,
        group=g, causal=causal, q_offset=Sk - Sq,
        block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"),
    )
    return obh.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("impl",))
def paged_attention(
    q: jax.Array,  # (B, T, H, D) — T freshly written tokens per sequence
    k_pages: jax.Array,  # (P, page_size, Hkv, D) — the KV page pool
    v_pages: jax.Array,  # (P, page_size, Hkv, Dv)
    block_tables: jax.Array,  # (B, n) int32 physical page ids, token order
    lens: jax.Array,  # (B,) int32 valid tokens through each FIRST query
    *,
    impl: str = "auto",  # auto | pallas | interpret | jnp
) -> jax.Array:
    """Model-layout paged-attention decode over a block-table-indexed pool.

    T == 1 is the single-token decode step; T == k+1 is speculative
    decode's verify pass (query ``t`` attends keys ``< lens[b] + t``).
    """
    B, T, H, D = q.shape
    P, _, Hkv, Dv = v_pages.shape
    g = H // Hkv
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        from repro.models.layers import paged_decode_attention

        return paged_decode_attention(q, k_pages, v_pages, block_tables, lens)
    # queries-major row stacking: row t*g + lane matches the kernel's
    # ``t = row // group`` per-row causal mask
    qg = (
        q.reshape(B, T, Hkv, g, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, T * g, D)
    )
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, P - 1)  # DMA-safe padding
    # the kernel reads head-major pages (its module docstring says why)
    obh = paged_attention_grouped(
        qg, k_pages.transpose(0, 2, 1, 3), v_pages.transpose(0, 2, 1, 3),
        bt, lens.astype(jnp.int32),
        num_queries=T,
        interpret=(impl == "interpret"),
    )
    return (
        obh.reshape(B, Hkv, T, g, Dv)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, T, H, Dv)
    )


@partial(jax.jit, static_argnames=("impl", "chunk"))
def wkv6(
    r: jax.Array,  # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,  # (B, S, H, V)
    lw: jax.Array,  # (B, S, H, K)
    u: jax.Array,  # (H, K)
    *,
    impl: str = "auto",
    chunk: int = 128,
) -> jax.Array:
    B, S, H, K = r.shape
    V = v.shape[-1]
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        from repro.models.rwkv import wkv6_chunked

        out, _ = wkv6_chunked(r, k, v, lw, u, chunk=chunk)
        return out
    tb = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])
    ubh = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    obh = wkv6_bh(
        tb(r), tb(k), tb(v), tb(lw), ubh,
        chunk=chunk, interpret=(impl == "interpret"),
    )
    return obh.reshape(B, H, S, V).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("impl", "chunk"))
def ssd(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    la: jax.Array,  # (B, S, H)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    D: jax.Array,  # (H,)
    *,
    impl: str = "auto",
    chunk: int = 128,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl == "jnp":
        from repro.models.ssm import ssd_chunked

        out, _ = ssd_chunked(x, dt, la, Bm, Cm, D, chunk=chunk)
        return out
    xbh = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtbh = dt.transpose(0, 2, 1).reshape(B * H, S)
    labh = la.transpose(0, 2, 1).reshape(B * H, S)
    bbh = jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    cbh = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    dbh = jnp.broadcast_to(D[None], (B, H)).reshape(B * H, 1)
    obh = ssd_bh(
        xbh, dtbh, labh, bbh, cbh, dbh,
        chunk=chunk, interpret=(impl == "interpret"),
    )
    return obh.reshape(B, H, S, P).transpose(0, 2, 1, 3)
