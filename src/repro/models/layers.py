"""Shared layer library for the model zoo.

Functional style: each block exposes ``*_specs(cfg) -> pytree[ParamSpec]``
and ``apply_*(ctx, params, ...)``.  Parameters are declared with *logical*
axes (see dist/sharding.py) so the same definitions shard on any mesh.

Attention is computed blockwise (flash-style online softmax in pure jnp) so
32k-token prefill never materializes an (S×S) score matrix; the Pallas
flash-attention kernel (kernels/) is the TPU fast path behind the same API.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist.sharding import AxisRules, ParamSpec, shard_constraint


@dataclass
class ModelContext:
    """Everything ``apply_*`` needs besides params."""

    cfg: ArchConfig
    mesh: Mesh
    rules: AxisRules
    use_kernels: bool = False  # Pallas path (TPU); jnp blockwise otherwise
    page_size: int = 16  # KV page the decode kernel reads (engine's pool)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    def constrain(self, x, axes):
        return shard_constraint(x, axes, self.rules, self.mesh)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def scan_stack(cfg: ArchConfig, body, carry, xs):
    """``lax.scan`` over stacked layer params — or a Python unroll when
    ``cfg.scan_layers`` is False.

    Every layer-stack loop in the model zoo must go through this helper:
    the dry-run's roofline probes lower reduced-depth UNROLLED variants
    (``scan_layers=False``) because XLA's cost_analysis visits a while-loop
    body once, not trip-count times.  A path that scans unconditionally
    silently under-reports FLOPs/bytes by ~n_layers×.
    """
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    # stack per-layer outputs exactly like scan would (None-trees stay None)
    stacked = jax.tree.map(lambda *a: jnp.stack(a, 0), *ys) if ys else None
    return carry, stacked


def norm_specs(cfg: ArchConfig, d: int) -> dict:
    s = {"scale": ParamSpec((d,), (None,), jnp.float32, init_scale=1.0)}
    if cfg.norm == "layernorm":
        s["bias"] = ParamSpec((d,), (None,), jnp.float32, init_scale=0.0)
    return s


def apply_norm(cfg: ArchConfig, params: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * params["scale"] + params["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * params["scale"]
    return out.astype(x.dtype)


def rmsnorm_nogain(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf**2, -1, keepdims=True) + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (incl. partial rotary and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def rope_cos_sin(positions: jax.Array, dim: int, theta: float):
    """positions (..., S) → cos/sin (..., S, dim/2)."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(dim, theta)
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions: jax.Array, dim: int, theta: float, sections):
    """M-RoPE: positions (3, B, S); frequency dims split into ``sections``
    (t, h, w), each rotated by its own position stream (arXiv:2409.12191)."""
    freqs = rope_freqs(dim, theta)  # (dim/2,)
    ang_all = positions[..., None].astype(jnp.float32) * freqs  # (3, B, S, dim/2)
    idx = []
    for i, sec in enumerate(sections):
        idx.extend([i] * sec)
    sel = np.asarray(idx)  # (dim/2,) which position stream each freq uses
    ang = jnp.where(sel == 0, ang_all[0], jnp.where(sel == 1, ang_all[1], ang_all[2]))
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, rotary_dim: int):
    """x (B, S, H, D); cos/sin (B, S, rotary_dim/2) — rotate first rotary_dim."""
    if rotary_dim == 0:
        return x
    xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot, xp], axis=-1)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------


def _attn_chunks(seq: int, target: int) -> int:
    """Largest divisor of ``seq`` that is ≤ ``target`` (chunk size).

    A full divisor scan matters for awkward lengths: whisper's 1500-frame
    encoder gets 750 (2 chunks) instead of 4 (375 chunks of 4 — a
    scheduling and MXU-utilization disaster).
    """
    for c in range(min(target, seq), 0, -1):
        if seq % c == 0:
            return c
    return 1


def blockwise_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    scale: float | None = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    unroll: bool = False,
    causal_skip: bool = False,
) -> jax.Array:
    """Flash-style attention in pure jnp: online softmax over KV chunks,
    outer map over Q chunks.  Never materializes (Sq × Sk).  GQA handled by
    grouped einsum (no KV repetition).

    ``unroll=True`` replaces the chunk scan/map with Python loops (identical
    math) so XLA cost_analysis sees every chunk — required by the dry-run's
    roofline probes, which measure reduced-seq unrolled variants.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qc = _attn_chunks(Sq, q_chunk)
    kc = _attn_chunks(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc

    qg = q.reshape(B, Sq, Hkv, G, D)
    q_pos_base = jnp.arange(qc)
    k_pos_base = jnp.arange(kc)

    def q_block(carry_i):
        i, = carry_i
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=1)  # (B,qc,Hkv,G,D)
        q_pos = q_pos_base + i * qc + q_offset

        def kv_step(state, j):
            m, l, acc = state
            kj = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=1)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi, kj, preferred_element_type=jnp.float32
            ) * scale
            if causal:
                k_pos = k_pos_base + j * kc
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        nk_live = nk
        if causal and causal_skip and isinstance(i, int) and isinstance(q_offset, int):
            # beyond-paper optimization: KV chunks entirely above the causal
            # diagonal contribute nothing — skip them statically.  Halves
            # attention FLOPs for prefill/train (the scanned-over-q version
            # must run every chunk and mask).
            nk_live = min(nk, (i * qc + qc - 1 + q_offset) // kc + 1)
        if unroll:
            st = (m0, l0, a0)
            for j in range(nk_live):
                st, _ = kv_step(st, j)
            m, l, acc = st
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), jnp.arange(nk_live)
            )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, qc, H, Dv)

    if nq == 1:
        out = q_block((0,))
    elif unroll or (causal and causal_skip and isinstance(q_offset, int)):
        # static python loop over q blocks: each block sees its own (static)
        # number of live KV chunks; program size grows by nq — acceptable at
        # nq ≤ 32 and required for the causal skip.
        out = jnp.concatenate([q_block((i,)) for i in range(nq)], axis=1)
    else:
        out = jax.lax.map(lambda i: q_block((i,)), jnp.arange(nq))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dv)
    return out.astype(v.dtype)


def decode_attention(
    q: jax.Array,  # (B, T, H, D) — T freshly written decode tokens
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, Dv)
    length: jax.Array,  # (,) valid length through the FIRST query's position
    *,
    scale: float | None = None,
) -> jax.Array:
    """Attention for T ≥ 1 decode tokens over a (possibly sequence-sharded)
    KV cache.  ``length`` counts valid cache entries through the first
    query's own position (``cache_index + 1``); query ``t`` additionally
    sees the ``t`` queries written before it, i.e. attends keys
    ``< length + t``.  T == 1 is the classic single-token decode step;
    T == k+1 is the speculative verify pass."""
    B, T, H, D = q.shape
    _, S, Hkv, Dv = v_cache.shape
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k_cache, preferred_element_type=jnp.float32
    )
    s = s * scale
    mask = jnp.arange(S)[None, :] < (length + jnp.arange(T))[:, None]  # (T, S)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgts,bshd->bthgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, T, H, Dv).astype(v_cache.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, T, H, D) — T freshly written decode tokens
    k_pages: jax.Array,  # (P, page_size, Hkv, D)
    v_pages: jax.Array,  # (P, page_size, Hkv, Dv)
    block_tables: jax.Array,  # (B, n) int32 physical page ids, token order
    lens: jax.Array,  # (B,) valid tokens through each row's FIRST query
    *,
    scale: float | None = None,
) -> jax.Array:
    """jnp reference for the paged decode kernel: gather each sequence's
    pages through its block table into a contiguous view, then attend with
    a per-sequence length mask.  Query ``t`` of row ``b`` sits at absolute
    position ``lens[b] - 1 + t`` and attends keys ``< lens[b] + t`` (T == 1
    is the single-token decode step, T == k+1 the speculative verify).
    ``lens[b] == 0`` rows produce garbage (a uniform average), never NaN —
    idle serving slots are unread anyway."""
    B, T, H, D = q.shape
    P, ps, Hkv, Dv = v_pages.shape
    n = block_tables.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bt = jnp.clip(block_tables, 0, P - 1)
    k = k_pages[bt].reshape(B, n * ps, Hkv, k_pages.shape[-1])
    v = v_pages[bt].reshape(B, n * ps, Hkv, Dv)
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32)
    s = s * scale
    mask = (
        jnp.arange(n * ps)[None, None, :]
        < (lens[:, None] + jnp.arange(T)[None, :])[:, :, None]
    )  # (B, T, n*ps)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgts,bshd->bthgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, T, H, Dv).astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (dense/MoE/encdec/hybrid families)
# ---------------------------------------------------------------------------


def _decode_attention_core(ctx: "ModelContext", q, k_cache, v_cache, length):
    """Decode-step dispatch: when kernels are enabled, view the dense
    per-slot cache as contiguous ``ctx.page_size`` pages (an arange block
    table) and run the paged-attention kernel; else the plain masked jnp
    decode attention.  A shape the kernel cannot take raises rather than
    quietly running jnp.  Handles T ≥ 1 query tokens (q ``(B, T, H, D)``):
    both backends mask query ``t`` to keys ``< length + t``."""
    B, S, Hkv, Dv = v_cache.shape
    if not ctx.use_kernels:
        return decode_attention(q, k_cache, v_cache, length)
    ps = ctx.page_size
    if q.shape[-1] != Dv or S % ps:
        raise ValueError(
            f"paged decode kernel cannot take q head dim {q.shape[-1]} / v "
            f"head dim {Dv} over a {S}-token cache in {ps}-token pages"
        )
    from repro.kernels.ops import paged_attention

    n = S // ps
    kp = k_cache.reshape(B * n, ps, Hkv, k_cache.shape[-1])
    vp = v_cache.reshape(B * n, ps, Hkv, Dv)
    bt = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    lens = jnp.full((B,), length, jnp.int32)
    return paged_attention(q, kp, vp, bt, lens)


def _attention_core(ctx: "ModelContext", q, k, v, *, causal: bool,
                    scale: float | None = None):
    """Dispatch: Pallas flash-attention kernel (TPU / interpret) when
    ``ctx.use_kernels``, else the pure-jnp blockwise path.  The kernel
    takes a uniform head dim, the default scale and scanned layers; any
    other shape raises rather than quietly running jnp."""
    cfg = ctx.cfg
    if ctx.use_kernels:
        Sq, Sk = q.shape[1], k.shape[1]
        if (scale is not None or q.shape[-1] != v.shape[-1]
                or not cfg.scan_layers
                or (Sq > 128 and Sq % 128) or (Sk > 128 and Sk % 128)):
            raise ValueError(
                f"flash-attention kernel cannot take q {q.shape} / v "
                f"{v.shape} (scale={scale}, scan_layers={cfg.scan_layers}): "
                "it needs a uniform head dim, the default scale, scanned "
                "layers and sequences of at most 128 or a multiple of 128"
            )
        from repro.kernels.ops import flash_attention

        return flash_attention(q, k, v, causal=causal)
    return blockwise_attention(q, k, v, causal=causal, scale=scale,
                               unroll=not cfg.scan_layers,
                               causal_skip=cfg.attn_causal_skip)


def attention_specs(cfg: ArchConfig, *, cross: bool = False) -> dict:
    E, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    return {
        "wq": ParamSpec((E, H, Dh), ("embed", "heads", None)),
        "wk": ParamSpec((E, Hkv, Dh), ("embed", "kv_heads", None)),
        "wv": ParamSpec((E, Hkv, Dh), ("embed", "kv_heads", None)),
        "wo": ParamSpec((H, Dh, E), ("heads", None, "embed")),
    }


def apply_attention(
    ctx: ModelContext,
    params: dict,
    x: jax.Array,  # (B, S, E)
    *,
    rope: tuple | None = None,  # (cos, sin) or None
    kv: jax.Array | None = None,  # cross-attention source (B, Skv, E)
    causal: bool = True,
    cache: dict | None = None,  # {"k","v"} (B, Smax, Hkv, Dh) + decode
    cache_index: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    cfg = ctx.cfg
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    rotary_dim = int(cfg.rotary_pct * Dh) if cfg.rotary_pct else 0
    rotary_dim -= rotary_dim % 2

    src = x if kv is None else kv
    q = jnp.einsum("bse,ehd->bshd", x, params["wq"])
    k = jnp.einsum("bse,ehd->bshd", src, params["wk"])
    v = jnp.einsum("bse,ehd->bshd", src, params["wv"])

    if rope is not None and rotary_dim:
        cos, sin = rope
        if cache_index is not None:
            # decode: rotate q at absolute position cache_index
            q = apply_rope(q, cos, sin, rotary_dim)
            k = apply_rope(k, cos, sin, rotary_dim)
        else:
            q = apply_rope(q, cos, sin, rotary_dim)
            k = apply_rope(k, cos, sin, rotary_dim)

    new_cache = None
    if cache is not None:
        if cache_index is not None:  # decode step: append one token
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, 1)
            new_cache = {"k": k_cache, "v": v_cache}
            o = _decode_attention_core(ctx, q, k_cache, v_cache, cache_index + 1)
        else:  # prefill: fill cache, run blockwise
            new_cache = {"k": k, "v": v}
            o = _attention_core(ctx, q, k, v, causal=causal)
    else:
        o = _attention_core(ctx, q, k, v, causal=causal)

    out = jnp.einsum("bshd,hde->bse", o, params["wo"])
    return ctx.constrain(out, ("batch", "seq", None)), new_cache


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v3)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ArchConfig) -> dict:
    E, H = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "w_dq": ParamSpec((E, ql), ("embed", None)),
        "q_norm": ParamSpec((ql,), (None,), jnp.float32, init_scale=1.0),
        "w_uq": ParamSpec((ql, H, dn + dr), (None, "heads", None)),
        "w_dkv": ParamSpec((E, kvl), ("embed", None)),
        "kv_norm": ParamSpec((kvl,), (None,), jnp.float32, init_scale=1.0),
        "w_kr": ParamSpec((E, dr), ("embed", None)),
        "w_uk": ParamSpec((kvl, H, dn), (None, "heads", None)),
        "w_uv": ParamSpec((kvl, H, dv), (None, "heads", None)),
        "wo": ParamSpec((H, dv, E), ("heads", None, "embed")),
    }


def _mla_qkr(ctx, params, x, rope):
    """Shared q / rope-key computation."""
    cfg = ctx.cfg
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = rmsnorm_nogain(jnp.einsum("bse,eq->bsq", x, params["w_dq"])) * params[
        "q_norm"
    ].astype(x.dtype)
    q = jnp.einsum("bsq,qhd->bshd", cq, params["w_uq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    k_rope = jnp.einsum("bse,ed->bsd", x, params["w_kr"])[:, :, None, :]  # 1 head
    cos, sin = rope
    q_rope = apply_rope(q_rope, cos, sin, dr)
    k_rope = apply_rope(k_rope, cos, sin, dr)
    return q_nope, q_rope, k_rope


def apply_mla(
    ctx: ModelContext,
    params: dict,
    x: jax.Array,
    *,
    rope: tuple,
    cache: dict | None = None,  # {"ckv": (B,Smax,kvl), "kr": (B,Smax,1,dr)}
    cache_index: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention.  Cache stores only the compressed
    (c_kv, k_rope) — MLA's memory saving.  Decode uses weight absorption."""
    cfg = ctx.cfg
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q_nope, q_rope, k_rope = _mla_qkr(ctx, params, x, rope)
    ckv = rmsnorm_nogain(jnp.einsum("bse,ek->bsk", x, params["w_dkv"])) * params[
        "kv_norm"
    ].astype(x.dtype)

    new_cache = None
    if cache is not None and cache_index is not None:
        # -- decode: absorbed attention over compressed cache --------------
        ckv_c = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, cache_index, 1)
        kr_c = jax.lax.dynamic_update_slice_in_dim(cache["kr"], k_rope, cache_index, 1)
        new_cache = {"ckv": ckv_c, "kr": kr_c}
        # absorb W_uk into q: q_eff (B,1,H,kvl)
        q_eff = jnp.einsum("bshd,khd->bshk", q_nope, params["w_uk"])
        s = jnp.einsum("bshk,btk->bhst", q_eff, ckv_c, preferred_element_type=jnp.float32)
        s += jnp.einsum(
            "bshd,btod->bhst", q_rope, kr_c, preferred_element_type=jnp.float32
        )
        S = ckv_c.shape[1]
        Sq = x.shape[1]
        # query t (of Sq freshly written tokens) attends keys < index+1+t
        mask = jnp.arange(S)[None, :] < (cache_index + 1 + jnp.arange(Sq))[:, None]
        s = jnp.where(mask[None, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btk->bshk", p.astype(ckv_c.dtype), ckv_c)
        o = jnp.einsum("bshk,khd->bshd", o_lat, params["w_uv"])
    else:
        # -- train/prefill: expanded attention ------------------------------
        k_nope = jnp.einsum("bsk,khd->bshd", ckv, params["w_uk"])
        v = jnp.einsum("bsk,khd->bshd", ckv, params["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, dr))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        # MLA has qk-dim 192 ≠ v-dim 128 → always the jnp blockwise path
        # (the Pallas kernel assumes a uniform head dim).
        o = _attention_core(ctx, q, k, v, causal=True, scale=scale)
        if cache is not None:
            new_cache = {"ckv": ckv, "kr": k_rope}

    out = jnp.einsum("bshd,hde->bse", o, params["wo"])
    return ctx.constrain(out, ("batch", "seq", None)), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ArchConfig, d_ff: int | None = None, gated: bool = True) -> dict:
    E, F = cfg.d_model, d_ff or cfg.d_ff
    s = {
        "wi": ParamSpec((E, F), ("embed", "mlp")),
        "wo": ParamSpec((F, E), ("mlp", "embed")),
    }
    if gated:
        s["wg"] = ParamSpec((E, F), ("embed", "mlp"))
    return s


def apply_mlp(ctx: ModelContext, params: dict, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bse,ef->bsf", x, params["wi"])
    if "wg" in params:
        g = jnp.einsum("bse,ef->bsf", x, params["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("bsf,fe->bse", h, params["wo"])
    return ctx.constrain(out, ("batch", "seq", None))


# ---------------------------------------------------------------------------
# Embedding / logits / loss
# ---------------------------------------------------------------------------


def embed_specs(cfg: ArchConfig) -> dict:
    s = {
        "embedding": ParamSpec(
            (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), init_scale=0.02
        )
    }
    if not cfg.tie_embeddings:
        s["unembed"] = ParamSpec(
            (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), init_scale=0.02
        )
    return s


def apply_embed(ctx: ModelContext, params: dict, tokens: jax.Array) -> jax.Array:
    out = jnp.take(params["embedding"], tokens, axis=0)
    return ctx.constrain(out.astype(ctx.compute_dtype), ("batch", "seq", None))


def apply_unembed(ctx: ModelContext, params: dict, x: jax.Array) -> jax.Array:
    if "unembed" in params:
        logits = jnp.einsum("bse,ev->bsv", x, params["unembed"])
    else:
        logits = jnp.einsum("bse,ve->bsv", x, params["embedding"])
    return ctx.constrain(logits, ("batch", None, "vocab"))


def cross_entropy(
    ctx: ModelContext,
    logits: jax.Array,
    labels: jax.Array,
    *,
    z_weight: float = 1e-4,
) -> jax.Array:
    """Next-token CE in fp32 with z-loss; padded-vocab columns masked.

    ``labels < 0`` positions (padding / vision-prefix) are excluded.
    """
    cfg = ctx.cfg
    lg = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        lg = jnp.where(pad_mask, lg, -1e30)
    valid = labels >= 0
    safe_labels = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, safe_labels[..., None], axis=-1)[..., 0]
    per_tok = (lse - gold) + z_weight * jnp.square(lse)
    per_tok = jnp.where(valid, per_tok, 0.0)
    denom = jnp.maximum(valid.sum(), 1)
    return per_tok.sum() / denom
