"""Pallas TPU paged-attention decode kernel (T ≥ 1 query tokens, GQA).

The serving engine stores KV in a page pool ``(P, page_size, Hkv, D)``; a
sequence's cache is the ordered list of physical pages its ``PageTable``
block table names.  This kernel attends a small block of freshly written
query tokens per sequence against a head-major view of that pool
(``ops.paged_attention`` makes it; the end of this docstring says why):
the block table is a **scalar-prefetched** operand, so each grid step's
BlockSpec index_map reads ``bt[b, i]`` and the page gather *is* the DMA
schedule — no dense ``(B, max_len, ...)`` cache is ever materialized, and
sequences pay for the pages they occupy, not for ``max_len``.

The query block covers speculative decode's verify pass: T = k+1 positions
per sequence attend in ONE kernel launch.  Queries stack into the row axis
as ``(T*G, D)`` — row ``r`` is query ``t = r // G``, head-group lane
``g = r % G`` — so the single-token layout (T == 1) is the degenerate case
and compiles to exactly the previous kernel.

Layout: q ``(B, Hkv, T*G, D)`` (T tokens per sequence, q heads grouped by
their kv head, queries-major), k/v pages head-major
``(P, Hkv, page_size, D)``, block tables ``(B, n)`` int32, lens ``(B,)``
int32 — ``lens[b]`` counts valid tokens through the FIRST query's own
position, so query ``t`` attends ``pos < lens[b] + t``.  Grid
``(B, Hkv, n)``: the page axis is sequential, so the online-softmax stats
(m, l, acc) live in VMEM scratch that persists across pages — same
accumulator discipline as flash_attention.  Pages at or beyond every
query's reach are skipped with ``pl.when`` (their DMA still lands on a
valid page — callers pad short block-table rows with any in-range page
id).

The pages are head-major so that one grid step's K/V block is
``(1, 1, page_size, D)``: its last two dims are whole array dims, which
Mosaic accepts.  A ``(1, page_size, 1, D)`` block over the token-major
``(P, page_size, Hkv, D)`` pool puts a one-head slice in the second-minor
dim, and the TPU compiler refuses it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    bt_ref,  # (B, n) int32 scalar-prefetch: the block tables
    lens_ref,  # (B,) int32 scalar-prefetch: valid tokens per sequence
    q_ref,  # (1, 1, T*G, D)
    k_ref,  # (1, 1, page_size, D)
    v_ref,  # (1, 1, page_size, Dv)
    o_ref,  # (1, 1, T*G, Dv)
    m_scr,  # (T*G, 1) f32
    l_scr,  # (T*G, 1) f32
    acc_scr,  # (T*G, Dv) f32
    *,
    scale: float,
    page_size: int,
    num_page_slots: int,
    group: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    num_queries = q_ref.shape[2] // group

    # page entirely past even the LAST query's reach: skip
    @pl.when(i * page_size < seq_len + num_queries - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (T*G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (page_size, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (T*G, page_size)
        pos = i * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row r is query t = r // group: it attends pos < seq_len + t
        t_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(pos < seq_len + t_row, s, NEG_INF)
        m_prev = m_scr[...]  # (T*G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(i == num_page_slots - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)  # lens == 0 → well-defined zeros
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_grouped(
    q: jax.Array,  # (B, Hkv, T*G, D) — queries-major row stacking
    k_pages: jax.Array,  # (P, Hkv, page_size, D) — head-major
    v_pages: jax.Array,  # (P, Hkv, page_size, Dv)
    block_tables: jax.Array,  # (B, n) int32 physical page ids, in token order
    lens: jax.Array,  # (B,) int32 — valid tokens through the first query
    *,
    num_queries: int = 1,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, QG, D = q.shape
    P, _, page_size, Dv = v_pages.shape
    n = block_tables.shape[1]
    if QG % num_queries:
        raise ValueError(f"query rows {QG} not divisible by T={num_queries}")
    G = QG // num_queries
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        page_size=page_size,
        num_page_slots=n,
        group=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables, lens) usable in index_maps
        grid=(B, Hkv, n),
        in_specs=[
            pl.BlockSpec((1, 1, QG, D), lambda b, h, i, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page_size, D), lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, page_size, Dv), lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, QG, Dv), lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((QG, 1), jnp.float32),
            pltpu.VMEM((QG, 1), jnp.float32),
            pltpu.VMEM((QG, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, QG, Dv), v_pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables, lens, q, k_pages, v_pages)
