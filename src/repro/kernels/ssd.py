"""Pallas TPU kernel for the Mamba2 SSD chunked recurrence.

Same chunked-sequential pattern as wkv6.py but with *scalar* per-head decay:
the state is carried in VMEM scratch across the sequential chunk grid
dimension; intra-chunk work is four MXU matmuls per chunk, the (C×N)·(N×C)
score tile, the (C×C)·(C×P) combine, the (C×N)·(N×P) read of the carried
state and the (N×C)·(C×P) state update.  The state is kept as (N, P) so
that every product contracts the lhs's last dim with the rhs's first, the
form Mosaic lowers without transposes.

Layouts (ops.py makes them): x (BH, S, P); dt and the cumulative log decay
within each chunk as columns (BH, S, 1), the decay also as a row (BH, 1, S);
C (B, S, N) and B transposed (B, N, S), shared by the H heads of a sequence
(one group) through the index maps; D (BH, 1, 1); initial state (BH, N, P)
float32.  Outputs: y (BH, S, P) and the final state (BH, N, P) float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _ssd_kernel(
    x_ref,  # (1, C, P)
    dt_ref,  # (1, C, 1)
    la_ref,  # (1, C, 1) inclusive cumulative log decay within the chunk
    lar_ref,  # (1, 1, C) the same, as a row
    c_ref,  # (1, C, N)
    bt_ref,  # (1, N, C)
    d_ref,  # (1, 1, 1)
    s0_ref,  # (1, N, P)
    o_ref,  # (1, C, P)
    sf_ref,  # (1, N, P)
    state_scr,  # (N, P) f32
    *,
    num_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (C, P)
    dt = dt_ref[0].astype(jnp.float32)  # (C, 1)
    la = la_ref[0].astype(jnp.float32)  # (C, 1)
    la_row = lar_ref[0].astype(jnp.float32)  # (1, C)
    Cm = c_ref[0].astype(jnp.float32)  # (C, N)
    Bt = bt_ref[0].astype(jnp.float32)  # (N, C)
    P = x.shape[1]
    # (1, 1) values reach (rows, P) tiles in two steps, lanes then sublanes:
    # Mosaic broadcasts along one of the two at a time
    Dh = jnp.broadcast_to(d_ref[0].astype(jnp.float32), (1, P))
    s = state_scr[...]  # (N, P)
    xdt = x * dt  # Δ_s x_s

    # inter-chunk: y_t += exp(la_t) · (C_t · s)
    y_inter = jnp.exp(la) * _dot(Cm, s)
    # intra-chunk: y_t += Σ_{s≤t} exp(la_t - la_s) (C_t·B_s) Δ_s x_s
    cb = _dot(Cm, Bt)  # (C, C) [t, s]
    ti = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
    si = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1)
    # decay diff masked BEFORE exp (≤0 in causal region → overflow-safe)
    dec = jnp.exp(jnp.where(ti >= si, la - la_row, -jnp.inf))
    y_intra = _dot(dec * cb, xdt)
    o_ref[0, :, :] = (y_inter + y_intra + Dh * x).astype(o_ref.dtype)

    # state update: s' = s·exp(la_C) + Σ_s exp(la_C - la_s) B_s ⊗ Δ_s x_s
    laC = la_row[:, -1:]  # (1, 1)
    s_new = (s * jnp.exp(jnp.broadcast_to(laC, (1, P)))
             + _dot(Bt, xdt * jnp.exp(laC - la)))
    state_scr[...] = s_new

    @pl.when(ci == num_chunks - 1)
    def _final():
        sf_ref[0, :, :] = s_new


def ssd_bh(
    x: jax.Array,  # (BH, S, P)
    dt: jax.Array,  # (BH, S, 1)
    la: jax.Array,  # (BH, S, 1) cumulative log decay within each chunk
    la_row: jax.Array,  # (BH, 1, S) the same, as a row
    Cm: jax.Array,  # (B, S, N)
    Bt: jax.Array,  # (B, N, S)
    D: jax.Array,  # (BH, 1, 1)
    s0: jax.Array,  # (BH, N, P) float32
    *,
    heads: int,  # H: BH = B * H, and row bh reads C/B of sequence bh // H
    chunk: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    BH, S, P = x.shape
    N = Cm.shape[-1]
    if S % chunk:
        raise ValueError(f"ssd kernel: {S} tokens are not a multiple of chunk {chunk}")
    n = S // chunk
    kernel = functools.partial(_ssd_kernel, num_chunks=n)
    return pl.pallas_call(
        kernel,
        grid=(BH, n),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh // heads, ci, 0)),
            pl.BlockSpec((1, N, chunk), lambda bh, ci: (bh // heads, 0, ci)),
            pl.BlockSpec((1, 1, 1), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, N, P), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, la, la_row, Cm, Bt, D, s0)
