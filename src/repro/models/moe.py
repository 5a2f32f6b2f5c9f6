"""Mixture-of-Experts layer with shard_map expert parallelism.

Dispatch strategy ("replicated-dispatch EP", chosen for TPU):
activations between blocks are replicated across the ``model`` axis (Megatron
style), so every model shard already holds every local token.  Each shard
therefore *selects* the tokens routed to its local experts (gather), runs
the expert FFNs, scatter-adds weighted outputs, and a single
``psum(model)`` combines expert contributions — the same collective shape
as a TP FFN all-reduce.  No giant one-hot dispatch einsums (which would
dominate HLO FLOPs) and no data-dependent all-to-all.

Capacity: per data-shard ``C = ceil(T_loc · top_k / E · capacity_factor)``;
overflow tokens drop (standard Switch-style behaviour, cf. DESIGN.md).
Router: softmax top-k with normalized gates + load-balance aux loss.
(deepseek-v3's bias-based aux-free routing is replaced by aux-loss routing —
recorded simplification.)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist.sharding import ParamSpec
from repro.models.layers import ModelContext


def moe_specs(cfg: ArchConfig) -> dict:
    E, X, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    s = {
        "router": ParamSpec((E, X), (None, None), jnp.float32),
        "wg": ParamSpec((X, E, F), ("expert", "embed", "mlp")),
        "wi": ParamSpec((X, E, F), ("expert", "embed", "mlp")),
        "wo": ParamSpec((X, F, E), ("expert", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        s["shared"] = {
            "wg": ParamSpec((E, Fs), ("embed", "mlp")),
            "wi": ParamSpec((E, Fs), ("embed", "mlp")),
            "wo": ParamSpec((Fs, E), ("mlp", "embed")),
        }
    return s


_DUMMY_AXIS = "__no_axis__"  # single-shard fallback (no mesh model axis)


def _local_moe(cfg: ArchConfig, model_axis: str, batch_axes: tuple[str, ...],
               x, router_w, wg, wi, wo):
    """Per-shard body (runs inside shard_map).  x: (T_loc, D) local tokens,
    replicated over the model axis; expert weights: local (X_loc, ·, ·)."""
    T_loc, D = x.shape
    X_loc = wg.shape[0]
    X = cfg.n_experts
    k = cfg.top_k
    C = max(1, math.ceil(T_loc * k / X * cfg.capacity_factor))

    if model_axis == _DUMMY_AXIS:
        lo = 0
    else:
        lo = jax.lax.axis_index(model_axis) * X_loc

    logits = jnp.einsum("td,dx->tx", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)  # (T_loc, X)
    gates, ids = jax.lax.top_k(probs, k)  # (T_loc, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (global over the data axes)
    density = jnp.zeros((X,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T_loc * k)
    pbar = probs.mean(0)
    if batch_axes:
        density = jax.lax.pmean(density, batch_axes)
        pbar = jax.lax.pmean(pbar, batch_axes)
    aux = X * jnp.sum(density * pbar)

    # --- dispatch: select local-expert tokens into (X_loc, C) slots -------
    flat_ids = ids.reshape(-1)  # (T_loc*k,)
    flat_gates = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(T_loc), k)
    lid = flat_ids - lo
    valid = (lid >= 0) & (lid < X_loc)
    one_hot = jnp.where(valid[:, None], jax.nn.one_hot(lid, X_loc, dtype=jnp.int32), 0)
    pos = jnp.cumsum(one_hot, axis=0) * one_hot  # 1-based position per expert
    pos = (pos.sum(-1) - 1)  # (T_loc*k,) position of this pair in its expert
    keep = valid & (pos >= 0) & (pos < C)
    lid_w = jnp.where(keep, lid, X_loc)  # overflow → scratch row
    pos_w = jnp.where(keep, pos, 0)

    slot_tok = jnp.full((X_loc + 1, C), T_loc, jnp.int32)  # sentinel → zero row
    slot_tok = slot_tok.at[lid_w, pos_w].set(
        jnp.where(keep, tok_idx, T_loc).astype(jnp.int32)
    )
    slot_gate = jnp.zeros((X_loc + 1, C), jnp.float32)
    slot_gate = slot_gate.at[lid_w, pos_w].set(jnp.where(keep, flat_gates, 0.0))
    slot_tok, slot_gate = slot_tok[:X_loc], slot_gate[:X_loc]

    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], 0)
    xin = x_pad[slot_tok]  # (X_loc, C, D)

    h = jax.nn.silu(jnp.einsum("xcd,xdf->xcf", xin, wg)) * jnp.einsum(
        "xcd,xdf->xcf", xin, wi
    )
    y = jnp.einsum("xcf,xfd->xcd", h, wo)  # (X_loc, C, D)
    y = y * slot_gate[..., None].astype(y.dtype)

    out = jnp.zeros((T_loc + 1, D), y.dtype).at[slot_tok.reshape(-1)].add(
        y.reshape(-1, D)
    )[:T_loc]
    if model_axis != _DUMMY_AXIS:
        out = jax.lax.psum(out, model_axis)
    return out, aux


def apply_moe(ctx: ModelContext, params: dict, x: jax.Array):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    cfg = ctx.cfg
    B, S, D = x.shape
    batch_axes = ctx.batch_axes
    mesh = ctx.mesh
    model_axis = "model" if "model" in mesh.shape else None

    xf = x.reshape(B * S, D)

    if model_axis is None or mesh.shape[model_axis] == 1 or cfg.n_experts == 1:
        # single-shard fallback (smoke tests): dense loop over experts
        out, aux = _local_moe(
            cfg, _DUMMY_AXIS, (), xf, params["router"],
            params["wg"], params["wi"], params["wo"],
        )
    else:
        bspec = P(batch_axes if batch_axes else None, None)
        f = jax.shard_map(
            partial(_local_moe, cfg, model_axis, batch_axes),
            mesh=mesh,
            in_specs=(
                bspec,  # x: tokens sharded over batch axes, replicated on model
                P(None, None),  # router: replicated
                P("model", None, None),  # wg
                P("model", None, None),  # wi
                P("model", None, None),  # wo
            ),
            out_specs=(bspec, P()),
            check_vma=False,
        )
        out, aux = f(xf, params["router"], params["wg"], params["wi"], params["wo"])

    out = out.reshape(B, S, D).astype(x.dtype)
    if cfg.n_shared_experts:
        from repro.models.layers import apply_mlp

        out = out + apply_mlp(ctx, params["shared"], x)
    return ctx.constrain(out, ("batch", None, None)), aux
