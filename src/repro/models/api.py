"""Unified model API: ``build_model(ctx)`` + ``input_specs(...)``.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input —
weak-type-correct, shardable, no device allocation — used by the multi-pod
dry-run and the roofline harness.  Modality frontends are stubs per the
assignment: whisper gets precomputed frame embeddings, qwen2-vl gets
precomputed patch embeddings + (t, h, w) M-RoPE position ids.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models.encdec import EncDecLM
from repro.models.hybrid import HybridLM
from repro.models.layers import ModelContext
from repro.models.rwkv import RWKV6LM
from repro.models.ssm import Zamba2LM
from repro.models.transformer import DecoderLM


def build_model(ctx: ModelContext):
    fam = ctx.cfg.family
    if fam in ("dense", "moe", "mla_moe"):
        return DecoderLM(ctx)
    if fam == "encdec":
        return EncDecLM(ctx)
    if fam == "rwkv":
        return RWKV6LM(ctx)
    if fam == "hybrid":
        return Zamba2LM(ctx)
    if fam == "mamba_attn":
        return HybridLM(ctx)
    raise ValueError(f"unknown family {fam!r}")


def train_input_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    i32 = jnp.int32
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
        "labels": jax.ShapeDtypeStruct((batch, seq), i32),
    }
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_frames, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    if cfg.use_mrope:
        specs["positions"] = jax.ShapeDtypeStruct((3, batch, seq), i32)
    if cfg.vision_embeds:
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.vision_embeds, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    return specs


def train_input_shardings(cfg: ArchConfig, specs: dict, rules, mesh):
    """NamedShardings matching ``train_input_specs`` (batch over data axes)."""
    from jax.sharding import NamedSharding

    from repro.dist.sharding import logical_to_spec

    def spec_for(name, s):
        if name == "positions":
            axes = (None, "batch", None)
        elif name in ("frames", "vision_embeds"):
            axes = ("batch", None, None)
        else:
            axes = ("batch", None)
        return NamedSharding(mesh, logical_to_spec(s.shape, axes, rules, mesh))

    return {k: spec_for(k, v) for k, v in specs.items()}


def decode_input_specs(cfg: ArchConfig, batch: int) -> dict:
    return {
        "tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }


def verify_input_specs(cfg: ArchConfig, batch: int, num_tokens: int) -> dict:
    """Abstract inputs for speculative decode's verify pass: ``num_tokens``
    (= spec_k + 1) stacked positions per row, each row at its own length —
    the ``verify_batch`` operand shapes the dry-run lowers against."""
    return {
        "tokens": jax.ShapeDtypeStruct((batch, num_tokens), jnp.int32),
        "lens": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def decode_cache_specs(model, cfg: ArchConfig, batch: int, max_len: int):
    """ParamSpec pytree for the decode-time cache/state of any family."""
    if cfg.family == "rwkv":
        return model.state_specs(batch)
    if cfg.family == "hybrid":
        return model.state_specs(batch, max_len)
    if cfg.family == "mamba_attn":
        return {"kv": model.cache_specs(batch, max_len), "state": model.slot_state_specs(batch)}
    return model.cache_specs(batch, max_len)


def param_counts(model, cfg: ArchConfig) -> tuple[int, int]:
    """(total, active) parameter counts; active discounts routed experts."""
    import math

    from repro.dist.sharding import ParamSpec

    leaves = jax.tree.leaves(
        model.param_specs(), is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    total = active = 0
    for s in leaves:
        n = math.prod(s.shape)
        total += n
        if "expert" in s.axes and cfg.n_experts:
            active += n * cfg.top_k // cfg.n_experts
        else:
            active += n
    return total, active


def synth_batch(cfg: ArchConfig, batch: int, seq: int, rng=None) -> dict:
    """Materialized random batch matching train_input_specs (smoke tests)."""
    import numpy as np

    r = np.random.default_rng(0 if rng is None else rng)
    out = {
        "tokens": r.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),
        "labels": r.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),
    }
    if cfg.family == "encdec":
        out["frames"] = r.normal(size=(batch, cfg.encoder_frames, cfg.d_model)).astype(
            np.float32
        )
    if cfg.use_mrope:
        p = np.broadcast_to(np.arange(seq, dtype=np.int32), (batch, seq))
        out["positions"] = np.stack([p, p, p]).astype(np.int32)
    if cfg.vision_embeds:
        out["vision_embeds"] = r.normal(
            size=(batch, cfg.vision_embeds, cfg.d_model)
        ).astype(np.float32)
        out["labels"][:, : cfg.vision_embeds] = -1
    return out


def input_specs(cfg: ArchConfig, shape) -> dict | tuple:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    The assignment-level entry point: dispatches on the cell kind
    (train/prefill/decode) and returns weak-type-correct, shardable,
    allocation-free abstract inputs (the dry-run's lowering operands).
    """
    if shape.kind == "train":
        return train_input_specs(cfg, shape.global_batch, shape.seq_len)
    if shape.kind == "prefill":
        import jax, jax.numpy as jnp
        specs = {"tokens": jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)}
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_frames, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return specs
    return decode_input_specs(cfg, shape.global_batch)
