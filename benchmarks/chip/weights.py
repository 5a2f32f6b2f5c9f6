"""Seeded random weights of a Llama-style decoder, made on the device in one
jitted call, in the layout and type they are served in.

The benchmark makes the weights it hands the engine, and the reference makes
them again from the same seed with this same code, so the reference takes
nothing the program made.  Each leaf is drawn from the root key folded with
a CRC-32 of its tree path, so a leaf's values depend only on the seed, its
path and its shape.

Weight matrices are normal with std 1/sqrt(fan-in of one layer), the
embedding (and an untied head) normal with std ``embed_std``, norm scales 1.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


def shapes(arch: dict) -> dict[tuple, tuple]:
    """``path -> (shape, dtype, scale, fan_in)``: scale is the stated std
    (or the fill of a norm scale), None means fan-in scaled; fan_in is what
    one layer's matrix contracts over."""
    E, F = arch["hidden_size"], arch["intermediate_size"]
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    L, V = arch["num_hidden_layers"], arch["padded_vocab"]
    D = E // H
    std = arch["embed_std"]
    t = {
        ("embed", "embedding"): ((V, E), BF16, std, None),
        ("final_norm", "scale"): ((E,), F32, 1.0, None),
        ("dense_layers", "ln1", "scale"): ((L, E), F32, 1.0, None),
        ("dense_layers", "ln2", "scale"): ((L, E), F32, 1.0, None),
        ("dense_layers", "attn", "wq"): ((L, E, H, D), BF16, None, E),
        ("dense_layers", "attn", "wk"): ((L, E, Hkv, D), BF16, None, E),
        ("dense_layers", "attn", "wv"): ((L, E, Hkv, D), BF16, None, E),
        ("dense_layers", "attn", "wo"): ((L, H, D, E), BF16, None, H * D),
        ("dense_layers", "ffn", "wi"): ((L, E, F), BF16, None, E),
        ("dense_layers", "ffn", "wg"): ((L, E, F), BF16, None, E),
        ("dense_layers", "ffn", "wo"): ((L, F, E), BF16, None, F),
    }
    if not arch["tie_word_embeddings"]:
        t[("embed", "unembed")] = ((E, V), BF16, std, None)
    return t


def root_key(seed: int):
    """``PRNGKey(seed)``; seeds past 31 bits fold their high part in."""
    if 0 <= seed < 2**31:
        return jax.random.PRNGKey(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _path_fold(path: tuple) -> int:
    key = "".join(f"[{p!r}]" for p in path)  # jax.tree_util.keystr's form
    return zlib.crc32(key.encode()) & 0x7FFFFFFF


def _leaf(key, shape, dtype, scale, fan_in):
    if dtype == F32 and scale == 1.0:  # norm scales, stacked or not
        return jnp.ones(shape, dtype)
    std = scale if scale is not None else 1 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def make(arch: dict, seed: int, device=None) -> dict:
    """The nested parameter dict, made in one jitted call on ``device``."""
    table = shapes(arch)

    def build(key):
        out: dict = {}
        for path, (shape, dtype, scale, fan_in) in table.items():
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = _leaf(
                jax.random.fold_in(key, _path_fold(path)), shape, dtype, scale, fan_in,
            )
        return out

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
