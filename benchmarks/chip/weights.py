"""Seeded random weights, made on the device in one jitted call, in the
layout and type they are served in.

The table of leaves comes from the architecture's module
(``archs/<model_type>.py``: ``shapes(arch)``), ``path -> (shape, dtype,
scale, fan_in)``.  The benchmark makes the weights it hands the engine, and
the reference makes them again from the same seed with this same code, so
the reference takes nothing the program made.  Each leaf is drawn from the
root key folded with a CRC-32 of its tree path, so a leaf's values depend
only on the seed, its path and its shape.

Weight matrices are normal with std 1/sqrt(fan-in of one layer) where the
table gives no scale, otherwise with the stated std; float32 leaves of scale
1 (norm scales) are ones.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


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


def make(table: dict, seed: int, device=None) -> dict:
    """The nested parameter dict of ``table``, made in one jitted call on
    ``device``."""

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
