"""The main path's Pallas kernels compile for a TPU v5e at smollm-135m widths.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
cannot see what Mosaic refuses (block shapes off the (8, 128) tiling, too
much VMEM).  These tests compile each kernel for a *described* v5e chip —
the TPU compiler is installed even where no chip is attached — and check
that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("smollm-135m")  # 9 heads over 3 KV heads, head dim 64
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("T", [1, 5])  # decode step; speculative verify (k=4)
def test_paged_attention_compiles_for_v5e(T, one_chip, no_persistent_cache):
    B, n = 4, 32  # 4 slots over 512-token rows of 16-token pages
    H, Hkv, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_
    P = B * n + 1
    dt = jnp.dtype(CFG.dtype)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _hlo(
        lambda q, kp, vp, bt, lens: ops.paged_attention(
            q, kp, vp, bt, lens, impl="pallas"
        ),
        sds((B, T, H, D), dt),
        sds((P, PAGE, Hkv, D), dt),
        sds((P, PAGE, Hkv, D), dt),
        sds((B, n), jnp.int32),
        sds((B,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_flash_attention_prefill_compiles_for_v5e(one_chip, no_persistent_cache):
    B, S = 4, 512
    H, Hkv, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_
    dt = jnp.dtype(CFG.dtype)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hlo = _hlo(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, impl="pallas"),
        sds((B, S, H, D)),
        sds((B, S, Hkv, D)),
        sds((B, S, Hkv, D)),
    )
    assert "tpu_custom_call" in hlo


def test_ssd_prefill_compiles_for_v5e(one_chip, no_persistent_cache):
    """The chunked SSD scan at granite-4.0-h-micro widths (64 heads of 64,
    d_state 128, one group) for a 1024-token prompt, with its final state."""
    from repro.configs import get_config

    cfg = get_config("granite-4.0-h-micro")
    B, S = 1, 1024
    H, P, N = 2 * cfg.d_model // cfg.ssm_head_dim, cfg.ssm_head_dim, cfg.ssm_state

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = _hlo(
        lambda x, dt, la, b, c, d: ops.ssd(x, dt, la, b, c, d, impl="pallas"),
        sds((B, S, H, P), jnp.bfloat16), sds((B, S, H), jnp.float32),
        sds((B, S, H), jnp.float32), sds((B, S, N), jnp.bfloat16),
        sds((B, S, N), jnp.bfloat16), sds((H,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo and "ssd_scan" in hlo


def test_attention_kernels_compile_with_a_scale_for_v5e(one_chip, no_persistent_cache):
    """Both attention kernels at granite-4.0-h-micro's softmax scale 1/64
    (32 heads over 8 KV heads of 64)."""
    H, Hkv, D, S, B, n = 32, 8, 64, 512, 4, 32
    dt = jnp.bfloat16

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    flash = _hlo(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, scale=1 / 64, impl="pallas"),
        sds((1, S, H, D)), sds((1, S, Hkv, D)), sds((1, S, Hkv, D)),
    )
    paged = _hlo(
        lambda q, kp, vp, bt, lens: ops.paged_attention(q, kp, vp, bt, lens, scale=1 / 64,
                                                        impl="pallas"),
        sds((B, 1, H, D)), sds((B * n + 1, PAGE, Hkv, D)), sds((B * n + 1, PAGE, Hkv, D)),
        sds((B, n), jnp.int32), sds((B,), jnp.int32),
    )
    assert "tpu_custom_call" in flash and "tpu_custom_call" in paged
