"""Unrolled (probe) vs scanned (production) paths must be numerically equal.

The dry-run's roofline probes lower `scan_layers=False` variants in which
every layer loop (``L.scan_stack``), attention chunk loop
(``blockwise_attention(unroll=)``), and SSM/RWKV chunk loop
(``wkv6_chunked``/``ssd_chunked``) is a Python unroll.  The probe
extrapolation is only valid if the unrolled program computes the *same
function*, so this suite pins exact (up to fp tolerance) equivalence on
every family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.dist.sharding import materialize_params
from repro.launch.mesh import make_host_mesh, rules_for
from repro.models import layers as L
from repro.models.api import build_model, synth_batch
from repro.models.layers import ModelContext

ARCHS = [
    "smollm-135m",          # dense GQA
    "granite-moe-1b-a400m", # MoE
    "deepseek-v3-671b",     # MLA + MoE + MTP
    "whisper-medium",       # enc-dec
    "rwkv6-7b",             # WKV6 chunk recurrence
    "zamba2-1.2b",          # Mamba2 SSD + shared attention
]


def _ctx_pair(arch):
    mesh = make_host_mesh()
    rules = rules_for(mesh)
    cfg_scan = get_smoke_config(arch)
    cfg_unroll = cfg_scan.with_(scan_layers=False)
    return ModelContext(cfg_scan, mesh, rules), ModelContext(cfg_unroll, mesh, rules)


@pytest.mark.parametrize("arch", ARCHS)
def test_loss_scan_vs_unroll(arch):
    ctx_s, ctx_u = _ctx_pair(arch)
    model_s, model_u = build_model(ctx_s), build_model(ctx_u)
    params = materialize_params(model_s.param_specs(), jax.random.PRNGKey(0))
    batch = synth_batch(ctx_s.cfg, 2, 256, rng=1)
    with ctx_s.mesh:
        loss_s, _ = jax.jit(model_s.loss)(params, batch)
        loss_u, _ = jax.jit(model_u.loss)(params, batch)
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_u),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-7b", "zamba2-1.2b"])
def test_prefill_scan_vs_unroll(arch):
    ctx_s, ctx_u = _ctx_pair(arch)
    model_s, model_u = build_model(ctx_s), build_model(ctx_u)
    params = materialize_params(model_s.param_specs(), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 256), 0,
                                ctx_s.cfg.vocab)
    with ctx_s.mesh:
        lg_s, _ = jax.jit(lambda p, t: model_s.prefill(p, t, 256))(params, tokens)
        lg_u, _ = jax.jit(lambda p, t: model_u.prefill(p, t, 256))(params, tokens)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_u),
                               rtol=2e-2, atol=2e-2)


def test_blockwise_attention_unroll_multichunk():
    """Force multiple q/kv chunks and compare scan vs unroll vs exact."""
    key = jax.random.PRNGKey(0)
    B, S, H, D = 2, 512, 4, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) * 0.3
               for kk in jax.random.split(key, 3))
    o_scan = L.blockwise_attention(q, k, v, causal=True, q_chunk=128,
                                   kv_chunk=128, unroll=False)
    o_unroll = L.blockwise_attention(q, k, v, causal=True, q_chunk=128,
                                     kv_chunk=128, unroll=True)
    np.testing.assert_allclose(np.asarray(o_scan), np.asarray(o_unroll),
                               rtol=1e-5, atol=1e-5)
    # exact reference
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o_ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(o_unroll), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_attn_chunks_divisor():
    assert L._attn_chunks(1500, 1024) == 750
    assert L._attn_chunks(4096, 1024) == 1024
    assert L._attn_chunks(7, 1024) == 7
    assert L._attn_chunks(32768, 1024) == 1024


def test_causal_skip_equivalence():
    """causal_skip (beyond-paper lever) must not change the function."""
    key = jax.random.PRNGKey(3)
    B, S, H, D = 2, 512, 4, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) * 0.3
               for kk in jax.random.split(key, 3))
    base = L.blockwise_attention(q, k, v, causal=True, q_chunk=128,
                                 kv_chunk=128)
    skip = L.blockwise_attention(q, k, v, causal=True, q_chunk=128,
                                 kv_chunk=128, causal_skip=True)
    skip_unroll = L.blockwise_attention(q, k, v, causal=True, q_chunk=128,
                                        kv_chunk=128, causal_skip=True,
                                        unroll=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip_unroll),
                               rtol=1e-5, atol=1e-5)


def test_flat_dp_rules_resolve():
    """flat_dp profile shards batch over both axes and nothing over model."""
    from repro.dist.sharding import FLAT_DP_RULES, logical_to_spec
    from jax.sharding import PartitionSpec as P

    mesh = make_host_mesh()  # (1,1) same axis names
    spec = logical_to_spec((256, 128), ("batch", None), FLAT_DP_RULES, mesh)
    # on a 1×1 mesh everything degenerates to replication but resolution
    # must not error; real-mesh resolution is covered by the dry-run.
    assert isinstance(spec, P)


def test_attention_core_kernel_dispatch():
    """ctx.use_kernels routes GQA attention through the Pallas wrapper
    (jnp fallback on CPU) and must agree with the blockwise path."""
    from repro.models.layers import ModelContext, _attention_core
    from repro.configs import get_smoke_config

    mesh = make_host_mesh()
    cfg = get_smoke_config("smollm-135m")
    ctx_j = ModelContext(cfg, mesh, rules_for(mesh), use_kernels=False)
    ctx_k = ModelContext(cfg, mesh, rules_for(mesh), use_kernels=True)
    key = jax.random.PRNGKey(0)
    B, S, H, D = 2, 128, 4, 32
    q = jax.random.normal(key, (B, S, H, D), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 2, D), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, 2, D), jnp.float32) * 0.3
    o_j = _attention_core(ctx_j, q, k, v, causal=True)
    o_k = _attention_core(ctx_k, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_j), np.asarray(o_k),
                               rtol=2e-3, atol=2e-3)


def _kernel_ctx(page_size=16, **cfg_kw):
    mesh = make_host_mesh()
    cfg = get_smoke_config("smollm-135m").with_(**cfg_kw)
    return ModelContext(cfg, mesh, rules_for(mesh), use_kernels=True,
                        page_size=page_size)


def test_decode_attention_core_pages_from_context():
    """With kernels on, the decode core reads the cache in the caller's
    page size (here 8 over a 40-token cache) and agrees with jnp; a cache
    the pages do not tile raises instead of quietly running jnp."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 1, 4, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 40, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 40, 2, 32), jnp.float32)
    length = jnp.int32(29)
    want = L.decode_attention(q, k, v, length)
    got = L._decode_attention_core(_kernel_ctx(page_size=8), q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="16-token pages"):
        L._decode_attention_core(_kernel_ctx(page_size=16), q, k, v, length)
    with pytest.raises(ValueError, match="head dim"):
        L._decode_attention_core(_kernel_ctx(page_size=8), q, k, v[..., :16],
                                 length)


@pytest.mark.parametrize("case", ["scale", "unrolled", "ragged_seq"])
def test_attention_core_raises_where_kernel_cannot_run(case):
    """With kernels on, a shape the flash kernel cannot take raises rather
    than returning the jnp blockwise result."""
    key = jax.random.PRNGKey(4)
    S = 200 if case == "ragged_seq" else 64
    q = jax.random.normal(key, (1, S, 4, 32), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(key, 1), (1, S, 2, 32), jnp.float32)
    ctx = _kernel_ctx(scan_layers=case != "unrolled")
    scale = 0.5 if case == "scale" else None
    with pytest.raises(ValueError, match="flash-attention kernel cannot take"):
        L._attention_core(ctx, q, kv, kv, causal=True, scale=scale)
