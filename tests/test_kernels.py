"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles
(ref.py), swept across shapes and dtypes (assignment §c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import attention_ref, ssd_ref, wkv6_ref


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-3, atol=2e-3
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SWEEP = [
    # (B, Sq, Sk, H, Hkv, D, causal, dtype)
    (1, 128, 128, 2, 2, 64, True, jnp.float32),
    (2, 256, 256, 4, 2, 64, True, jnp.float32),   # GQA group=2
    (1, 128, 128, 4, 1, 32, True, jnp.bfloat16),  # MQA
    (2, 128, 128, 2, 2, 128, False, jnp.float32),  # non-causal (encoder)
    (1, 256, 256, 8, 2, 64, True, jnp.bfloat16),
    (1, 64, 256, 2, 2, 64, True, jnp.float32),     # Sq < Sk (chunked prefill)
]


@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,D,causal,dtype", ATTN_SWEEP)
def test_flash_attention_vs_ref(B, Sq, Sk, H, Hkv, D, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(k1, (B, Sq, H, D), dtype)
    k = rand(k2, (B, Sk, Hkv, D), dtype)
    v = rand(k3, (B, Sk, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, impl="interpret",
                              block_q=64, block_k=64)
    kx = jnp.repeat(k, H // Hkv, axis=2)
    vx = jnp.repeat(v, H // Hkv, axis=2)
    ref = attention_ref(q, kx, vx, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_flash_attention_jnp_fallback_matches_ref():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(k1, (2, 128, 4, 64), jnp.float32)
    k = rand(k2, (2, 128, 2, 64), jnp.float32)
    v = rand(k3, (2, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, impl="jnp")
    ref = attention_ref(
        q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# paged attention (decode over a block-table-indexed KV pool)
# ---------------------------------------------------------------------------


def paged_ref(q, k_pages, v_pages, block_tables, lens):
    """Dense oracle: gather each row's pages, mask by length, softmax."""
    B, _, H, D = q.shape
    P, ps, Hkv, Dv = v_pages.shape
    rows = []
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            rows.append(jnp.zeros((1, H, Dv), jnp.float32))
            continue
        k = k_pages[block_tables[b]].reshape(-1, Hkv, D)[:L]
        v = v_pages[block_tables[b]].reshape(-1, Hkv, Dv)[:L]
        kx = jnp.repeat(k, H // Hkv, axis=1).astype(jnp.float32)
        vx = jnp.repeat(v, H // Hkv, axis=1).astype(jnp.float32)
        s = jnp.einsum("qhd,khd->hqk", q[b].astype(jnp.float32), kx)
        p = jax.nn.softmax(s * (D ** -0.5), axis=-1)
        rows.append(jnp.einsum("hqk,khd->qhd", p, vx))
    return jnp.stack(rows)


def paged_case(seed, B, P, n, ps, H, Hkv, D, dtype, *, lens=None, T=1):
    """Random pool + per-row unique block tables + mixed lengths."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = rand(ks[0], (B, T, H, D), dtype)
    k_pages = rand(ks[1], (P, ps, Hkv, D), dtype)
    v_pages = rand(ks[2], (P, ps, Hkv, D), dtype)
    rng = np.random.default_rng(seed)
    bt = np.stack([rng.permutation(P)[:n] for _ in range(B)]).astype(np.int32)
    if lens is None:  # cover empty, partial-page, and full-coverage rows
        lens = rng.integers(0, n * ps + 1, B).astype(np.int32)
        lens[0] = n * ps
        if B > 1:
            lens[1] = max(1, ps - 1)  # mid-page boundary
    return q, k_pages, v_pages, jnp.asarray(bt), jnp.asarray(lens)


PAGED_SWEEP = [
    # (B, pool_pages, n, page_size, H, Hkv, D, dtype)
    (3, 24, 4, 8, 4, 2, 64, jnp.float32),
    (2, 16, 2, 16, 4, 1, 32, jnp.float32),   # MQA
    (4, 32, 4, 8, 8, 2, 64, jnp.bfloat16),
    (1, 12, 8, 4, 2, 2, 128, jnp.float32),   # many small pages
]


@pytest.mark.parametrize("B,P,n,ps,H,Hkv,D,dtype", PAGED_SWEEP)
def test_paged_attention_vs_ref(B, P, n, ps, H, Hkv, D, dtype):
    """Interpret-mode Pallas paged decode == dense gather-and-softmax."""
    q, kp, vp, bt, lens = paged_case(7, B, P, n, ps, H, Hkv, D, dtype)
    out = ops.paged_attention(q, kp, vp, bt, lens, impl="interpret")
    ref = paged_ref(q, kp, vp, np.asarray(bt), np.asarray(lens))
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("B,P,n,ps,H,Hkv,D,dtype", PAGED_SWEEP[:2])
def test_paged_attention_jnp_fallback_matches_ref(B, P, n, ps, H, Hkv, D, dtype):
    q, kp, vp, bt, lens = paged_case(11, B, P, n, ps, H, Hkv, D, dtype)
    out = ops.paged_attention(q, kp, vp, bt, lens, impl="jnp")
    ref = paged_ref(q, kp, vp, np.asarray(bt), np.asarray(lens))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_flash_attention_takes_a_scale(impl):
    """A non-default softmax scale (granite-4.0-h's 1/D, not 1/sqrt(D))
    matches the blockwise jnp attention at that scale; no scale is
    bit-identical to the scale 1/sqrt(D) spelled out."""
    from repro.models.layers import blockwise_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(8), 3)
    q = rand(k1, (1, 128, 4, 64), jnp.float32)
    k = rand(k2, (1, 128, 2, 64), jnp.float32)
    v = rand(k3, (1, 128, 2, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, scale=1 / 64, impl=impl,
                              block_q=64, block_k=64)
    want = blockwise_attention(q, k, v, causal=True, scale=1 / 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    plain = ops.flash_attention(q, k, v, causal=True, impl=impl, block_q=64, block_k=64)
    spelled = ops.flash_attention(q, k, v, causal=True, scale=1 / np.sqrt(64), impl=impl,
                                  block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spelled))
    assert not np.allclose(np.asarray(plain), np.asarray(got))


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_paged_attention_takes_a_scale(impl):
    """The paged decode kernel at a non-default scale matches the dense
    gather-and-softmax at that scale; no scale is bit-identical to
    1/sqrt(D) spelled out."""
    q, kp, vp, bt, lens = paged_case(13, 3, 24, 4, 8, 4, 2, 64, jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, lens, scale=1 / 64, impl=impl)
    want = paged_ref(q * (np.sqrt(64) / 64), kp, vp, np.asarray(bt), np.asarray(lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    plain = ops.paged_attention(q, kp, vp, bt, lens, impl=impl)
    spelled = ops.paged_attention(q, kp, vp, bt, lens, scale=1 / np.sqrt(64), impl=impl)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spelled))


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_paged_attention_ignores_padding_pages(impl):
    """Block-table entries beyond a row's length are never read: garbage
    (even out-of-range) padding ids change nothing — the property the
    engine's null-page padding relies on."""
    B, P, n, ps, H, Hkv, D = 2, 16, 4, 8, 4, 2, 64
    q, kp, vp, bt, lens = paged_case(
        13, B, P, n, ps, H, Hkv, D, jnp.float32,
        lens=np.asarray([ps + 3, 2 * ps], np.int32),  # cover ≤ 2 of 4 pages
    )
    base = ops.paged_attention(q, kp, vp, bt, lens, impl=impl)
    junk = np.asarray(bt).copy()
    junk[:, 2:] = 10_000  # uncovered slots → nonsense (clipped internally)
    out = ops.paged_attention(q, kp, vp, jnp.asarray(junk), lens, impl=impl)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_paged_attention_zero_length_row_is_finite(impl):
    q, kp, vp, bt, _ = paged_case(17, 2, 8, 2, 4, 2, 1, 32, jnp.float32)
    lens = jnp.asarray([0, 5], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lens, impl=impl)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def paged_multi_ref(q, k_pages, v_pages, block_tables, lens):
    """Dense multi-query oracle: query ``t`` attends keys ``< lens + t``
    (speculative verify's per-position causal staircase)."""
    T = q.shape[1]
    cols = [
        paged_ref(q[:, t : t + 1], k_pages, v_pages, block_tables,
                  np.asarray(lens) + t)
        for t in range(T)
    ]
    return jnp.concatenate(cols, axis=1)


PAGED_MQ_SWEEP = [
    # (B, pool_pages, n, page_size, H, Hkv, D, T, dtype)
    (3, 24, 4, 8, 4, 2, 64, 2, jnp.float32),
    (2, 16, 4, 8, 4, 1, 32, 4, jnp.float32),   # MQA, spec_k=3 verify width
    (2, 24, 4, 8, 8, 2, 64, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("B,P,n,ps,H,Hkv,D,T,dtype", PAGED_MQ_SWEEP)
@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_paged_attention_multi_query_vs_ref(impl, B, P, n, ps, H, Hkv, D, T,
                                            dtype):
    """Speculative verify pass (T > 1): interpret-mode Pallas and the jnp
    fallback both match the dense staircase oracle, including a row whose
    last query exactly fills the block table."""
    rng = np.random.default_rng(31)
    lens = rng.integers(1, n * ps - T + 2, B).astype(np.int32)
    lens[0] = n * ps - T + 1  # last query covers the final pool token
    q, kp, vp, bt, lens = paged_case(
        29, B, P, n, ps, H, Hkv, D, dtype, lens=lens, T=T
    )
    out = ops.paged_attention(q, kp, vp, bt, lens, impl=impl)
    ref = paged_multi_ref(q, kp, vp, np.asarray(bt), np.asarray(lens))
    assert out.shape == (B, T, H, D)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_paged_attention_multi_query_first_column_matches_single(impl):
    """Column t=0 of a T-query verify equals the plain T=1 decode step:
    stacking speculative queries cannot change the committed token."""
    B, P, n, ps, H, Hkv, D, T = 2, 16, 4, 8, 4, 2, 64, 3
    lens = np.asarray([ps + 3, 2 * ps], np.int32)
    q, kp, vp, bt, lens = paged_case(
        37, B, P, n, ps, H, Hkv, D, jnp.float32, lens=lens, T=T
    )
    multi = ops.paged_attention(q, kp, vp, bt, lens, impl=impl)
    single = ops.paged_attention(q[:, :1], kp, vp, bt, lens, impl=impl)
    np.testing.assert_allclose(
        np.asarray(multi[:, :1], np.float32),
        np.asarray(single, np.float32),
        rtol=1e-5, atol=1e-5,
    )


def test_paged_attention_matches_contiguous_decode():
    """Paging a contiguous cache (identity block table) reproduces plain
    dense decode attention — the layout is a pure reindexing."""
    from repro.models.layers import decode_attention

    B, S, ps, H, Hkv, D = 2, 64, 16, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = rand(ks[0], (B, 1, H, D), jnp.float32)
    k = rand(ks[1], (B, S, Hkv, D), jnp.float32)
    v = rand(ks[2], (B, S, Hkv, D), jnp.float32)
    n = S // ps
    kp = k.reshape(B * n, ps, Hkv, D)
    vp = v.reshape(B * n, ps, Hkv, D)
    bt = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    L = S - 5  # decode_attention takes one scalar length for the batch
    lens = jnp.full((B,), L, jnp.int32)
    paged = ops.paged_attention(q, kp, vp, bt, lens, impl="interpret")
    dense = decode_attention(q, k, v, jnp.int32(L))
    np.testing.assert_allclose(
        np.asarray(paged), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

WKV_SWEEP = [
    # (B, S, H, K, V, chunk, dtype)
    (1, 64, 2, 16, 16, 16, jnp.float32),
    (2, 128, 2, 32, 32, 32, jnp.float32),
    (1, 128, 4, 64, 64, 64, jnp.bfloat16),
    (1, 96, 1, 16, 16, 32, jnp.float32),  # S not multiple of chunk → clamps
]


@pytest.mark.parametrize("B,S,H,K,V,chunk,dtype", WKV_SWEEP)
def test_wkv6_kernel_vs_ref(B, S, H, K, V, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r = rand(ks[0], (B, S, H, K), dtype)
    k = rand(ks[1], (B, S, H, K), dtype)
    v = rand(ks[2], (B, S, H, V), dtype)
    # realistic decays: lw in [-6, -0.02]
    lw = (-jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.5)).astype(jnp.float32)
    u = rand(ks[4], (H, K), jnp.float32)
    ref, _ = wkv6_ref(r, k, v, lw, u)
    if S % chunk == 0:
        out = ops.wkv6(r, k, v, lw, u, impl="interpret", chunk=chunk)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )
    # chunked-jnp path must also match the sequential oracle
    out2 = ops.wkv6(r, k, v, lw, u, impl="jnp", chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(out2, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_wkv6_decode_step_consistency():
    """Sequential single-step decode equals the chunked form, step by step."""
    from repro.models.rwkv import wkv6_chunked, wkv6_step

    B, S, H, K = 1, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = rand(ks[0], (B, S, H, K), jnp.float32)
    k = rand(ks[1], (B, S, H, K), jnp.float32)
    v = rand(ks[2], (B, S, H, K), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.3)
    u = rand(ks[4], (H, K), jnp.float32)
    full, sF = wkv6_chunked(r, k, v, lw, u, chunk=8)
    s = jnp.zeros((B, H, K, K))
    outs = []
    for t in range(S):
        o, s = wkv6_step(r[:, t], k[:, t], v[:, t], lw[:, t], u, s)
        outs.append(o)
    step_out = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(step_out), np.asarray(full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sF), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd (mamba2)
# ---------------------------------------------------------------------------

SSD_SWEEP = [
    # (B, S, H, P, N, chunk, dtype)
    (1, 64, 2, 16, 16, 16, jnp.float32),
    (2, 128, 4, 32, 16, 32, jnp.float32),
    (1, 128, 2, 64, 64, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,P,N,chunk,dtype", SSD_SWEEP)
def test_ssd_kernel_vs_ref(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = rand(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (B, S, H)) * 0.3)
    la = dt * a / jnp.maximum(dt, 1e-3) * jnp.minimum(dt, 1.0)  # bounded decay
    la = -jnp.abs(la)
    Bm = rand(ks[3], (B, S, N), jnp.float32)
    Cm = rand(ks[4], (B, S, N), jnp.float32)
    D = rand(ks[5], (H,), jnp.float32)
    ref, ref_state = ssd_ref(x, dt, la, Bm, Cm, D)
    for impl in ("interpret", "jnp"):
        out, state = ops.ssd(x, dt, la, Bm, Cm, D, impl=impl, chunk=chunk)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
        )
        np.testing.assert_allclose(np.asarray(state), np.asarray(ref_state), **tol(dtype))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [(1, 32, 2, 8, 16, 8), (2, 24, 3, 16, 8, 8)])
def test_ssd_kernel_final_state_matches_chunked_and_steps(B, S, H, P, N, chunk):
    """The kernel's final state (from a nonzero start) is ``ssd_chunked``'s
    and the one-token recurrence's after S steps: what decode continues
    from."""
    from repro.models.ssm import ssd_chunked, ssd_step

    ks = jax.random.split(jax.random.PRNGKey(6), 7)
    x = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    la = -jnp.abs(jax.random.normal(ks[2], (B, S, H)) * 0.3)
    Bm = rand(ks[3], (B, S, N), jnp.float32)
    Cm = rand(ks[4], (B, S, N), jnp.float32)
    D = rand(ks[5], (H,), jnp.float32)
    s0 = rand(ks[6], (B, H, P, N), jnp.float32)
    y, state = ops.ssd(x, dt, la, Bm, Cm, D, s0, impl="interpret", chunk=chunk)
    y_c, state_c = ssd_chunked(x, dt, la, Bm, Cm, D, s0, chunk=chunk)
    s = s0
    for t in range(S):
        _, s = ssd_step(x[:, t], dt[:, t], la[:, t], Bm[:, t], Cm[:, t], D, s)
    assert state.shape == (B, H, P, N) and state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_c), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_c), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s), rtol=1e-4, atol=1e-4)


def test_ssd_decode_step_consistency():
    from repro.models.ssm import ssd_chunked, ssd_step

    B, S, H, P, N = 1, 12, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    la = -jnp.abs(jax.random.normal(ks[2], (B, S, H)) * 0.3)
    Bm = rand(ks[3], (B, S, N), jnp.float32)
    Cm = rand(ks[4], (B, S, N), jnp.float32)
    D = rand(ks[5], (H,), jnp.float32)
    full, sF = ssd_chunked(x, dt, la, Bm, Cm, D, chunk=4)
    s = jnp.zeros((B, H, P, N))
    outs = []
    for t in range(S):
        o, s = ssd_step(x[:, t], dt[:, t], la[:, t], Bm[:, t], Cm[:, t], D, s)
        outs.append(o)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(sF), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# property-based: invariances the kernels must satisfy
# ---------------------------------------------------------------------------

from hypothesis import given, settings
from hypothesis import strategies as st


@given(
    st.integers(1, 3), st.sampled_from([32, 64]), st.sampled_from([1, 2, 4]),
    st.sampled_from([16, 32]),
)
@settings(max_examples=8, deadline=None)
def test_attention_softmax_rowsum_property(B, S, H, D):
    """Attention output of constant V must be that constant (softmax sums 1)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * 100 + S))
    q = rand(k1, (B, S, H, D), jnp.float32)
    k = rand(k2, (B, S, H, D), jnp.float32)
    v = jnp.ones((B, S, H, D), jnp.float32) * 0.5
    out = ops.flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), 0.5, rtol=1e-5, atol=1e-5)


@given(st.sampled_from([16, 32, 64]), st.sampled_from([8, 16]))
@settings(max_examples=6, deadline=None)
def test_wkv6_zero_decay_accumulates(S, K):
    """With w=1 (lw=0), u=0: o_t = r_t · Σ_{s≤t} k_sᵀ v_s (pure accumulation)."""
    ks = jax.random.split(jax.random.PRNGKey(S + K), 3)
    r = rand(ks[0], (1, S, 1, K), jnp.float32)
    k = rand(ks[1], (1, S, 1, K), jnp.float32)
    v = rand(ks[2], (1, S, 1, K), jnp.float32)
    lw = jnp.zeros((1, S, 1, K))
    u = jnp.zeros((1, K))
    out = ops.wkv6(r, k, v, lw, u, impl="jnp", chunk=16)
    # direct cumulative check: EXCLUSIVE prefix (current token enters via u only)
    kv = jnp.einsum("bshk,bshv->bshkv", k, v)
    S_cum = jnp.cumsum(kv, axis=1) - kv
    ref = jnp.einsum("bshk,bshkv->bshv", r, S_cum)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
