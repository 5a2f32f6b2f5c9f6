"""Per-architecture smoke tests: reduced same-family configs, one forward /
train-step on CPU, asserting output shapes + finiteness (assignment §f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import arch_names, get_smoke_config
from repro.dist.sharding import (
    DEFAULT_RULES,
    abstract_params,
    count_params,
    materialize_params,
)
from repro.models.api import build_model, decode_cache_specs, synth_batch
from repro.models.layers import ModelContext

ARCHS = arch_names()


def make_ctx(cfg):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    return ModelContext(cfg=cfg, mesh=mesh, rules=DEFAULT_RULES)


@pytest.fixture(scope="module")
def cache():
    return {}


def _setup(name):
    cfg = get_smoke_config(name).with_(remat="none")
    ctx = make_ctx(cfg)
    model = build_model(ctx)
    specs = model.param_specs()
    params = materialize_params(specs, jax.random.PRNGKey(0))
    return cfg, ctx, model, params


@pytest.mark.parametrize("name", ARCHS)
def test_train_step_smoke(name):
    cfg, ctx, model, params = _setup(name)
    batch = synth_batch(cfg, batch=2, seq=32)
    with ctx.mesh:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True
        )(params)
    assert np.isfinite(float(loss)), f"{name}: non-finite loss"
    # gradients exist and are finite for every leaf
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.all(np.isfinite(np.asarray(g, dtype=np.float32))), (
            f"{name}: non-finite grad at {jax.tree_util.keystr(path)}"
        )
    # loss ~ log(vocab) at init (sanity that logits aren't degenerate)
    assert 0.5 * np.log(cfg.vocab) < float(metrics["ce"]) < 3.0 * np.log(cfg.vocab)


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_decode_smoke(name):
    cfg, ctx, model, params = _setup(name)
    B, S, MAX = 2, 16, 32
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (B, S)).astype(np.int32)
    with ctx.mesh:
        logits, cache = model.prefill(params, jnp.asarray(tokens), MAX)
        assert logits.shape == (B, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        nxt = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)[:, None]
        logits2, cache = model.decode_step(params, cache, nxt, jnp.int32(S))
        assert logits2.shape == (B, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


@pytest.mark.parametrize("name", ARCHS)
def test_decode_matches_prefill(name):
    """Teacher-forced decode must reproduce the prefill/next-token logits —
    catches cache-indexing and recurrence bugs."""
    if name == "qwen2-vl-72b":
        pytest.skip("mrope decode positions differ from text-only prefill stub")
    cfg, ctx, model, params = _setup(name)
    B, S = 1, 8
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab, (B, S + 1)).astype(np.int32)
    with ctx.mesh:
        # full-sequence logits via prefill of S+1 tokens
        full_logits, _ = model.prefill(params, jnp.asarray(tokens), S + 4)
        # prefill S tokens then teacher-force the last one
        _, cache = model.prefill(params, jnp.asarray(tokens[:, :S]), S + 4)
        step_logits, _ = model.decode_step(
            params, cache, jnp.asarray(tokens[:, S:]), jnp.int32(S)
        )
    np.testing.assert_allclose(
        np.asarray(step_logits, np.float32),
        np.asarray(full_logits, np.float32),
        rtol=0.15, atol=0.15,
    )


@pytest.mark.parametrize("name", ARCHS)
def test_full_config_param_specs(name):
    """FULL configs build abstract params only (no allocation) with sane
    parameter counts."""
    from repro.configs import get_config

    cfg = get_config(name)
    ctx = make_ctx(cfg)
    model = build_model(ctx)
    specs = model.param_specs()
    n = count_params(specs)
    expected = {
        "deepseek-v3-671b": (600e9, 750e9),
        "granite-moe-1b-a400m": (0.8e9, 1.6e9),
        "whisper-medium": (0.6e9, 0.95e9),  # enc+dec 24L each ≈ 769M + pads
        "qwen2-vl-72b": (60e9, 80e9),
        "rwkv6-7b": (6e9, 9e9),
        "granite-8b": (7e9, 9.5e9),
        "smollm-135m": (0.1e9, 0.2e9),
        "stablelm-1.6b": (1.3e9, 2.0e9),
        "deepseek-7b": (6e9, 8e9),
        "zamba2-1.2b": (0.9e9, 1.8e9),
        "granite-4.0-h-micro": (3.0e9, 3.4e9),
    }
    lo, hi = expected[name]
    assert lo < n < hi, f"{name}: {n/1e9:.2f}B params outside [{lo/1e9},{hi/1e9}]B"
    abstract = abstract_params(specs)
    assert all(
        hasattr(x, "shape") for x in jax.tree.leaves(abstract)
    )
