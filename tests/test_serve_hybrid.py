"""Mamba2 layers beside attention (granite-4.0-h-micro, smoke preset): the
model against a plain float32 reference, and the serve engine's state slots.

The reference is the benchmark's own (``benchmarks/chip/archs/
granitemoehybrid.py``, loaded by path): float32, every matrix product at
``Precision.HIGHEST``, Mamba2 layers one token at a time; it imports nothing
of the program.  Its weights (``shapes``, drawn by the benchmark's
``weights.make``) are the program's parameter layout.
"""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.serve.engine import ServeEngine, serve_context
from test_serve_engine import serve

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CFG = get_smoke_config("granite-4.0-h-micro")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's weights and the architecture's reference module."""
    sys.path.insert(0, str(CHIP))
    try:
        import weights

        spec = importlib.util.spec_from_file_location(
            "arch_granitemoehybrid_tests", CHIP / "archs" / "granitemoehybrid.py")
        arch_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(arch_mod)
    finally:
        sys.path.remove(str(CHIP))
    import json

    config = json.loads((CHIP / "configs" / "granite-4.0-h-micro.json").read_text())
    arch = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    arch.update(config["weights"], padded_vocab=config["program"]["padded_vocab"])
    arch = arch_mod.rehearse(arch, CFG)
    return arch_mod, arch, weights.make(arch_mod.shapes(arch), 2**31 + 17)


def _engine(params, *, dtype="bfloat16", slots=2, max_len=64, use_kernels=False, **kw):
    ctx = serve_context(CFG.with_(dtype=dtype), use_kernels=use_kernels)
    return ServeEngine(ctx, params, slots=slots, max_len=max_len, page_size=8, eos_id=-1, **kw)


def _sequential(model, params, prompt, n, max_len):
    """Greedy decode of one request alone: prefill, then one token a step."""
    vocab = model.cfg.vocab
    prefill = jax.jit(model.prefill, static_argnums=2)
    step = jax.jit(model.decode_step)
    logits, cache = prefill(params, jnp.asarray(prompt[None]), max_len)
    out = [int(jnp.argmax(logits[0, :vocab]))]
    for pos in range(len(prompt), len(prompt) + n - 1):
        logits, cache = step(params, cache, jnp.asarray([[out[-1]]], jnp.int32), jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, :vocab])))
    return out


@pytest.fixture
def interpret_kernels(monkeypatch):
    for name in ("flash_attention", "paged_attention", "ssd"):
        monkeypatch.setattr(ops, name, functools.partial(getattr(ops, name), impl="interpret"))


# (program dtype, Pallas kernels in interpret mode, tolerance on logits whose
# spread is about 5e-3): float32 agrees to rounding; bfloat16 weights and
# activations to a few percent of the spread
@pytest.mark.parametrize("dtype,kernels,atol", [
    ("float32", False, 1e-6), ("float32", True, 1e-6), ("bfloat16", True, 2e-3),
])
def test_prefill_then_decode_logits_match_the_float32_reference(
        bench, dtype, kernels, atol, request):
    """Prefill of a 16-token prompt, then eight decode steps through the
    K/V cache and the carried state: every logit against the reference's
    full forward pass over the same tokens."""
    arch_mod, arch, w = bench
    if kernels:
        request.getfixturevalue("interpret_kernels")
    model = _engine(w, dtype=dtype, use_kernels=kernels).model
    tokens = np.random.default_rng(3).integers(1, CFG.vocab, 24).astype(np.int32)
    ref = np.asarray(arch_mod.reference_logits(w, arch, tokens))
    logits, cache = jax.jit(model.prefill, static_argnums=2)(w, jnp.asarray(tokens[None, :16]), 32)
    got = [np.asarray(logits[0, : CFG.vocab], np.float32)]
    step = jax.jit(model.decode_step)
    for pos in range(16, 23):
        logits, cache = step(w, cache, jnp.asarray(tokens[None, pos:pos + 1]), jnp.int32(pos))
        got.append(np.asarray(logits[0, : CFG.vocab], np.float32))
    np.testing.assert_allclose(np.stack(got), ref[15:23], rtol=0, atol=atol)
    assert np.abs(ref[15:23]).max() > 5 * atol  # the logits carry signal


def test_a_state_reset_moves_the_reference(bench):
    """The reference's ``state_reset`` variant changes every logit after the
    reset: the benchmark's control of the carried state can fail a run."""
    arch_mod, arch, w = bench
    tokens = np.random.default_rng(4).integers(1, CFG.vocab, 24).astype(np.int32)
    full = np.asarray(arch_mod.reference_logits(w, arch, tokens))
    reset = np.asarray(arch_mod.reference_logits(w, arch, tokens, reset_at=16))
    np.testing.assert_array_equal(full[:16], reset[:16])
    assert np.abs(full[16:] - reset[16:]).max() > 1e-3


@pytest.mark.parametrize("case", ["admission_batch", "slot_reuse", "shared_prefix"])
def test_engine_tokens_equal_a_sequential_decode(bench, case):
    """Greedy tokens through the engine (state rows in the slot pool, K/V in
    pages, every slot decoded in one step) equal each request decoded
    alone.  ``admission_batch``: four requests admitted in one batch;
    ``slot_reuse``: one slot serves three requests in turn, so a stale row
    would show; ``shared_prefix``: prompts sharing two pages, which a model
    with recurrent state must not share."""
    _, _, w = bench
    rng = np.random.default_rng({"admission_batch": 5, "slot_reuse": 6, "shared_prefix": 7}[case])
    slots = {"admission_batch": 4, "slot_reuse": 1, "shared_prefix": 2}[case]
    engine = _engine(w, dtype="float32", slots=slots)
    prefix = rng.integers(1, CFG.vocab, 16).astype(np.int32)
    reqs = {}
    for i in range(4 if case != "slot_reuse" else 3):
        tail = rng.integers(1, CFG.vocab, int(rng.integers(2, 12))).astype(np.int32)
        prompt = np.concatenate([prefix, tail]) if case == "shared_prefix" else tail
        reqs[f"r{i}"] = (prompt, int(rng.integers(3, 10)))
    done, _ = serve(engine, reqs)
    for rid, (prompt, n) in reqs.items():
        assert done[rid]["tokens"] == _sequential(engine.model, w, prompt, n, 64), rid
    m = engine.metrics
    assert m["state_inserts"] == len(reqs)
    assert m["state_row_steps"] == slots * m["decode_steps"] > 0
    if case == "admission_batch":
        assert m["batched_prefills"] >= 1
    if case == "shared_prefix":
        assert not engine.share_prefixes and m["prefix_shared_pages"] == 0
    assert engine.pages.pages_in_use() == 0
    engine.close()


def test_admission_overwrites_the_slot_row_with_the_prefill_state(bench):
    """After an admission, the slot's row of the state pool is the prefill's
    final state exactly, whatever the row held; the other rows are
    untouched."""
    from repro.serve.engine import Request

    _, _, w = bench
    engine = _engine(w, dtype="float32", slots=2)
    engine._ensure_cache()
    engine._state = jax.tree.map(lambda a: jnp.full_like(a, 7.0), engine._state)
    prompt = np.random.default_rng(8).integers(1, CFG.vocab, 13).astype(np.int32)
    engine.admit(Request("a", prompt, 4), 1)
    _, cache = engine._prefill(w, jnp.asarray(prompt[None]))  # the program admission ran
    for got, want in zip(jax.tree.leaves(engine._state), jax.tree.leaves(cache["state"])):
        np.testing.assert_array_equal(np.asarray(got[:, 1]), np.asarray(want[:, 0]))
        assert np.all(np.asarray(got[:, 0]) == 7.0)
    engine.close()


def test_speculative_decode_raises_for_a_model_with_state(bench):
    _, _, w = bench
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(w, spec_k=2)
