"""The Llama decoder (``model_type`` ``llama``): what the benchmark needs of
it, found by that name.

Published architecture: RMSNorm, rotary embedding over the whole head in the
rotate-half form, causal grouped-query attention with scale 1/sqrt(head
dim), SiLU-gated MLP, tied or untied output head.  The program runs it as
its ``dense`` family.

The reference (``hidden``, ``logits``) computes in float32 with every matrix
product at ``Precision.HIGHEST`` (``reference._mm``), one layer at a time
under ``lax.scan``, over one sequence at a time.  It imports nothing of the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, _mm, _rms, _rope
from weights import BF16
from work import DTYPE_BYTES

# -- the program ---------------------------------------------------------------------


def check(cfg, config: dict) -> str | None:
    """How the program's ``ArchConfig`` departs from the configuration
    file's published sizes, or None where it does not."""
    c, prog = config, config["program"]
    want = {
        "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "n_layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
        "tie_embeddings": c["tie_word_embeddings"], "rope_theta": c["rope_theta"],
        "padded_vocab": prog["padded_vocab"], "head_dim_": c["hidden_size"] // c["num_attention_heads"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want or cfg.family != "dense" or cfg.norm != "rmsnorm" or cfg.rotary_pct != 1.0:
        return f"{got} vs {want}"
    return None


def rehearse(arch: dict, cfg) -> dict:
    """The architecture with the sizes of the program's smoke preset ``cfg``."""
    return dict(
        arch,
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab,
        padded_vocab=cfg.padded_vocab, tie_word_embeddings=cfg.tie_embeddings,
    )


# -- weights ---------------------------------------------------------------------------


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


# -- the reference ---------------------------------------------------------------------


def hidden(w: dict, arch: dict, tokens, fp8: bool = False):
    """Final normed hidden states (S, E) of one token sequence."""
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    S = tokens.shape[0]
    G = H // Hkv
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"]["embedding"][tokens].astype(F32)

    def block(x, lw):
        h = _rms(x, lw["ln1"]["scale"], eps)
        q = _rope(_mm("se,ehd->shd", h, lw["attn"]["wq"], fp8), pos, theta)
        k = _rope(_mm("se,ehd->shd", h, lw["attn"]["wk"], fp8), pos, theta)
        v = _mm("se,ehd->shd", h, lw["attn"]["wv"], fp8)
        D = q.shape[-1]
        qg = q.reshape(S, Hkv, G, D)
        s = _mm("qhgd,khd->hgqk", qg, k, fp8) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("hgqk,khd->qhgd", p, v, fp8).reshape(S, H, D)
        x = x + _mm("shd,hde->se", o, lw["attn"]["wo"], fp8)
        h = _rms(x, lw["ln2"]["scale"], eps)
        g = _mm("se,ef->sf", h, lw["ffn"]["wg"], fp8)
        u = _mm("se,ef->sf", h, lw["ffn"]["wi"], fp8)
        x = x + _mm("sf,fe->se", jax.nn.silu(g) * u, lw["ffn"]["wo"], fp8)
        return x, None

    x, _ = jax.lax.scan(block, x, w["dense_layers"])
    return _rms(x, w["final_norm"]["scale"], eps)


def logits(w: dict, arch: dict, h, fp8: bool = False):
    """Logits over the real vocabulary for hidden states ``h`` (K, E)."""
    if arch["tie_word_embeddings"]:
        out = _mm("ke,ve->kv", h, w["embed"]["embedding"], fp8)
    else:
        out = _mm("ke,ev->kv", h, w["embed"]["unembed"], fp8)
    return out[:, : arch["vocab_size"]]


def controls(arch: dict, config: dict) -> dict[str, dict]:
    """Variants of the architecture the control reports beside it: the
    published RMSNorm eps, where the file runs another."""
    cut = config.get("reduced", {}).get("rms_norm_eps")
    return {"published_eps": dict(arch, rms_norm_eps=cut["published"])} if cut else {}


# -- work --------------------------------------------------------------------------------


def _dims(arch: dict):
    E, H = arch["hidden_size"], arch["num_attention_heads"]
    return (E, H, arch["num_key_value_heads"], E // H,
            arch["intermediate_size"], arch["num_hidden_layers"], arch["vocab_size"])


def dense_flops_per_token(arch: dict) -> float:
    """Projections and MLP of every layer, for one token."""
    E, H, Hkv, D, F, L, _ = _dims(arch)
    return 2.0 * L * (E * H * D + 2 * E * Hkv * D + H * D * E + 3 * E * F)


def head_flops(arch: dict) -> float:
    """The output head for one token, over the real vocabulary."""
    E, *_, V = _dims(arch)
    return 2.0 * E * V


def flash_attention(arch: dict, prompt_lens) -> tuple[float, float]:
    """Causal self-attention over each prompt, every layer: (ops, bytes)."""
    E, H, Hkv, D, F, L, V = _dims(arch)
    flops = bytes_ = 0.0
    for s in prompt_lens:
        flops += L * 4.0 * H * D * s * (s + 1) / 2  # QK^T and PV, lower triangle
        bytes_ += L * s * (2 * H + 2 * Hkv) * D * DTYPE_BYTES  # q, k, v in; o out
    return flops, bytes_


def paged_attention(arch: dict, ctx_lens) -> tuple[float, float]:
    """One decode query per sequence over its ``ctx`` live cached tokens,
    every layer: (ops, bytes)."""
    E, H, Hkv, D, F, L, V = _dims(arch)
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += L * 4.0 * H * D * c
        bytes_ += L * (2 * c * Hkv * D + 2 * H * D) * DTYPE_BYTES  # K, V; q, o
    return flops, bytes_
