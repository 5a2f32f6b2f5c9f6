"""Plain reference of a Llama-style decoder in float32, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  It follows the published
architecture (RMSNorm, rotary embedding over the whole head in the
rotate-half form, causal grouped-query attention with scale 1/sqrt(head
dim), SiLU-gated MLP, tied or untied output head), computes in float32 with
every matrix product at ``Precision.HIGHEST``, one layer at a time under
``lax.scan``, over one sequence at a time.

The comparison: for each served token, how far its logit lies below the
reference's best logit at that position (0 where the served token is the
reference's argmax); the widest such gap over the sampled requests is the
number held against the configuration's ``max_logit_gap``.

The control (``control=True``) runs the same reference with every matrix
product's operands rounded to float8 (e4m3, one scale per tensor), the step
below the served bfloat16, and reads the gap of the token that precision
puts first at each of the same positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = E4M3_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _mm(spec, a, b, fp8: bool):
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, heads, D): rotate-half rotary embedding at positions ``pos``."""
    D = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos[:, None].astype(F32) * freqs  # (S, D/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


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


@functools.partial(jax.jit, static_argnames=("arch_items", "control"))
def _gaps(w, tokens, at, served, arch_items, control):
    arch = dict(arch_items)
    ref = logits(w, arch, hidden(w, arch, tokens)[at])
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    out = {"served": best - got}
    if control:
        low = logits(w, arch, hidden(w, arch, tokens, fp8=True)[at], fp8=True)
        pick = jnp.argmax(low, axis=-1)
        out["control"] = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return out


def gaps(w: dict, arch: dict, prompt, served, *, pad_to: int, out_pad: int,
         control: bool = False) -> dict[str, np.ndarray]:
    """Per served token: the reference's best logit minus the served token's
    logit, at the position that produced it (and, with ``control``, the same
    for the float8 run's first choice).  The sequence is padded to
    ``pad_to`` tokens and the answer to ``out_pad``, so one program serves
    every request; causal attention keeps the padding inert."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n = len(served)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad_to or n > out_pad:
        raise ValueError(f"sequence {len(seq)}/{n} exceeds the pads {pad_to}/{out_pad}")
    tokens = np.zeros((pad_to,), np.int32)
    tokens[: len(seq)] = seq
    at = np.zeros((out_pad,), np.int32)
    at[:n] = len(prompt) - 1 + np.arange(n)
    sv = np.zeros((out_pad,), np.int32)
    sv[:n] = served
    items = tuple(sorted((k, v) for k, v in arch.items() if not isinstance(v, (dict, list))))
    out = _gaps(w, jnp.asarray(tokens), jnp.asarray(at), jnp.asarray(sv), items, control)
    return {k: np.asarray(v)[:n] for k, v in out.items()}
