"""The comparison that decides ``correct``, over a plain float32 reference.

The reference imports nothing of the program.  Its block is the
architecture's own (``archs/<model_type>.py``: ``hidden`` and ``logits``),
built from the parts here: float32 throughout, every matrix product at
``Precision.HIGHEST`` (``_mm``), RMSNorm, the rotate-half rotary embedding.

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


@functools.partial(jax.jit, static_argnames=("model", "arch_items", "control"))
def _gaps(w, tokens, at, served, model, arch_items, control):
    arch = dict(arch_items)
    ref = model.logits(w, arch, model.hidden(w, arch, tokens)[at])
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    out = {"served": best - got}
    if control:
        low = model.logits(w, arch, model.hidden(w, arch, tokens, fp8=True)[at], fp8=True)
        pick = jnp.argmax(low, axis=-1)
        out["control"] = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return out


def gaps(model, w: dict, arch: dict, prompt, served, *, pad_to: int, out_pad: int,
         control: bool = False) -> dict[str, np.ndarray]:
    """Per served token: the reference's best logit minus the served token's
    logit, at the position that produced it (and, with ``control``, the same
    for the float8 run's first choice), under the block of ``model``, the
    architecture's module.  The sequence is padded to ``pad_to`` tokens and
    the answer to ``out_pad``, so one program serves every request; causal
    attention keeps the padding inert.  A module that defines ``gaps``
    itself (to compute a long context in blocks, say) is called in this
    one's place, with the same arguments after ``model``."""
    if hasattr(model, "gaps"):
        return model.gaps(w, arch, prompt, served, pad_to=pad_to, out_pad=out_pad,
                          control=control)
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
    out = _gaps(w, jnp.asarray(tokens), jnp.asarray(at), jnp.asarray(sv), model, items,
                control)
    return {k: np.asarray(v)[:n] for k, v in out.items()}
