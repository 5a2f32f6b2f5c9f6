"""The Granite-4.0-H decoder (``model_type`` ``granitemoehybrid``) with no
experts: what the benchmark needs of it, found by that name.

Published architecture (``num_local_experts`` 0): the embedding times
``embedding_multiplier``; then layer ``i`` is ``x + r·mixer(rmsnorm(x))``
and ``x + r·mlp(rmsnorm(x))``, ``r`` = ``residual_multiplier``, where the
mixer is attention if ``layer_types[i]`` says so and Mamba-2 otherwise;
final RMSNorm, tied head, logits divided by ``logits_scaling``.

- attention: grouped-query, no positional encoding (``nope``), softmax
  scale ``attention_multiplier``, no biases;
- Mamba-2 (one group): ``in_proj`` (no bias) to z, x, B, C and dt; a
  causal depthwise conv of ``mamba_d_conv`` with bias over (x, B, C), then
  SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(dt_t·A) h_{t-1} + dt_t·x_t ⊗ B_t``, ``y_t = C_t·h_t + D·x_t``;
  RMSNorm with gain of ``y · silu(z)`` over the whole inner width;
  ``out_proj`` (no bias);
- MLP: SiLU-gated, ``shared_intermediate_size`` wide.

The program runs it as its ``mamba_attn`` family.  The configuration file
carries the layer pattern as two scalars, ``attn_layer_period`` and
``attn_layer_offset`` (attention where ``i % period == offset``), since the
harness keeps only a file's scalar keys; ``check`` fails unless they
reproduce ``layer_types``.

The reference (``gaps``) computes in float32 with every matrix product at
``Precision.HIGHEST`` (``reference._mm``), one sequence at a time; its
Mamba-2 layers run the token-by-token recurrence, not the chunked form the
program's kernel runs.  It imports nothing of the program.  It departs from
the published block where the program does, and so the comparison holds the
program to what it runs:

- every RMSNorm, the gated one included, uses ``rms_norm_eps`` as the file
  runs it (1e-6; the published 1e-5 is listed in ``reduced``);
- ``A_log`` is clipped to [-8, 4] before ``exp``, as the program does (the
  weights here draw it with std 0.8, so the clip seldom bites);
- weights are random from the seed (``shapes``): fan-in scaled matrices,
  embedding std ``embed_std``, norm gains and ``D`` ones, ``A_log`` and
  ``dt_bias`` normal with std 0.8 and 2 (so heads forget over one token to
  hundreds), conv weight and bias with std 1/sqrt(``mamba_d_conv``).

A variant with ``state_reset`` true zeroes every Mamba-2 layer's conv and
SSD state at the first decoded position, the control of the state the
engine carries between its prefill and its decode steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, HI, _mm, _rms
from weights import BF16
from work import DTYPE_BYTES

A_LOG_CLIP = (-8.0, 4.0)

# -- the program ---------------------------------------------------------------------


def pattern(arch: dict) -> list[str]:
    """``layer_types`` as the two pattern scalars give it."""
    per, off = arch["attn_layer_period"], arch["attn_layer_offset"]
    return ["attention" if i % per == off else "mamba" for i in range(arch["num_hidden_layers"])]


def check(cfg, config: dict) -> str | None:
    """How the program's ``ArchConfig`` departs from the configuration
    file's published sizes, or None where it does not."""
    c, prog = config, config["program"]
    if pattern(c) != c["layer_types"]:
        return (f"attn_layer_period {c['attn_layer_period']} and attn_layer_offset "
                f"{c['attn_layer_offset']} do not reproduce layer_types")
    fixed = {"num_local_experts": 0, "mamba_n_groups": 1, "mamba_proj_bias": False,
             "attention_bias": False, "hidden_act": "silu", "position_embedding_type": "nope",
             "normalization_function": "rmsnorm"}
    off = {k: c.get(k) for k, v in fixed.items() if c.get(k) != v}
    if off:
        return f"the program runs none of {off}"
    d_inner = c["mamba_expand"] * c["hidden_size"]
    if c["mamba_n_heads"] * c["mamba_d_head"] != d_inner:
        return f"mamba_n_heads x mamba_d_head is not the inner width {d_inner}"
    want = {
        "family": "mamba_attn", "norm": "rmsnorm", "rotary_pct": 0.0,
        "d_model": c["hidden_size"], "d_ff": c["shared_intermediate_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "n_layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
        "tie_embeddings": c["tie_word_embeddings"], "padded_vocab": prog["padded_vocab"],
        "head_dim_": c["hidden_size"] // c["num_attention_heads"],
        "ssm_state": c["mamba_d_state"], "ssm_head_dim": c["mamba_d_head"],
        "ssm_expand": c["mamba_expand"], "ssm_conv": c["mamba_d_conv"],
        "ssm_conv_bias": c["mamba_conv_bias"],
        "attn_layer_period": c["attn_layer_period"], "attn_layer_offset": c["attn_layer_offset"],
        "embedding_multiplier": c["embedding_multiplier"],
        "residual_multiplier": c["residual_multiplier"],
        "attention_multiplier": c["attention_multiplier"],
        "logits_scaling": c["logits_scaling"],
    }
    got = {k: getattr(cfg, k) for k in want}
    return None if got == want else f"{got} vs {want}"


def rehearse(arch: dict, cfg) -> dict:
    """The architecture with the sizes of the program's smoke preset ``cfg``."""
    return dict(
        arch,
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff, shared_intermediate_size=cfg.d_ff,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab, padded_vocab=cfg.padded_vocab,
        tie_word_embeddings=cfg.tie_embeddings,
        mamba_d_state=cfg.ssm_state, mamba_d_head=cfg.ssm_head_dim,
        mamba_n_heads=cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim,
        mamba_expand=cfg.ssm_expand, mamba_d_conv=cfg.ssm_conv,
        attn_layer_period=cfg.attn_layer_period, attn_layer_offset=cfg.attn_layer_offset,
    )


# -- weights ---------------------------------------------------------------------------


def _dims(arch: dict):
    E, H = arch["hidden_size"], arch["num_attention_heads"]
    d_inner = arch["mamba_expand"] * E
    N = arch["mamba_d_state"]
    return {
        "E": E, "H": H, "Hkv": arch["num_key_value_heads"], "D": E // H,
        "F": arch["shared_intermediate_size"], "V": arch["vocab_size"],
        "Vp": arch["padded_vocab"], "L": arch["num_hidden_layers"],
        "per": arch["attn_layer_period"], "pre": arch["attn_layer_offset"],
        "post": arch["attn_layer_period"] - arch["attn_layer_offset"] - 1,
        "periods": arch["num_hidden_layers"] // arch["attn_layer_period"],
        "d_inner": d_inner, "Hs": arch["mamba_n_heads"], "P": arch["mamba_d_head"],
        "N": N, "K": arch["mamba_d_conv"], "Dc": d_inner + 2 * N,
    }


def _layer(d, mixer: dict) -> dict:
    E, F = d["E"], d["F"]
    return {
        ("ln1", "scale"): ((E,), F32, 1.0, None),
        **mixer,
        ("ln2", "scale"): ((E,), F32, 1.0, None),
        ("ffn", "wi"): ((E, F), BF16, None, E),
        ("ffn", "wg"): ((E, F), BF16, None, E),
        ("ffn", "wo"): ((F, E), BF16, None, F),
    }


def shapes(arch: dict) -> dict[tuple, tuple]:
    """``path -> (shape, dtype, scale, fan_in)``: scale is the stated std
    (or the fill of a norm scale), None means fan-in scaled; fan_in is what
    one layer's matrix contracts over."""
    d = _dims(arch)
    E, H, Hkv, D, Hs, di, N, K, Dc = (d[k] for k in ("E", "H", "Hkv", "D", "Hs", "d_inner",
                                                     "N", "K", "Dc"))
    attn = _layer(d, {
        ("attn", "wq"): ((E, H, D), BF16, None, E),
        ("attn", "wk"): ((E, Hkv, D), BF16, None, E),
        ("attn", "wv"): ((E, Hkv, D), BF16, None, E),
        ("attn", "wo"): ((H, D, E), BF16, None, H * D),
    })
    mamba = _layer(d, {
        ("mixer", "in_proj_z"): ((E, di), BF16, None, E),
        ("mixer", "in_proj_x"): ((E, di), BF16, None, E),
        ("mixer", "in_proj_B"): ((E, N), BF16, None, E),
        ("mixer", "in_proj_C"): ((E, N), BF16, None, E),
        ("mixer", "in_proj_dt"): ((E, Hs), BF16, None, E),
        ("mixer", "conv_w"): ((K, Dc), F32, None, K),
        **({("mixer", "conv_b"): ((Dc,), F32, None, K)} if arch["mamba_conv_bias"] else {}),
        ("mixer", "dt_bias"): ((Hs,), F32, 2.0, None),
        ("mixer", "a_log"): ((Hs,), F32, 0.8, None),
        ("mixer", "D"): ((Hs,), F32, 1.0, None),
        ("mixer", "norm_gate"): ((di,), F32, 1.0, None),
        ("mixer", "out_proj"): ((di, E), BF16, None, di),
    })
    t = {
        ("embed", "embedding"): ((d["Vp"], E), BF16, arch["embed_std"], None),
        ("final_norm", "scale"): ((E,), F32, 1.0, None),
    }
    n = d["periods"]
    for path, (shape, dtype, scale, fan_in) in attn.items():
        t[("periods", "attn") + path] = ((n,) + shape, dtype, scale, fan_in)
    for name in ("pre", "post"):
        if d[name]:
            for path, (shape, dtype, scale, fan_in) in mamba.items():
                t[("periods", name) + path] = ((n, d[name]) + shape, dtype, scale, fan_in)
    if not arch["tie_word_embeddings"]:
        t[("embed", "unembed")] = ((E, d["Vp"]), BF16, arch["embed_std"], None)
    return t


# -- the reference ---------------------------------------------------------------------


def _mlp(x, lw, arch, fp8):
    r, eps = arch["residual_multiplier"], arch["rms_norm_eps"]
    h = _rms(x, lw["ln2"]["scale"], eps)
    g = _mm("se,ef->sf", h, lw["ffn"]["wg"], fp8)
    u = _mm("se,ef->sf", h, lw["ffn"]["wi"], fp8)
    return x + r * _mm("sf,fe->se", jax.nn.silu(g) * u, lw["ffn"]["wo"], fp8)


def _attention(x, lw, arch, fp8):
    r, eps = arch["residual_multiplier"], arch["rms_norm_eps"]
    H, Hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"]["scale"], eps)
    q = _mm("se,ehd->shd", h, lw["attn"]["wq"], fp8)
    k = _mm("se,ehd->shd", h, lw["attn"]["wk"], fp8)
    v = _mm("se,ehd->shd", h, lw["attn"]["wv"], fp8)
    D = q.shape[-1]
    qg = q.reshape(S, Hkv, H // Hkv, D)
    s = _mm("qhgd,khd->hgqk", qg, k, fp8) * arch["attention_multiplier"]
    p = jax.nn.softmax(jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf), axis=-1)
    o = _mm("hgqk,khd->qhgd", p, v, fp8).reshape(S, H, D)
    return _mlp(x + r * _mm("shd,hde->se", o, lw["attn"]["wo"], fp8), lw, arch, fp8)


def _mamba(x, lw, arch, reset_at, fp8):
    """One Mamba-2 layer over the sequence, the SSD one token at a time;
    with ``reset_at`` >= 0, the conv and SSD state are zero there."""
    r, eps = arch["residual_multiplier"], arch["rms_norm_eps"]
    d = _dims(arch)
    S, Hs, P, N, K, di = x.shape[0], d["Hs"], d["P"], d["N"], d["K"], d["d_inner"]
    m = lw["mixer"]
    h = _rms(x, lw["ln1"]["scale"], eps)
    z = _mm("se,ei->si", h, m["in_proj_z"], fp8)
    u = jnp.concatenate([_mm("se,ei->si", h, m["in_proj_" + k], fp8) for k in "xBC"], axis=-1)
    dt = jax.nn.softplus(_mm("se,eh->sh", h, m["in_proj_dt"], fp8) + m["dt_bias"])
    # causal conv: tap i reads the input K-1-i positions back; none from
    # before reset_at reaches a position at or after it
    t = jnp.arange(S)
    up = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    conv = m["conv_b"] if "conv_b" in m else jnp.zeros((u.shape[1],), F32)
    for i in range(K):
        src = t - (K - 1 - i)
        cut = (reset_at >= 0) & (t >= reset_at) & (src < reset_at)
        conv = conv + jnp.where(cut[:, None], 0.0, up[i : i + S]) * m["conv_w"][i]
    u = jax.nn.silu(conv)
    xs = u[:, :di].reshape(S, Hs, P)
    Bm, Cm = u[:, di : di + N], u[:, di + N :]
    decay = jnp.exp(dt * -jnp.exp(jnp.clip(m["a_log"], *A_LOG_CLIP)))  # (S, Hs)

    def step(s, xs_t):
        t_, x_t, dt_t, a_t, B_t, C_t = xs_t
        s = jnp.where(t_ == reset_at, 0.0, s)
        s = s * a_t[:, None, None] + (dt_t[:, None] * x_t)[:, :, None] * B_t
        y = jnp.einsum("n,hpn->hp", C_t, s, precision=HI) + m["D"][:, None] * x_t
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), F32), (t, xs, dt, decay, Bm, Cm))
    y = y.reshape(S, di) * jax.nn.silu(z)
    y = _rms(y, m["norm_gate"], eps)
    return _mlp(x + r * _mm("si,ie->se", y, m["out_proj"], fp8), lw, arch, fp8)


def _hidden(w, arch, tokens, reset_at, fp8):
    """Final normed hidden states (S, E) of one token sequence."""
    x = w["embed"]["embedding"][tokens].astype(F32) * arch["embedding_multiplier"]

    def mambas(x, stack):
        return jax.lax.scan(lambda x, lw: (_mamba(x, lw, arch, reset_at, fp8), None), x, stack)[0]

    def period(x, pw):
        if "pre" in pw:
            x = mambas(x, pw["pre"])
        x = _attention(x, pw["attn"], arch, fp8)
        if "post" in pw:
            x = mambas(x, pw["post"])
        return x, None

    x, _ = jax.lax.scan(period, x, w["periods"])
    return _rms(x, w["final_norm"]["scale"], arch["rms_norm_eps"])


def _logits(w, arch, h, fp8):
    if arch["tie_word_embeddings"]:
        out = _mm("ke,ve->kv", h, w["embed"]["embedding"], fp8)
    else:
        out = _mm("ke,ev->kv", h, w["embed"]["unembed"], fp8)
    return out[:, : arch["vocab_size"]] / arch["logits_scaling"]


def reference_logits(w, arch, tokens, reset_at=-1, fp8: bool = False):
    """Logits (S, vocab) at every position of one token sequence."""
    return _logits(w, arch, _hidden(w, arch, jnp.asarray(tokens), reset_at, fp8), fp8)


@functools.partial(jax.jit, static_argnames=("arch_items", "control"))
def _gaps(w, tokens, at, served, reset_at, arch_items, control):
    arch = dict(arch_items)
    ref = _logits(w, arch, _hidden(w, arch, tokens, reset_at, False)[at], False)
    best = ref.max(axis=-1)
    out = {"served": best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]}
    if control:
        low = _logits(w, arch, _hidden(w, arch, tokens, reset_at, True)[at], True)
        pick = jnp.argmax(low, axis=-1)
        out["control"] = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return out


def gaps(w: dict, arch: dict, prompt, served, *, pad_to: int, out_pad: int,
         control: bool = False) -> dict[str, np.ndarray]:
    """Per served token, the reference's best logit minus the served
    token's, at the position that produced it (``reference.gaps``'s
    contract); the sequence is padded to ``pad_to`` and the answer to
    ``out_pad`` so one program serves every request (causal attention and
    the recurrence keep the padding inert)."""
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
    reset_at = len(prompt) if arch.get("state_reset") else -1
    items = tuple(sorted((k, v) for k, v in arch.items()
                         if k != "state_reset" and not isinstance(v, (dict, list))))
    out = _gaps(w, jnp.asarray(tokens), jnp.asarray(at), jnp.asarray(sv), jnp.int32(reset_at),
                items, control)
    return {k: np.asarray(v)[:n] for k, v in out.items()}


def controls(arch: dict, config: dict) -> dict[str, dict]:
    """Variants the control reports beside the float8 picks: every Mamba-2
    state zeroed where decoding begins, and the published RMSNorm eps where
    the file runs another."""
    out = {"state_reset": dict(arch, state_reset=True)}
    cut = config.get("reduced", {}).get("rms_norm_eps")
    if cut:
        out["published_eps"] = dict(arch, rms_norm_eps=cut["published"])
    return out


# -- work --------------------------------------------------------------------------------


def dense_flops_per_token(arch: dict) -> float:
    """Every layer's projections and MLP, and each Mamba-2 layer's conv and
    recurrence (a multiply-add per state element to decay and add to it, one
    to read it out), for one token."""
    d = _dims(arch)
    E, H, Hkv, D, F = d["E"], d["H"], d["Hkv"], d["D"], d["F"]
    n_attn, n_mamba = d["periods"], d["L"] - d["periods"]
    mlp = 3 * E * F
    attn = E * H * D + 2 * E * Hkv * D + H * D * E
    mamba = (E * (2 * d["d_inner"] + 2 * d["N"] + d["Hs"]) + d["d_inner"] * E
             + d["K"] * d["Dc"] + 2 * d["Hs"] * d["P"] * d["N"])
    return 2.0 * (d["L"] * mlp + n_attn * attn + n_mamba * mamba)


def head_flops(arch: dict) -> float:
    """The output head for one token, over the real vocabulary."""
    d = _dims(arch)
    return 2.0 * d["E"] * d["V"]


def flash_attention(arch: dict, prompt_lens) -> tuple[float, float]:
    """Causal self-attention over each prompt in the attention layers:
    (ops, bytes)."""
    d = _dims(arch)
    n, H, Hkv, D = d["periods"], d["H"], d["Hkv"], d["D"]
    flops = bytes_ = 0.0
    for s in prompt_lens:
        flops += n * 4.0 * H * D * s * (s + 1) / 2
        bytes_ += n * s * (2 * H + 2 * Hkv) * D * DTYPE_BYTES
    return flops, bytes_


def paged_attention(arch: dict, ctx_lens) -> tuple[float, float]:
    """One decode query per sequence over its ``ctx`` live cached tokens in
    the attention layers: (ops, bytes)."""
    d = _dims(arch)
    n, H, Hkv, D = d["periods"], d["H"], d["Hkv"], d["D"]
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += n * 4.0 * H * D * c
        bytes_ += n * (2 * c * Hkv * D + 2 * H * D) * DTYPE_BYTES
    return flops, bytes_


CHUNK = 128  # the program's chunked scan (ops.ssd): chunks of at most 128 tokens


def ssd_scan(arch: dict, prompt_lens) -> tuple[float, float]:
    """The chunked SSD scan of each prompt through every Mamba-2 layer, from
    a zero state: (ops, bytes).  Per head and chunk of ``c`` tokens: C·h and
    the state update, 2cNP each; the causal (C·B) tile and its combine with
    Δx over the c(c+1)/2 pairs, 2N and 2P each.  Bytes: x in and y out
    (bfloat16), dt and the log decay (float32) per head and token, B and C
    (bfloat16) once per sequence, the final state out (float32)."""
    d = _dims(arch)
    n_mamba, Hs, P, N = d["L"] - d["periods"], d["Hs"], d["P"], d["N"]
    flops = bytes_ = 0.0
    for s in prompt_lens:
        for c0 in range(0, s, CHUNK):
            c = min(CHUNK, s - c0)
            flops += n_mamba * Hs * (4.0 * c * N * P + c * (c + 1) * (N + P))
        bytes_ += n_mamba * (s * Hs * (2 * P * DTYPE_BYTES + 2 * 4) + s * 2 * N * DTYPE_BYTES
                             + Hs * P * N * 4)
    return flops, bytes_


def state_row_bytes(arch: dict) -> int:
    """One sequence's recurrent state: every Mamba-2 layer's conv inputs
    (bfloat16) and SSD state (float32)."""
    d = _dims(arch)
    per_layer = (d["K"] - 1) * d["Dc"] * DTYPE_BYTES + d["Hs"] * d["P"] * d["N"] * 4
    return (d["L"] - d["periods"]) * per_layer


def weight_bytes(arch: dict) -> int:
    """Every weight as served (``shapes``)."""
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for shape, dtype, _, _ in shapes(arch).values())


def decode_hbm_bytes(arch: dict, steps: int, tokens: int, ctx: int) -> float:
    """The least bytes of ``steps`` decode steps that decoded ``tokens``
    tokens over ``ctx`` keys in all: the weights once a step; for each
    decoded token, its live K/V in the attention layers (as
    ``paged_attention``) and its row of state read and written once."""
    d = _dims(arch)
    kv = d["periods"] * (2 * ctx * d["Hkv"] * d["D"] + 2 * tokens * d["H"] * d["D"]) * DTYPE_BYTES
    return steps * weight_bytes(arch) + kv + tokens * 2 * state_row_bytes(arch)
