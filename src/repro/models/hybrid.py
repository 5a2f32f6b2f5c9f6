"""Mamba2 layers with attention at a fixed period: granite-4.0-h.

Layer ``i`` is grouped-query attention when ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba2 mixer (``ssm.mamba2_mixer``) otherwise;
every layer then runs a SiLU-gated MLP.  Each layer is
``x + r·mixer(norm(x))`` and then ``x + r·mlp(norm(x))`` with ``r`` the
residual multiplier; the embedding is scaled by ``embedding_multiplier`` and
the logits divided by ``logits_scaling``.  Attention has no positional
encoding where ``rotary_pct`` is 0 and takes ``attention_multiplier`` as its
softmax scale.

Parameters are stacked by place in the period, so one scan over the periods
runs the whole depth and compile time does not grow with it:
``periods.pre`` (n_periods, offset, ...) holds the Mamba2 layers before the
attention layer, ``periods.attn`` (n_periods, ...) the attention layers and
``periods.post`` (n_periods, period - offset - 1, ...) the Mamba2 layers
after it.  Mamba2 layer ``m`` (in depth order, attention layers not counted)
is ``pre`` or ``post`` of period ``m // (period - 1)``.

Serving contract (serve/engine.py):

- ``cache_specs(B, S)``: the attention layers' K/V only, what the engine
  pools in pages and prices per token;
- ``slot_state_specs(B)``: conv and SSD state of every Mamba2 layer,
  ``(n_mamba, B, ...)``, which the engine keeps one row per slot;
- ``prefill(params, tokens, max_len)`` → (last-token logits, ``{"kv",
  "state"}``): the K/V padded to ``max_len`` and the final state;
- ``decode_step(params, {"kv", "state"}, tokens, index)`` → (logits, the
  same), each row at its own position ``index`` (a scalar or ``(B,)``); the
  state is read and written layer by layer in the carried buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import ParamSpec
from repro.models import layers as L
from repro.models.layers import ModelContext
from repro.models.ssm import mamba2_mixer, mamba2_mixer_specs, mamba2_state_specs
from repro.models.transformer import _remat, stack_specs


class HybridLM:
    def __init__(self, ctx: ModelContext):
        self.ctx = ctx
        self.cfg = cfg = ctx.cfg
        per, off = cfg.attn_layer_period, cfg.attn_layer_offset
        if per < 1 or not 0 <= off < per or cfg.n_layers % per:
            raise ValueError(
                f"{cfg.name}: attention every {per} layers at offset {off} "
                f"does not tile {cfg.n_layers} layers"
            )
        self.n_periods = cfg.n_layers // per
        self.n_pre, self.n_post = off, per - off - 1
        self.n_mamba = self.n_periods * (per - 1)

    # -- params -------------------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg

        def layer(**mixer):
            return {"ln1": L.norm_specs(cfg, cfg.d_model), **mixer,
                    "ln2": L.norm_specs(cfg, cfg.d_model), "ffn": L.mlp_specs(cfg)}

        mamba = layer(mixer=mamba2_mixer_specs(cfg))
        periods = {"attn": stack_specs(layer(attn=L.attention_specs(cfg)), self.n_periods)}
        for name, n in (("pre", self.n_pre), ("post", self.n_post)):
            if n:
                periods[name] = stack_specs(stack_specs(mamba, n), self.n_periods)
        return {
            "embed": L.embed_specs(cfg),
            "periods": periods,
            "final_norm": L.norm_specs(cfg, cfg.d_model),
        }

    # -- states/caches -------------------------------------------------------
    def cache_specs(self, batch_size: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = ParamSpec(
            (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim_),
            ("batch", "kv_seq", "kv_heads", None), jnp.dtype(cfg.dtype), 0.0,
        )
        return stack_specs({"k": kv, "v": kv}, self.n_periods)

    def slot_state_specs(self, batch_size: int) -> dict:
        return mamba2_state_specs(self.cfg, batch_size, self.n_mamba)

    # -- layers --------------------------------------------------------------
    def _mlp(self, p, x):
        cfg = self.cfg
        h = L.apply_norm(cfg, p["ln2"], x)
        return x + cfg.residual_multiplier * L.apply_mlp(self.ctx, p["ffn"], h)

    def _mamba(self, p, x, state, *, decode: bool):
        cfg = self.cfg
        h = L.apply_norm(cfg, p["ln1"], x)
        out, state = mamba2_mixer(self.ctx, p["mixer"], h, state, decode=decode)
        return self._mlp(p, x + cfg.residual_multiplier * out), state

    def _attn(self, p, x, *, collect: bool):
        """Causal attention over the whole sequence; its K/V if ``collect``."""
        cfg = self.cfg
        h = L.apply_norm(cfg, p["ln1"], x)
        out, kv = L.apply_attention(self.ctx, p["attn"], h, cache={} if collect else None)
        return self._mlp(p, x + cfg.residual_multiplier * out), kv

    def _attn_rows(self, p, x, kv, lens):
        """One decode token per row, each at its own position ``lens[b]``,
        against its own K/V row."""
        cfg = self.cfg
        h = L.apply_norm(cfg, p["ln1"], x)

        def one(h_b, k_b, v_b, i_b):
            out, nc = L.apply_attention(
                self.ctx, p["attn"], h_b[None], cache={"k": k_b[None], "v": v_b[None]},
                cache_index=i_b,
            )
            return out[0], nc["k"][0], nc["v"][0]

        out, k, v = jax.vmap(one)(h, kv["k"], kv["v"], lens)
        return self._mlp(p, x + cfg.residual_multiplier * out), {"k": k, "v": v}

    def _embed(self, params, tokens):
        x = L.apply_embed(self.ctx, params["embed"], tokens)
        return x * self.cfg.embedding_multiplier

    def _logits(self, params, h):
        hn = L.apply_norm(self.cfg, params["final_norm"], h)
        return L.apply_unembed(self.ctx, params["embed"], hn) / self.cfg.logits_scaling

    # -- whole sequences -------------------------------------------------------
    def _forward(self, params, tokens, *, collect: bool):
        """Every layer over (B, S) tokens from a zero state: the last hidden
        states and, if ``collect``, the attention K/V stacked
        (n_periods, B, S, ...) and each Mamba2 layer's final state stacked
        (n_mamba, B, ...)."""
        cfg = self.cfg

        def mamba_body(x, p):
            x, state = self._mamba(p, x, None, decode=False)
            return x, state if collect else None

        def period(x, pp):
            states = []
            if "pre" in pp:
                x, st = L.scan_stack(cfg, mamba_body, x, pp["pre"])
                states.append(st)
            x, kv = self._attn(pp["attn"], x, collect=collect)
            if "post" in pp:
                x, st = L.scan_stack(cfg, mamba_body, x, pp["post"])
                states.append(st)
            if not collect:
                return x, None
            # (pre, post) of one period, in depth order
            return x, (kv, jax.tree.map(lambda *s: jnp.concatenate(s, 0), *states))

        x, ys = L.scan_stack(cfg, _remat(cfg, period), self._embed(params, tokens),
                             params["periods"])
        if not collect:
            return x, None, None
        kv, state = ys
        state = jax.tree.map(lambda a: a.reshape((self.n_mamba,) + a.shape[2:]), state)
        return x, kv, state

    def loss(self, params, batch):
        h, _, _ = self._forward(params, batch["tokens"], collect=False)
        loss = L.cross_entropy(self.ctx, self._logits(params, h), batch["labels"])
        return loss, {"ce": loss}

    def prefill(self, params, tokens, max_len: int):
        """Run the prompt batch (B, S) from a zero state: (last-token logits,
        {"kv": K/V padded to ``max_len``, "state": the final state})."""
        h, kv, state = self._forward(params, tokens, collect=True)

        def pad(c):
            w = [(0, 0)] * c.ndim
            w[2] = (0, max(0, max_len - c.shape[2]))
            return jnp.pad(c, w)

        logits = self._logits(params, h[:, -1:])
        return logits[:, 0], {"kv": jax.tree.map(pad, kv), "state": state}

    def decode_step(self, params, cache, tokens, index):
        """One token per row: ``tokens`` (B, 1) at positions ``index``
        (scalar or (B,)); ``cache`` {"kv": (n_periods, B, S, ...) K/V,
        "state": (n_mamba, B, ...)}.  Returns (logits (B, V), new cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        lens = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
        per_period = cfg.attn_layer_period - 1

        def mamba_from(first, period_idx):
            def body(carry, xs):
                x, state = carry
                p, j = xs
                m = period_idx * per_period + first + j
                row = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False), state
                )
                x, new = self._mamba(p, x, row, decode=True)
                state = jax.tree.map(
                    lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype), m, 0),
                    state, new,
                )
                return (x, state), None
            return body

        def period(carry, xs):
            pp, kv, i = xs
            if "pre" in pp:
                carry, _ = L.scan_stack(cfg, mamba_from(0, i), carry,
                                        (pp["pre"], jnp.arange(self.n_pre)))
            x, state = carry
            x, kv = self._attn_rows(pp["attn"], x, kv, lens)
            carry = (x, state)
            if "post" in pp:
                carry, _ = L.scan_stack(cfg, mamba_from(self.n_pre, i), carry,
                                        (pp["post"], jnp.arange(self.n_post)))
            return carry, kv

        (x, state), kv = L.scan_stack(
            cfg, period, (self._embed(params, tokens), cache["state"]),
            (params["periods"], cache["kv"], jnp.arange(self.n_periods)),
        )
        return self._logits(params, x)[:, 0], {"kv": kv, "state": state}
