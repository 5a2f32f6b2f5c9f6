"""Mamba2 (SSD) blocks + the Zamba2 hybrid LM.

Zamba2: a Mamba2 backbone with ONE shared attention+MLP transformer block
whose weights are reused every ``shared_attn_every`` layers (arXiv:2411.15242;
per-invocation LoRA omitted — DESIGN.md §7).  Each shared-block *invocation*
keeps its own KV cache at decode time.

The SSD recurrence  h_t = a_t·h_{t-1} + (Δ_t x_t) ⊗ B_t,  y_t = C_t·h_t + D·x_t
(scalar decay per head) is computed chunkwise-parallel in log space — the
same scheme as the Pallas ``ssd`` kernel (kernels/ssd.py); decode is the O(1)
single-step recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import ParamSpec
from repro.models import layers as L
from repro.models.layers import ModelContext
from repro.models.transformer import _remat, stack_specs


def _dims(cfg: ArchConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    return d_inner, nheads, cfg.ssm_head_dim, cfg.ssm_state


def ssd_chunked(x, dt, a_log, Bm, Cm, D, state=None, chunk: int = 128,
                unroll: bool = False):
    """Chunkwise SSD.  x (B,S,H,P); dt (B,S,H) ≥0; a_log (B,S,H) = log decay
    per step (≤0); Bm/Cm (B,S,N); D (H,).  Returns (y, final state (B,H,P,N)).

    ``unroll=True``: Python chunk loop (same math) for roofline probes."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    from repro.models.rwkv import _chunk_size

    C = _chunk_size(S, chunk)
    Nc = S // C

    xc = x.reshape(Bsz, Nc, C, H, P).astype(f32)
    dtc = dt.reshape(Bsz, Nc, C, H).astype(f32)
    lac = a_log.reshape(Bsz, Nc, C, H).astype(f32)
    Bc = Bm.reshape(Bsz, Nc, C, N).astype(f32)
    Cc = Cm.reshape(Bsz, Nc, C, N).astype(f32)

    s0 = state.astype(f32) if state is not None else jnp.zeros((Bsz, H, P, N), f32)

    def step(s, xs):
        xj, dtj, laj, Bj, Cj = xs  # (B,C,H,P) (B,C,H) (B,C,H) (B,C,N) (B,C,N)
        la = jnp.cumsum(laj, axis=1)  # (B,C,H) inclusive cumulative log decay
        # inter-chunk: y_t += C_t · (s * exp(la_t))
        y_inter = jnp.einsum("bcn,bhpn,bch->bchp", Cj, s, jnp.exp(la))
        # intra-chunk: y_t += Σ_{s≤t} exp(la_t-la_s)(C_t·B_s) Δ_s x_s
        cb = jnp.einsum("bcn,bsn->bcs", Cj, Bj)  # (B,C,C)
        mask = jnp.tril(jnp.ones((C, C), bool))
        # decay diff masked BEFORE exp (≤0 in causal region → overflow-safe)
        diff = la[:, :, None, :] - la[:, None, :, :]  # (B,C,C,H) [t,s]
        dec = jnp.exp(jnp.where(mask[None, :, :, None], diff, -jnp.inf))
        m = dec * cb[..., None]
        y_intra = jnp.einsum("bcsh,bsh,bshp->bchp", m, dtj, xj)
        # state update: s' = s·exp(la_C) + Σ_s exp(la_C-la_s) Δ_s x_s ⊗ B_s
        laC = la[:, -1]  # (B,H)
        w = jnp.exp(laC[:, None] - la) * dtj  # (B,C,H)
        s_new = s * jnp.exp(laC)[:, :, None, None] + jnp.einsum(
            "bch,bchp,bcn->bhpn", w, xj, Bj
        )
        return s_new, y_inter + y_intra

    if unroll:
        s, ys_l = s0, []
        for j in range(Nc):
            s, yj = step(s, (xc[:, j], dtc[:, j], lac[:, j], Bc[:, j], Cc[:, j]))
            ys_l.append(yj)
        sF = s
        y = jnp.concatenate(ys_l, axis=1)
    else:
        sF, ys = jax.lax.scan(step, s0, (
            xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
            lac.transpose(1, 0, 2, 3), Bc.transpose(1, 0, 2, 3),
            Cc.transpose(1, 0, 2, 3),
        ))
        y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)
    y = y + x.astype(f32) * D.astype(f32)[None, None, :, None]
    return y.astype(x.dtype), sF


def ssd_step(x, dt, a_log, Bm, Cm, D, state):
    """Single-token SSD for decode.  x (B,H,P); dt/a_log (B,H); Bm/Cm (B,N);
    state (B,H,P,N)."""
    f32 = jnp.float32
    x32, dt32 = x.astype(f32), dt.astype(f32)
    s_new = state * jnp.exp(a_log.astype(f32))[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt32, x32, Bm.astype(f32)
    )
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(f32), s_new)
    y = y + x32 * D.astype(f32)[None, :, None]
    return y.astype(x.dtype), s_new


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def mamba2_mixer_specs(cfg: ArchConfig) -> dict:
    """The Mamba2 mixer alone: projections, causal conv, SSD, gated norm."""
    E = cfg.d_model
    d_inner, H, P, N = _dims(cfg)
    conv_dim = d_inner + 2 * N
    s = {
        "in_proj_z": ParamSpec((E, d_inner), ("embed", "mlp")),
        "in_proj_x": ParamSpec((E, d_inner), ("embed", "mlp")),
        "in_proj_B": ParamSpec((E, N), ("embed", None)),
        "in_proj_C": ParamSpec((E, N), ("embed", None)),
        "in_proj_dt": ParamSpec((E, H), ("embed", "heads")),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_dim), (None, "mlp"), jnp.float32),
        "dt_bias": ParamSpec((H,), ("heads",), jnp.float32),
        "a_log": ParamSpec((H,), ("heads",), jnp.float32),
        "D": ParamSpec((H,), ("heads",), jnp.float32),
        "norm_gate": ParamSpec((d_inner,), (None,), jnp.float32, 1.0),
        "out_proj": ParamSpec((d_inner, E), ("mlp", "embed")),
    }
    if cfg.ssm_conv_bias:
        s["conv_b"] = ParamSpec((conv_dim,), ("mlp",), jnp.float32, 0.0)
    return s


def mamba2_specs(cfg: ArchConfig) -> dict:
    return {"ln": L.norm_specs(cfg, cfg.d_model), **mamba2_mixer_specs(cfg)}


def mamba2_state_specs(cfg: ArchConfig, batch_size: int, n_layers: int) -> dict:
    """Decode state of ``n_layers`` stacked Mamba2 mixers: the conv's last
    K-1 inputs and the SSD state."""
    d_inner, H, P, N = _dims(cfg)
    conv_dim = d_inner + 2 * N
    per = {
        "conv": ParamSpec(
            (batch_size, cfg.ssm_conv - 1, conv_dim), ("batch", None, "mlp"),
            jnp.dtype(cfg.dtype), 0.0,
        ),
        "ssd": ParamSpec(
            (batch_size, H, P, N), ("batch", "heads", None, None), jnp.float32, 0.0
        ),
    }
    return stack_specs(per, n_layers)


def _causal_conv(u, w, conv_state=None, b=None):
    """Depthwise causal conv along S.  u (B,S,Dc); w (K,Dc); b (Dc,) or None;
    conv_state (B,K-1,Dc) carries the last K-1 inputs for decode/chunking."""
    K = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((u.shape[0], K - 1, u.shape[2]), u.dtype)
    up = jnp.concatenate([conv_state, u], axis=1)
    out = sum(
        up[:, i : i + u.shape[1]] * w[i].astype(u.dtype) for i in range(K)
    )
    if b is not None:
        out = out + b.astype(u.dtype)
    new_state = up[:, -(K - 1) :] if K > 1 else conv_state
    return jax.nn.silu(out), new_state


def _ssd_core(ctx, x, dt, la, Bm, Cm, D, state):
    """Prefill/train SSD dispatch: the Pallas kernel (``ops.ssd``) when
    ``ctx.use_kernels``, else the jnp chunked scan.  A sequence the kernel
    cannot tile raises rather than quietly running jnp."""
    if ctx.use_kernels:
        S = x.shape[1]
        if S > 128 and S % 128:
            raise ValueError(
                f"ssd kernel cannot take a sequence of {S} tokens: it needs at "
                "most 128 or a multiple of 128"
            )
        from repro.kernels.ops import ssd

        return ssd(x, dt, la, Bm, Cm, D, state)
    return ssd_chunked(x, dt, la, Bm, Cm, D, state, unroll=not ctx.cfg.scan_layers)


def mamba2_mixer(ctx, p, h, state, *, decode: bool):
    """The Mamba2 mixer on normed input ``h`` (B,S,E); returns (out, state).
    state: {"conv": (B,K-1,Dc), "ssd": (B,H,P,N)}; without ``decode`` it may
    be None or lack either entry (a zero start)."""
    cfg = ctx.cfg
    d_inner, H, P, N = _dims(cfg)
    B_, S, E = h.shape
    state = state or {}
    z = jnp.einsum("bse,ei->bsi", h, p["in_proj_z"])
    xs = jnp.einsum("bse,ei->bsi", h, p["in_proj_x"])
    Bm = jnp.einsum("bse,en->bsn", h, p["in_proj_B"])
    Cm = jnp.einsum("bse,en->bsn", h, p["in_proj_C"])
    dt = jnp.einsum("bse,eh->bsh", h, p["in_proj_dt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    u = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_in_state = state["conv"] if decode else None
    u, new_conv = _causal_conv(u, p["conv_w"], conv_in_state, p.get("conv_b"))
    xs, Bm, Cm = u[..., :d_inner], u[..., d_inner : d_inner + N], u[..., d_inner + N :]
    xh = xs.reshape(B_, S, H, P)

    a = -jnp.exp(jnp.clip(p["a_log"], -8.0, 4.0))  # A < 0
    la = dt * a  # log decay per step (B,S,H)

    if decode:
        y, new_ssd = ssd_step(
            xh[:, 0], dt[:, 0], la[:, 0], Bm[:, 0], Cm[:, 0], p["D"], state["ssd"]
        )
        y = y[:, None]
    else:
        y, new_ssd = _ssd_core(ctx, xh, dt, la, Bm, Cm, p["D"], state.get("ssd"))

    y = y.reshape(B_, S, d_inner)
    y = L.rmsnorm_nogain(y * jax.nn.silu(z)) * p["norm_gate"].astype(y.dtype)
    out = jnp.einsum("bsi,ie->bse", y, p["out_proj"])
    out = ctx.constrain(out, ("batch", None, None))
    return out, {"conv": new_conv, "ssd": new_ssd}


def apply_mamba2(ctx, p, x, state, *, decode: bool):
    """Pre-norm Mamba2 block with its residual.  state: {"conv": (B,K-1,Dc),
    "ssd": (B,H,P,N)}."""
    h = L.apply_norm(ctx.cfg, p["ln"], x)
    out, new_state = mamba2_mixer(ctx, p, h, state, decode=decode)
    return x + out, new_state


# ---------------------------------------------------------------------------
# Zamba2 hybrid LM
# ---------------------------------------------------------------------------


class Zamba2LM:
    def __init__(self, ctx: ModelContext):
        self.ctx = ctx
        self.cfg = ctx.cfg
        n, e = ctx.cfg.n_layers, ctx.cfg.shared_attn_every
        # shared block invoked after layers e-1, 2e-1, … (Python-static plan)
        self.shared_points = [i for i in range(n) if i % e == e - 1] if e else []

    def param_specs(self) -> dict:
        cfg = self.cfg
        s = {
            "embed": L.embed_specs(cfg),
            "layers": stack_specs(mamba2_specs(cfg), cfg.n_layers),
            "final_norm": L.norm_specs(cfg, cfg.d_model),
        }
        if self.shared_points:
            s["shared"] = {
                "ln1": L.norm_specs(cfg, cfg.d_model),
                "attn": L.attention_specs(cfg),
                "ln2": L.norm_specs(cfg, cfg.d_model),
                "ffn": L.mlp_specs(cfg),
            }
        return s

    # -- states/caches -----------------------------------------------------
    def mamba_state_specs(self, batch_size: int) -> dict:
        return mamba2_state_specs(self.cfg, batch_size, self.cfg.n_layers)

    def attn_cache_specs(self, batch_size: int, max_len: int) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        per = {
            "k": ParamSpec(
                (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim_),
                ("batch", "kv_seq", "kv_heads", None), dt, 0.0,
            ),
            "v": ParamSpec(
                (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim_),
                ("batch", "kv_seq", "kv_heads", None), dt, 0.0,
            ),
        }
        return stack_specs(per, len(self.shared_points))

    def state_specs(self, batch_size: int, max_len: int) -> dict:
        return {
            "mamba": self.mamba_state_specs(batch_size),
            "attn": self.attn_cache_specs(batch_size, max_len),
        }

    def _zero_mamba_state(self, B):
        from repro.dist.sharding import materialize_params

        return materialize_params(self.mamba_state_specs(B), jax.random.PRNGKey(0))

    # -- forward -------------------------------------------------------------
    def _run(self, params, x, mamba_state, attn_cache, rope, *, decode: bool,
             cache_index=None, collect_cache: bool = False):
        """Groups of mamba layers with shared-attn invocations between."""
        ctx, cfg = self.ctx, self.cfg
        e = cfg.shared_attn_every or cfg.n_layers
        n = cfg.n_layers
        new_mamba_chunks, new_attn = [], []
        inv = 0
        for g0 in range(0, n, e):
            g1 = min(g0 + e, n)
            lp = jax.tree.map(lambda a: a[g0:g1], params["layers"])
            st = jax.tree.map(lambda a: a[g0:g1], mamba_state)

            def body(x, xs):
                p, s = xs
                return apply_mamba2(ctx, p, x, s, decode=decode)

            x, new_st = L.scan_stack(cfg, _remat(cfg, body), x, (lp, st))
            new_mamba_chunks.append(new_st)
            if g1 - 1 in self.shared_points and "shared" in params:
                sp = params["shared"]
                h = L.apply_norm(cfg, sp["ln1"], x)
                cache_i = (
                    jax.tree.map(lambda a: a[inv], attn_cache)
                    if attn_cache is not None else None
                )
                if decode:
                    att, nc = L.apply_attention(
                        ctx, sp["attn"], h, rope=rope,
                        cache=cache_i, cache_index=cache_index,
                    )
                else:
                    att, nc = L.apply_attention(
                        ctx, sp["attn"], h, rope=rope,
                        cache={} if collect_cache else None, cache_index=None,
                    )
                x = x + att
                h2 = L.apply_norm(cfg, sp["ln2"], x)
                x = x + L.apply_mlp(ctx, sp["ffn"], h2)
                if nc is not None:
                    new_attn.append(nc)
                inv += 1
        new_mamba = jax.tree.map(
            lambda *cs: jnp.concatenate(cs, 0), *new_mamba_chunks
        )
        new_attn_stacked = (
            jax.tree.map(lambda *cs: jnp.stack(cs, 0), *new_attn) if new_attn else None
        )
        return x, new_mamba, new_attn_stacked

    def _rope(self, B, S, positions=None):
        cfg = self.cfg
        pos = positions if positions is not None else jnp.arange(S)[None]
        pos = jnp.broadcast_to(pos, (B, S))
        return L.rope_cos_sin(pos, cfg.head_dim_, cfg.rope_theta)

    def loss(self, params, batch):
        cfg, ctx = self.cfg, self.ctx
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        x = L.apply_embed(ctx, params["embed"], tokens)
        st = self._zero_mamba_state(B)
        h, _, _ = self._run(
            params, x, st, None, self._rope(B, S), decode=False
        )
        hn = L.apply_norm(cfg, params["final_norm"], h)
        logits = L.apply_unembed(ctx, params["embed"], hn)
        loss = L.cross_entropy(ctx, logits, labels)
        return loss, {"ce": loss}

    def prefill(self, params, tokens, max_len: int):
        cfg, ctx = self.cfg, self.ctx
        B, S = tokens.shape
        x = L.apply_embed(ctx, params["embed"], tokens)
        st = self._zero_mamba_state(B)
        h, new_mamba, new_attn = self._run(
            params, x, st, None, self._rope(B, S), decode=False, collect_cache=True
        )

        def pad(c):
            pad_len = max_len - c.shape[2]
            if pad_len <= 0:
                return c
            w = [(0, 0)] * c.ndim
            w[2] = (0, pad_len)
            return jnp.pad(c, w)

        new_attn = jax.tree.map(pad, new_attn) if new_attn is not None else None
        hn = L.apply_norm(cfg, params["final_norm"], h[:, -1:])
        logits = L.apply_unembed(ctx, params["embed"], hn)
        return logits[:, 0], {"mamba": new_mamba, "attn": new_attn}

    def decode_step(self, params, state, tokens, index):
        cfg, ctx = self.cfg, self.ctx
        B = tokens.shape[0]
        x = L.apply_embed(ctx, params["embed"], tokens)
        rope = self._rope(B, 1, positions=jnp.full((1, 1), index))
        h, new_mamba, new_attn = self._run(
            params, x, state["mamba"], state["attn"], rope,
            decode=True, cache_index=index,
        )
        hn = L.apply_norm(cfg, params["final_norm"], h)
        logits = L.apply_unembed(ctx, params["embed"], hn)
        return logits[:, 0], {"mamba": new_mamba, "attn": new_attn}


class Mamba2LM(Zamba2LM):
    """Pure-Mamba2 LM (shared_attn_every=0 config)."""
