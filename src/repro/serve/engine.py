"""Continuous-batching serving engine on the proxy patterns.

Architecture = the paper's Fig 4 applied to inference:

- requests arrive on a **ProxyStream**: the admission thread consumes
  *metadata only* (request id, prompt length, max tokens) and resolves the
  bulk prompt just-in-time, overlapped with the decode loop;
- each admitted sequence's control-plane state (page list, per-page KV
  cells) is **ownership**-managed (kvcache.PageTable) — completion
  deterministically frees everything, including the store memory;
- results stream back on a response topic as **incremental token deltas**
  (metadata-only events, one per token) plus a final bulk completion
  proxy — a client sees its first token the moment the prefill admits the
  request, not a whole generation later (serve/client.ServeClient
  assembles them).

The engine loop is *notification-driven*: no sleep-poll anywhere.  A puller
thread blocks in the request consumer (broker condition wait / connector
``wait_for`` under PR 3's protocol) and hands requests over a condition
variable; the decode loop blocks on that condition only when every slot is
idle, and otherwise drains admissions between jit'd decode steps (the
decode deadline: an active batch never waits on the request stream).

Decode (``paged=True``, the default) runs over a **page pool**: each
stacked cache leaf is ``(L, P+1, page_size, ...)`` (P = the PageTable's
pages, plus one null scratch page — see kvcache's module docstring for the
layout).  The jit'd step gathers each slot's live pages through its block
table (``pages_of``, null-padded to a power-of-two width so recompiles
stay bounded), decodes every slot at its own position, and scatters back
*only the one page each slot wrote* — donated, so the pool updates in
place and a short sequence touches its own pages, never ``max_len``.
Admission inserts prefilled KV page-by-page: each admitted request runs
its own one-row prefill at its own length, then one donated insert of its
pages (``batch_prefill=True`` drains up to ``slots`` queued requests into
one admission batch: one dispatch, one pull of the first tokens, one
emit), and ``share_prefixes=True`` aliases common prompt
prefixes through the PageTable's refcounted cells — copy-on-write events
are mirrored onto the pool as device page copies before the step that
would diverge.  ``paged=False`` keeps the dense ``(L, B, S, ...)`` layout
(the benchmark baseline, and the fallback for indivisible page sizes).

Admission is backpressured through PageTable reservations: a request is
admitted only when the pool can cover its *whole* generation, so decode
never OOMs mid-sequence; requests the pool can never fit are rejected onto
the response stream as errors.

The engine thread marks each phase of ``run`` with a leaf
``jax.profiler.TraceAnnotation`` (``serve.admit.*``, ``serve.decode.*``,
``serve.idle``): no span encloses another, so a profiler trace names every
idle gap of the device by the phase the host was in.  A span carries its
parent as a stat instead: ``step``, the ``decode_steps`` counter when it
opens, and for admission ``batch``, the ``admissions`` counter.  With no
profiler running a span costs about a microsecond.  Each token delta
carries ``sent_at``, the engine's ``time.perf_counter()`` at the send: a
host-monotonic clock, comparable across processes on one host only.

Speculative decode (``spec_k > 0`` + a ``draft_model``): each step, the
draft proposes up to k tokens per active slot (k+1 chained single-token
steps over its own page pool, re-feeding the previous token so the draft
cache self-heals after full acceptance), then the target verifies all k+1
positions in ONE jit'd paged forward (``verify_batch`` → multi-query paged
attention: query t attends keys < len+t).  Greedy rejection accepts the
longest draft prefix matching the target's own argmaxes plus one corrected
token — emitted tokens are ALWAYS target argmaxes, so the output is
bit-identical to plain greedy decode for any draft; draft quality only
moves the accepted-tokens/step rate.  Rejected draft KV "rolls back" by
never scattering positions past the accepted length into the pool (a
PageTable only grows), and ``k_eff = min(k, remaining-1, horizon)`` clamps
keep every extend inside the admission reservation, so speculation can
never OOM and pricing is unchanged.  The draft runs a second PageTable (its
own Store, no prefix sharing) in lockstep: ``can_admit`` checks both pools
and ``_finish`` frees both.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sanitize as _sanitize
from repro.core.lifetimes import ContextLifetime
from repro.core.proxy import extract
from repro.core.store import Store
from repro.core.streaming import StreamConsumer, StreamProducer
from repro.dist.sharding import ParamSpec, materialize_params, sharding_tree
from repro.models.api import build_model
from repro.models.layers import ModelContext

# How often the puller/idle waits re-check stop/exit flags.  This is NOT a
# poll interval for events — both waits are notification-driven (broker
# condition / connector wait_for) and wake immediately on traffic; the tick
# only bounds how long shutdown can lag.
_WAIT_TICK = 0.25

_span = jax.profiler.TraceAnnotation


def serve_context(
    cfg, mesh=None, *, use_kernels: bool = False, page_size: int = 16
) -> ModelContext:
    """ModelContext with the ``serve`` rules profile applied.

    The serve profile shards the KV cache's sequence axis over the model
    axis (``kv_seq`` wins the model axis; decode is KV-bound) — the rules
    flow into both param placement and the cache shardings the engine
    applies in :meth:`ServeEngine._ensure_cache`.  ``page_size`` is the
    KV page the decode kernel reads (``ServeEngine`` sets it to its own).
    """
    from repro.launch.mesh import make_host_mesh, rules_for

    mesh = mesh if mesh is not None else make_host_mesh()
    return ModelContext(
        cfg, mesh, rules_for(mesh, "serve"), use_kernels, page_size
    )


@dataclass
class Request:
    req_id: str
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    arrived: float = field(default_factory=time.perf_counter)


@dataclass
class SlotState:
    req: Request | None = None
    pos: int = 0  # current length (prompt + generated)
    generated: list[int] = field(default_factory=list)
    first_token_at: float | None = None
    pages: list[int] = field(default_factory=list)  # cached block table


class ServeEngine:
    def __init__(
        self,
        ctx: ModelContext,
        params,
        *,
        slots: int = 4,
        max_len: int = 128,
        page_size: int = 16,
        eos_id: int = 0,
        model=None,
        kv_store: Store | None = None,
        paged: bool = True,
        batch_prefill: bool = True,
        share_prefixes: bool = True,
        spec_k: int = 0,
        draft_model=None,
        draft_params=None,
        on_load_change=None,
        done_commit_prefix: str | None = None,
    ):
        from repro.core.connectors import new_key
        from repro.serve.kvcache import PageTable

        # the decode kernel reads the KV cache in the pool's own pages
        ctx = replace(ctx, page_size=page_size)
        self.ctx = ctx
        self.cfg = ctx.cfg
        self.model = model if model is not None else build_model(ctx)
        self.params = params
        self.slots = [SlotState() for _ in range(slots)]
        self.max_len = max_len
        self.eos_id = eos_id
        self._owns_store = kv_store is None
        self.kv_store = kv_store if kv_store is not None else Store(f"kv-{new_key()}")
        self.pages = PageTable(
            num_pages=slots * (max_len // page_size),
            page_size=page_size,
            store=self.kv_store,
            page_bytes=self._page_bytes(page_size),
        )
        # paged decode needs pages to tile max_len exactly; else dense
        self.paged = paged and max_len % page_size == 0
        self.batch_prefill = batch_prefill
        self.share_prefixes = share_prefixes
        # Fleet hooks (serve/router.py).  ``on_load_change(pages_available)``
        # fires after every admission batch and every completion so an
        # engine can publish its capacity as store metadata (the router's
        # least-loaded signal); a failing hook is counted, never fatal.
        # ``done_commit_prefix`` switches completions to the exactly-once
        # ``send_committed`` path: the record lands at the deterministic
        # key ``{prefix}{req_id}`` via put_if_absent, so a redispatched
        # request re-completed by a survivor engine commits ONE payload
        # however many engines finish it.
        self.on_load_change = on_load_change
        self.done_commit_prefix = done_commit_prefix
        # speculative decode: a draft model proposes spec_k tokens per slot
        # per step; the target verifies all of them in one paged forward.
        # Greedy rejection keeps the longest matching prefix plus the
        # target's corrected token, so the emitted stream is bit-identical
        # to target-only greedy decode by construction.
        if spec_k > 0 and draft_model is None:
            raise ValueError("spec_k > 0 requires a draft_model")
        if spec_k > 0 and not self.paged:
            raise ValueError(
                "speculative decode requires the paged cache layout "
                "(max_len must be a multiple of page_size, paged=True)"
            )
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self.draft_params = draft_params if draft_params is not None else {}
        # pool geometry, pinned at construction (tests may shrink the
        # allocator's num_pages afterwards to force backpressure — the
        # device pool keeps its build-time size, so every id stays valid)
        self._null_page = self.pages.num_pages
        self._pages_per_slot = max(1, max_len // page_size)
        if self.paged:
            self._cache_specs = self._pool_specs()
        else:
            self._cache_specs = self.model.cache_specs(len(self.slots), self.max_len)
        # serve-profile shardings for the cache (kv_seq over the model
        # axis); a no-op placement on the 1-device smoke mesh
        self._cache_shardings = sharding_tree(self._cache_specs, ctx.rules, ctx.mesh)
        if self.spec_k:
            from repro.serve.kvcache import page_bytes_for

            # The draft pool mirrors the target pool's geometry (same page
            # ids, same null page) but is priced off the DRAFT model's
            # per-token cache, and lives in its own store so its page
            # cells never collide with the target's keys.  No prefix
            # sharing on the draft side: its KV is advisory (drafts only
            # steer acceptance, never the emitted tokens).
            self._draft_store = Store(f"kvdraft-{new_key()}")
            self.draft_pages = PageTable(
                num_pages=self.pages.num_pages,
                page_size=page_size,
                store=self._draft_store,
                page_bytes=page_bytes_for(draft_model, self.cfg.dtype, page_size),
            )
            self._draft_cache_specs = self._pool_specs(draft_model)
            self._draft_shardings = sharding_tree(
                self._draft_cache_specs, ctx.rules, ctx.mesh
            )
        else:
            self._draft_store = None
            self.draft_pages = None
        # cache donated on the per-token hot path too: the step rewrites
        # the KV buffers in place instead of allocating a full copy per
        # token (self._cache is reassigned from the result, so the donated
        # input is never reused)
        if self.paged:
            self._decode = jax.jit(self._decode_paged_body, donate_argnums=(1,))
        else:
            self._decode = jax.jit(self._decode_body, donate_argnums=(1,))
        # per-slot cache insert: donated so XLA updates the batch buffers in
        # place; the slot index / page ids are traced, so one compilation
        # covers every admission target instead of re-lowering per slot
        self._admit_cache = jax.jit(self._admit_body, donate_argnums=(0,))
        self._insert_pages = jax.jit(self._insert_body, donate_argnums=(0,))
        self._copy_page = jax.jit(self._copy_body, donate_argnums=(0,))
        self._prefill = jax.jit(
            lambda p, tokens: self.model.prefill(p, tokens, self.max_len)
        )
        if self.spec_k:
            self._spec_draft = jax.jit(self._spec_draft_body, donate_argnums=(1,))
            self._spec_verify = jax.jit(self._spec_verify_body, donate_argnums=(1,))
            self._draft_prefill = jax.jit(
                lambda p, tokens: self.draft_model.prefill(p, tokens, self.max_len)
            )
        self._cache = None  # paged: (L, P+1, ps, ...); dense: (L, B, S, ...)
        self._draft_cache = None  # spec_k only: draft model's page pool
        self._live_prompts: dict[str, np.ndarray] = {}  # for prefix sharing
        # Per-request lifetimes, split by custodian.  Request-side payloads
        # (persistent prompt bulks) are consumed by THIS engine, so close()
        # always reclaims them.  Response-side payloads (completion bulks)
        # are custody shared with the client: a resolving client reclaims
        # them itself (one-shot stream contract), and close() sweeps only
        # what no client claimed — unless the response stream outlives the
        # engine (restart handoff), see close(reclaim_responses=False).
        self._req_lifetimes: dict[str, ContextLifetime] = {}
        self._resp_lifetimes: dict[str, ContextLifetime] = {}
        self.completed: dict[str, dict] = {}
        self.rejected: dict[str, str] = {}
        self.metrics = {
            "prefills": 0,
            "decode_steps": 0,
            "tokens": 0,
            "loop_iters": 0,
            "idle_waits": 0,
            "queued_admissions": 0,
            "max_pending": 0,
            "malformed_events": 0,
            "batched_prefills": 0,  # admission batches of more than one request
            # tokens run through the target's prefill programs, padding
            # rows and positions included
            "prefill_tokens": 0,
            "prefix_shared_pages": 0,
            "cow_page_copies": 0,
            "spec_steps": 0,
            "spec_slot_steps": 0,
            "spec_accepted_tokens": 0,
            "reclaim_failures": 0,
            "load_publish_failures": 0,
            "admissions": 0,  # admission batches run
            # sum over admitted requests of their batch's start minus
            # Request.arrived (the puller's receipt)
            "queue_wait_s": 0.0,
        }

    def _page_bytes(self, page_size: int) -> int:
        """Host-side KV bytes one page represents (the PageTable cell size)."""
        from repro.serve.kvcache import page_bytes_for

        return page_bytes_for(self.model, self.cfg.dtype, page_size)

    def _pool_specs(self, model=None):
        """Page-pool cache specs: each dense (L, B, S, ...) leaf becomes
        (L, P+1, page_size, ...) — axis 1 is the physical page id (the
        last index is the null scratch page), axis 2 the in-page offset."""
        per_page = (model or self.model).cache_specs(1, self.pages.page_size)
        P = self._null_page + 1

        def to_pool(s):
            return ParamSpec(
                (s.shape[0], P) + s.shape[2:],
                (s.axes[0], "kv_seq", None) + s.axes[3:],
                s.dtype,
                s.init_scale,
            )

        return jax.tree.map(
            to_pool, per_page, is_leaf=lambda x: isinstance(x, ParamSpec)
        )

    # -- model glue ---------------------------------------------------------
    def _decode_body(self, params, cache, tokens, lens):
        """Dense-layout decode: each slot at its own index via vmap over
        the batch axis (the ``paged=False`` baseline path)."""

        def one(cache_b, tok_b, len_b):
            c = jax.tree.map(lambda x: x[:, None], cache_b)  # re-add batch dim
            logits, nc = self.model.decode_step(params, c, tok_b[None], len_b)
            return jax.tree.map(lambda x: x[:, 0], nc), logits[0]

        new_cache, logits = jax.vmap(
            one, in_axes=(1, 0, 0), out_axes=(1, 0)
        )(cache, tokens, lens)
        return new_cache, logits

    def _decode_paged_body(self, params, pool, bt, tokens, lens):
        """Paged decode: gather each slot's pages into a contiguous view,
        decode every slot at its own position, scatter back **only the one
        page each slot wrote** (the model's decode contract: the step
        writes position ``lens[b]`` and nothing else).

        ``bt`` (B, n) is the null-padded block table; n is the power-of-two
        page coverage of the longest active slot, so the gathered view —
        and the attention the model runs inside it — scales with what the
        batch actually occupies, not with max_len."""
        ps = self.pages.page_size
        B, n = bt.shape

        def gather(leaf):
            g = leaf[:, bt]  # (L, B, n, ps, ...)
            return g.reshape(g.shape[:2] + (n * ps,) + g.shape[4:])

        dense = jax.tree.map(gather, pool)

        def one(cache_b, tok_b, len_b):
            c = jax.tree.map(lambda x: x[:, None], cache_b)
            logits, nc = self.model.decode_step(params, c, tok_b[None], len_b)
            return jax.tree.map(lambda x: x[:, 0], nc), logits[0]

        new_dense, logits = jax.vmap(
            one, in_axes=(1, 0, 0), out_axes=(1, 0)
        )(dense, tokens, lens)

        page_slot = lens // ps  # (B,) block-table index of the written page
        dst = jnp.take_along_axis(bt, page_slot[:, None], axis=1)[:, 0]  # (B,)

        def pick(nd_b, p_idx):  # (L, n*ps, ...) → the written (L, ps, ...)
            return jax.lax.dynamic_slice_in_dim(nd_b, p_idx * ps, ps, axis=1)

        def scatter(leaf, nd):
            written = jax.vmap(pick, in_axes=(1, 0), out_axes=1)(nd, page_slot)
            return leaf.at[:, dst].set(written.astype(leaf.dtype))

        return jax.tree.map(scatter, pool, new_dense), logits

    def _admit_body(self, cache, one, slot_idx):
        """Dense path: insert a (batch=1) prefill cache at slot
        ``slot_idx`` — a dynamic per-slot update on donated buffers."""
        return jax.tree.map(
            lambda full, o: jax.lax.dynamic_update_index_in_dim(
                full, o[:, 0].astype(full.dtype), slot_idx, 1
            ),
            cache,
            one,
        )

    def _insert_body(self, pool, caches, page_ids):
        """Paged admission insert: ``caches`` (L, 1, max_len, ...) from one
        request's prefill, viewed as (L, pages_per_slot, page_size, ...)
        pages; ``page_ids`` (pages_per_slot,) their physical destinations.
        The unowned tail and *shared (borrowed) prefix pages* point at the
        null page — the insert never writes a page another sequence
        owns."""
        ps = self.pages.page_size

        def one(pool_leaf, c):
            mp = c.shape[2] // ps
            cp = c.reshape((c.shape[0], c.shape[1] * mp, ps) + c.shape[3:])
            return pool_leaf.at[:, page_ids].set(cp.astype(pool_leaf.dtype))

        return jax.tree.map(one, pool, caches)

    def _copy_body(self, pool, src, dst):
        """Copy-on-write mirror: duplicate physical page src → dst."""
        return jax.tree.map(lambda leaf: leaf.at[:, dst].set(leaf[:, src]), pool)

    # -- speculative decode (spec_k > 0) -------------------------------------
    #
    # Per step, three phases over the same block-table machinery as
    # _decode_paged_body:
    #   draft:  k+1 chained single-token steps on the DRAFT pool propose
    #           d_1..d_k per slot (the first step re-feeds the previous
    #           token so a fully-accepted run's bonus token is caught up —
    #           rewriting position pos-1 with the same token is a no-op);
    #   verify: ONE multi-position target forward feeds [last, d_1..d_k]
    #           at positions pos..pos+k and computes the acceptance length
    #           in-graph: a = LCP(draft, target argmax) + 1 — the emitted
    #           tokens are ALWAYS the target's argmaxes, so the stream is
    #           bit-identical to target-only greedy decode;
    #   rollback: pages past the accepted length are simply not scattered
    #           back (dst redirected to the null page) — the PageTable
    #           never rolls back, and stale draft-side bytes are rewritten
    #           by the next step before anything attends them.
    #
    # Per-slot speculation depth k_eff clamps to (remaining-1, max_len-2-pos)
    # so every extend stays inside the admission-time reservation; rows pad
    # their draft tokens with -1 beyond k_eff, which can never match an
    # argmax, capping acceptance exactly at k_eff+1.

    def _gather_dense(self, pool, bt):
        """(L, P+1, ps, ...) pool → contiguous (L, B, n*ps, ...) view."""
        ps = self.pages.page_size
        n = bt.shape[1]

        def gather(leaf):
            g = leaf[:, bt]
            return g.reshape(g.shape[:2] + (n * ps,) + g.shape[4:])

        return jax.tree.map(gather, pool)

    def _scatter_span(self, pool, dense, bt, first, last):
        """Scatter pages ``first[b]..last[b]`` of each row's dense view back
        to their physical pages; rows/pages outside the span write the null
        scratch page.  The static write bound covers the k+1 positions one
        speculative step can touch."""
        ps = self.pages.page_size
        n = bt.shape[1]
        n_wr = min(n, (self.spec_k + ps - 1) // ps + 1)

        def pick(nd_b, p_idx):  # (L, n*ps, ...) → page p_idx's (L, ps, ...)
            return jax.lax.dynamic_slice_in_dim(nd_b, p_idx * ps, ps, axis=1)

        for j in range(n_wr):
            slot_j = jnp.clip(first + j, 0, n - 1)  # (B,)
            keep = (first + j >= 0) & (first + j <= last)
            dstp = jnp.take_along_axis(bt, slot_j[:, None], axis=1)[:, 0]
            dst = jnp.where(keep, dstp, self._null_page)

            def scatter(leaf, nd):
                written = jax.vmap(pick, in_axes=(1, 0), out_axes=1)(nd, slot_j)
                return leaf.at[:, dst].set(written.astype(leaf.dtype))

            pool = jax.tree.map(scatter, pool, dense)
        return pool

    def _spec_draft_body(self, draft_params, pool, bt, prev, last, lens, k_eff):
        """Draft proposal: k+1 chained decode steps on the draft pool.

        Step 0 re-feeds ``prev`` at position lens-1 (catch-up: after a
        fully-accepted run the draft cache is one token behind the
        target's; otherwise the rewrite is byte-identical).  Step 1 feeds
        ``last`` at lens and yields d_1; step j>=2 chains the argmax.
        Returns (new_pool, drafts (B, k)) — drafts are advisory only."""
        dense = self._gather_dense(pool, bt)

        def one(cache_b, tok_b, idx_b):
            c = jax.tree.map(lambda x: x[:, None], cache_b)
            logits, nc = self.draft_model.decode_step(
                draft_params, c, tok_b[None, None], idx_b
            )
            return jax.tree.map(lambda x: x[:, 0], nc), logits[0]

        step = jax.vmap(one, in_axes=(1, 0, 0), out_axes=(1, 0))
        cur = prev
        drafts = []
        for j in range(self.spec_k + 1):
            dense, logits = step(dense, cur, lens - 1 + j)
            nxt = jnp.argmax(
                logits[:, : self.cfg.vocab], axis=-1
            ).astype(jnp.int32)
            if j == 0:
                cur = last  # step 0's output is `last` itself: known
            else:
                drafts.append(nxt)
                cur = nxt
        # persist positions lens-1 .. lens-1+k_eff (catch-up + the drafts
        # the verify pass may accept); later writes are scratch
        first = (lens - 1) // self.pages.page_size
        lastp = (lens - 1 + k_eff) // self.pages.page_size
        pool = self._scatter_span(pool, dense, bt, first, lastp)
        return pool, jnp.stack(drafts, axis=1)

    def _spec_verify_body(self, params, pool, bt, tokens, lens, k_eff):
        """Target verify: ONE multi-position forward over the paged pool.

        ``tokens`` (B, k+1) = [last, d_1..d_k] per row (-1 beyond k_eff),
        landing at positions lens..lens+k.  Acceptance and rollback are
        in-graph: a = LCP + 1, and only pages holding accepted positions
        scatter back — everything past them is dropped on the floor."""
        dense = self._gather_dense(pool, bt)
        logits, new_dense = self.model.verify_batch(params, dense, tokens, lens)
        out = jnp.argmax(logits[..., : self.cfg.vocab], axis=-1).astype(
            jnp.int32
        )  # (B, k+1): out[:, t] corrects/extends after fed token t
        match = (out[:, :-1] == tokens[:, 1:]).astype(jnp.int32)  # (B, k)
        acc = jnp.cumprod(match, axis=1).sum(axis=1) + 1  # (B,) in 1..k+1
        acc = jnp.minimum(acc, k_eff + 1)  # -1 padding already enforces this
        first = lens // self.pages.page_size
        lastp = (lens + acc - 1) // self.pages.page_size
        pool = self._scatter_span(pool, new_dense, bt, first, lastp)
        return pool, out, acc

    def _ensure_cache(self):
        if self._cache is None:
            cache = materialize_params(self._cache_specs, jax.random.PRNGKey(0))
            self._cache = jax.device_put(cache, self._cache_shardings)
        if self.spec_k and self._draft_cache is None:
            cache = materialize_params(
                self._draft_cache_specs, jax.random.PRNGKey(0)
            )
            self._draft_cache = jax.device_put(cache, self._draft_shardings)

    def _apply_cow(self):
        """Mirror queued PageTable copy-on-write events on the device pool
        and refresh the affected slot's cached block table."""
        for seq, src, dst in self.pages.drain_cow_events():
            self._ensure_cache()
            self._cache = self._copy_page(
                self._cache, jnp.int32(src), jnp.int32(dst)
            )
            self.metrics["cow_page_copies"] += 1
            for s in self.slots:
                if s.req is not None and s.req.req_id == seq:
                    s.pages = self.pages.pages_of(seq)

    def _bt_width(self, needed: int) -> int:
        """Block-table width: next power of two ≥ the widest active slot's
        page coverage (capped at a full slot) — recompiles stay O(log)."""
        n = 1
        while n < needed:
            n *= 2
        return min(n, max(self._pages_per_slot, 1))

    def _bt_width_spec(self, needed: int) -> int:
        """Speculative block-table width: like _bt_width, but capped one
        burst wider than a full slot.  The verify forward WRITES k+1
        positions starting at pos regardless of per-row k_eff, and
        ``dynamic_update_slice`` clamps out-of-range starts — a too-narrow
        gathered view would silently shift those writes onto valid KV.  The
        extra columns are null pages: written as scratch, never scattered."""
        cap = -(-(self.max_len + self.spec_k) // self.pages.page_size)
        n = 1
        while n < needed:
            n *= 2
        return min(max(n, 1), max(cap, 1))

    # -- request admission --------------------------------------------------
    def _prefix_parent(self, prompt: np.ndarray) -> tuple[str | None, int]:
        """Longest-common-prefix live sequence to share pages with (must
        cover at least one full page to be worth a refcount)."""
        if not (self.paged and self.share_prefixes):
            return None, 0
        best, best_l = None, 0
        for sid, pp in self._live_prompts.items():
            if sid not in self.pages.live_sequences():
                continue
            m = min(len(pp), len(prompt))
            if m <= best_l:
                continue
            neq = np.nonzero(pp[:m] != prompt[:m])[0]
            l = int(neq[0]) if len(neq) else m
            if l > best_l:
                best, best_l = sid, l
        if best_l >= self.pages.page_size:
            return best, best_l
        return None, 0

    def _allocate_for(self, req: Request) -> None:
        """Claim (and possibly share) pages for ``req`` — the admission
        decision; device-side prefill/insert happens in _insert_prefill."""
        total = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        parent, ptok = self._prefix_parent(req.prompt)
        if parent is not None:
            self.pages.allocate(
                req.req_id, len(req.prompt), reserve_tokens=total,
                prefix_of=parent, prefix_tokens=ptok,
            )
            self.metrics["prefix_shared_pages"] += len(
                self.pages.borrowed_pages(req.req_id)
            )
        else:
            self.pages.allocate(req.req_id, len(req.prompt), reserve_tokens=total)
        if self.spec_k:
            # lockstep draft allocation (no sharing: draft KV is advisory);
            # keep the two pools atomic — a draft-side failure must not
            # leave a half-admitted sequence holding target pages
            try:
                self.draft_pages.allocate(
                    req.req_id, len(req.prompt), reserve_tokens=total
                )
            except BaseException:
                self.pages.free_sequence(req.req_id)
                raise
        self._live_prompts[req.req_id] = np.asarray(req.prompt, np.int32)

    def _slot_ids_row(self, req_id: str, table=None) -> np.ndarray:
        """Physical destination pages for one admitted row's insert: owned
        pages in token order; borrowed (shared-prefix) pages and the
        unallocated tail map to the null page."""
        table = table if table is not None else self.pages
        ids = np.full((self._pages_per_slot,), self._null_page, np.int32)
        borrowed = table.borrowed_pages(req_id)
        for j, p in enumerate(table.pages_of(req_id)):
            if p not in borrowed:
                ids[j] = p
        return ids

    def _admit_span(self, name: str, **stats):
        """A leaf span of admission: its decode gap and its batch as stats."""
        return _span(name, step=self.metrics["decode_steps"],
                     batch=self.metrics["admissions"], **stats)

    def _prefill_one(self, req: Request, slot_idx: int):
        """Enqueue one request's prefill and insert; returns its logits."""
        prompt = jnp.asarray(req.prompt[None], jnp.int32)
        logits, cache1 = self._prefill(self.params, prompt)
        self.metrics["prefill_tokens"] += prompt.size
        if self.paged:
            ids = self._slot_ids_row(req.req_id)
            self._cache = self._insert_pages(
                self._cache, cache1, jnp.asarray(ids)
            )
            if self.spec_k:
                _, dcache1 = self._draft_prefill(self.draft_params, prompt)
                self._draft_cache = self._insert_pages(
                    self._draft_cache,
                    dcache1,
                    jnp.asarray(
                        self._slot_ids_row(req.req_id, self.draft_pages)
                    ),
                )
        else:
            self._cache = self._admit_cache(
                self._cache, cache1, jnp.int32(slot_idx)
            )
        return logits

    def _insert_prefill(self, batch: list[tuple[Request, int]]) -> list[int]:
        """Prefill + device insert for admitted requests; returns each
        request's first token (from the prefill logits).

        Every request runs its own one-row prefill at its own length, then
        its own insert; the batch shares one dispatch span, one pull of the
        logits and one emit, not a program.  Rows of different requests
        would share only the read of the weights: a prefill row of more
        than about 240 tokens is compute-bound alone (the v5e's ridge
        point, 197e12 FLOP/s over 819e9 B/s), so padding rows up to the
        slot count, or to the batch's longest prompt, adds work nobody
        reads.  And a one-row prefill reaches only the shapes a request
        sent alone reaches, so warming each prompt length alone warms
        every batch: a row count that followed the batch size would meet
        its first multi-request batch uncompiled, while serving."""
        tokens = sum(len(req.prompt) for req, _ in batch)
        with self._admit_span("serve.admit.dispatch", reqs=len(batch),
                              rows=len(batch), tokens=tokens):
            self._ensure_cache()
            if self.paged:
                self._apply_cow()  # allocate-time COW copies land before insert
            logits = [self._prefill_one(req, slot_idx) for req, slot_idx in batch]
        with self._admit_span("serve.admit.pull"):
            firsts = [
                int(np.argmax(np.asarray(lg[0, : self.cfg.vocab], np.float32)))
                for lg in jax.device_get(logits)
            ]
        if len(batch) > 1:
            self.metrics["batched_prefills"] += 1
        now = time.perf_counter()
        for (req, slot_idx), first in zip(batch, firsts):
            slot = self.slots[slot_idx]
            slot.req = req
            # pos = KV entries in the cache; the first token's KV is
            # written by the decode step that consumes it
            slot.pos = len(req.prompt)
            slot.generated = [first]
            slot.first_token_at = now
            slot.pages = self.pages.pages_of(req.req_id) if self.paged else []
            self.metrics["prefills"] += 1
            self.metrics["tokens"] += 1
        self._notify_load()
        return firsts

    def _notify_load(self) -> None:
        """Publish current capacity through the ``on_load_change`` hook.

        A broken publish channel (store server briefly unreachable) must
        not abort the serve loop — the failure is counted so it is never
        silent, and the next admission/completion retries naturally.
        """
        if self.on_load_change is None:
            return
        try:
            self.on_load_change(self.pages.pages_available())
        except BaseException:
            self.metrics["load_publish_failures"] += 1

    def _request_lifetime(self, req_id: str) -> ContextLifetime:
        lt = self._req_lifetimes.get(req_id)
        if lt is None:
            lt = self._req_lifetimes[req_id] = ContextLifetime()
        return lt

    def _response_lifetime(self, req_id: str) -> ContextLifetime:
        lt = self._resp_lifetimes.get(req_id)
        if lt is None:
            lt = self._resp_lifetimes[req_id] = ContextLifetime()
        return lt

    def admit(self, req: Request, slot_idx: int) -> int:
        """Admit one request into ``slot_idx``; returns its *first* token.

        The first generated token comes from the prefill logits — it exists
        the moment the request is admitted, before any decode step (the
        decode loop's job is tokens 2..n, each fed back at its own per-slot
        position).
        """
        self._allocate_for(req)
        return self._insert_prefill([(req, slot_idx)])[0]

    def _finish(self, slot_idx: int):
        slot = self.slots[slot_idx]
        req = slot.req
        self.pages.free_sequence(req.req_id)  # ownership free → pages + store
        if self.spec_k:
            self.draft_pages.free_sequence(req.req_id)
        self._live_prompts.pop(req.req_id, None)
        now = time.perf_counter()
        self.completed[req.req_id] = {
            "tokens": list(slot.generated),
            "latency": now - req.arrived,
            "ttft": (slot.first_token_at or now) - req.arrived,
        }
        slot.req = None
        slot.pos = 0
        slot.generated = []
        slot.first_token_at = None
        slot.pages = []
        self._notify_load()

    def _spec_decode_step(self, active, send_delta, finish_if_done):
        """One speculative engine step over the active slots: draft k
        proposals per slot, verify all of them in one target forward, emit
        the accepted run (target argmaxes — bit-identical to plain greedy).

        Per-slot depth ``k_eff`` clamps speculation to what the request can
        still accept (remaining-1) and to the cache horizon (max_len-2-pos),
        so both pools' extends stay inside the admission reservation.  A
        k_eff of 0 degenerates to an exact single-token decode step."""
        k = self.spec_k
        B = len(self.slots)
        prev = np.zeros((B,), np.int32)
        last = np.zeros((B,), np.int32)
        lens = np.ones((B,), np.int32)  # idle rows decode garbage at pos 0
        k_eff = np.zeros((B,), np.int32)
        for i in active:
            s = self.slots[i]
            g = len(s.generated)
            remaining = s.req.max_new_tokens - g
            k_eff[i] = max(0, min(k, remaining - 1, self.max_len - 2 - s.pos))
            last[i] = s.generated[-1]
            prev[i] = s.generated[-2] if g >= 2 else int(s.req.prompt[-1])
            lens[i] = s.pos
            # both pools must own every page a fully-accepted run writes
            # BEFORE the step (extend within the reservation never fails)
            if self.pages.extend(s.req.req_id, s.pos + int(k_eff[i]) + 1):
                s.pages = self.pages.pages_of(s.req.req_id)
            self.draft_pages.extend(s.req.req_id, s.pos + int(k_eff[i]))
        self._apply_cow()
        width = self._bt_width_spec(max(
            self.pages.pages_needed(self.slots[i].pos + k + 1) for i in active
        ))
        bt = np.full((B, width), self._null_page, np.int32)
        bt_d = np.full((B, width), self._null_page, np.int32)
        for i in active:
            s = self.slots[i]
            m = min(len(s.pages), width)
            bt[i, :m] = s.pages[:m]
            dpages = self.draft_pages.pages_of(s.req.req_id)
            md = min(len(dpages), width)
            bt_d[i, :md] = dpages[:md]
        self._ensure_cache()
        self._draft_cache, drafts = self._spec_draft(
            self.draft_params, self._draft_cache, jnp.asarray(bt_d),
            jnp.asarray(prev), jnp.asarray(last), jnp.asarray(lens),
            jnp.asarray(k_eff),
        )
        drafts_np = np.asarray(drafts, np.int32)  # (B, k)
        ver = np.full((B, k + 1), -1, np.int32)
        ver[:, 0] = last
        for i in active:  # -1 beyond k_eff never matches an argmax
            ver[i, 1 : 1 + k_eff[i]] = drafts_np[i, : k_eff[i]]
        self._cache, out, acc = self._spec_verify(
            self.params, self._cache, jnp.asarray(bt), jnp.asarray(ver),
            jnp.asarray(lens), jnp.asarray(k_eff),
        )
        self.metrics["decode_steps"] += 1
        self.metrics["spec_steps"] += 1
        out_np = np.asarray(out, np.int32)
        acc_np = np.asarray(acc, np.int32)
        for i in active:
            s = self.slots[i]
            self.metrics["spec_slot_steps"] += 1
            for t in out_np[i, : int(acc_np[i])]:
                t = int(t)
                s.generated.append(t)
                s.pos += 1  # this token's KV scattered back by the verify
                self.metrics["tokens"] += 1
                self.metrics["spec_accepted_tokens"] += 1
                send_delta(s.req.req_id, t, len(s.generated) - 1)
                if t == self.eos_id:
                    break  # accepted run truncates at eos; pages free below
            finish_if_done(i)

    # -- main loop ----------------------------------------------------------
    def run(
        self,
        request_consumer: StreamConsumer,
        response_producer: StreamProducer | None = None,
        *,
        max_requests: int | None = None,
        response_topic: str = "responses",
        stream_deltas: bool = True,
        close_responses: bool = True,
    ):
        """Serve until the request stream closes (or ``max_requests`` have
        been served) and all slots drain.  Re-entrant: a later ``run`` on a
        consumer that resumes the topic continues where this one stopped
        (the engine-restart path).

        No polling: while idle the loop sleeps on a condition variable the
        puller thread notifies; while decoding it never waits on the
        request stream at all.
        """
        pending: deque[Request] = deque()
        cond = threading.Condition()
        state = {
            "open": True, "pulled": 0, "error": None, "stop": False,
            "failed": [],  # (req_id, why) from the puller → rejected here
        }

        def want_more() -> bool:
            return max_requests is None or state["pulled"] < max_requests

        # Pull-side backpressure: resolve at most this many requests ahead
        # of admission (the seed engine's slots-bounded drain, kept) — a
        # 100k-deep request topic must not materialize 100k prompt arrays.
        high_water = 2 * len(self.slots)

        def pull_loop():
            # Blocks in the consumer (broker condition wait / connector
            # wait_for); the tick only makes stop/max_requests responsive.
            while True:
                with cond:
                    while (
                        not state["stop"]
                        and state["open"]
                        and want_more()
                        and len(pending) >= high_water
                    ):
                        cond.wait(_WAIT_TICK)  # admission drains → notify
                    if state["stop"] or not (state["open"] and want_more()):
                        return
                try:
                    proxy, meta = request_consumer.next_with_metadata(
                        timeout=_WAIT_TICK
                    )
                except StopIteration:
                    with cond:
                        state["open"] = False
                        cond.notify_all()
                    return
                except TimeoutError:
                    continue
                except BaseException as e:  # stream-level failure (broker,
                    # subscriber): fatal for the run, surfaced by run() —
                    # never a silently dead puller and a hung engine
                    with cond:
                        state["error"] = e
                        state["open"] = False
                        cond.notify_all()
                    return
                if proxy is None:
                    continue  # stray meta-only event: not a request
                # Per-request failures are NOT fatal: one tenant's evicted
                # payload or missing field must not abort everyone else's
                # generation.  Addressable bad requests become rejections;
                # unaddressable events (no req_id) can only be counted.
                req_id = None
                try:
                    req_id = meta["req_id"]
                    # metadata-only dispatch: the bulk prompt resolves
                    # here, in the engine — overlapped with the decode
                    # loop, never in an intermediate scheduler
                    body = extract(proxy)
                    f = object.__getattribute__(proxy, "__factory__")
                    if not getattr(f, "evict_on_resolve", True):
                        # persistent prompt bulk (producer without the
                        # one-shot contract): the request's lifetime takes
                        # custody so close() reclaims it
                        self._request_lifetime(req_id).add(
                            Store.get_or_reattach(f.store_name, f.connector),
                            f.key,
                        )
                    req = Request(
                        req_id=req_id,
                        prompt=np.asarray(body["prompt"], np.int32),
                        max_new_tokens=int(meta.get("max_new_tokens", 16)),
                    )
                except BaseException as e:
                    if req_id is None:
                        # unaddressable event: nobody else will ever pull
                        # this topic, so its unresolved bulk payload would
                        # be resident forever — reclaim it.  A failed
                        # reclaim is no longer swallowed: it is counted
                        # (``reclaim_failures``) and the orphan is handed
                        # to ProxySan so it surfaces in the leak report
                        # for as long as it stays resident.
                        f = None
                        try:
                            f = object.__getattribute__(proxy, "__factory__")
                            Store.get_or_reattach(
                                f.store_name, f.connector
                            ).evict(f.key)
                        except BaseException:
                            self.metrics["reclaim_failures"] += 1
                            if f is not None:
                                san = _sanitize.active_for(f.store_name)
                                if san is not None:
                                    san.note_orphan(
                                        f.store_name, f.connector, f.key
                                    )
                    with cond:
                        state["pulled"] += 1
                        if req_id is None:
                            self.metrics["malformed_events"] += 1
                        else:
                            state["failed"].append(
                                (req_id, f"bad request: {e!r}")
                            )
                        cond.notify_all()
                    continue
                with cond:
                    state["pulled"] += 1
                    pending.append(req)
                    self.metrics["max_pending"] = max(
                        self.metrics["max_pending"], len(pending)
                    )
                    cond.notify_all()

        puller = threading.Thread(target=pull_loop, daemon=True)
        puller.start()

        def send_done(req_id: str):
            if response_producer is None:
                return
            entry = self.completed[req_id]
            meta = {
                "req_id": req_id,
                "kind": "done",
                "n_tokens": len(entry["tokens"]),
            }
            if self.done_commit_prefix is not None:
                # fleet mode: commit the record at the deterministic key
                # shared by every engine that might finish this request
                # (put_if_absent — one payload no matter how many twins
                # complete a redispatched request); the event always
                # references that key, the router forwards the first one
                response_producer.send_committed(
                    response_topic,
                    {"req_id": req_id, **entry},
                    key=f"{self.done_commit_prefix}{req_id}",
                    metadata=meta,
                    lifetime=self._response_lifetime(req_id),
                )
                return
            response_producer.send(
                response_topic,
                {"req_id": req_id, **entry},
                metadata=meta,
                # the response lifetime takes custody of the completion
                # bulk: a client that never resolves it (crashed, filtered)
                # no longer leaks it past engine.close(); a client that
                # does resolve it evicts it first (one-shot contract)
                lifetime=self._response_lifetime(req_id),
            )
            response_producer.flush_topic(response_topic)

        def send_reject(req_id: str, why: str):
            self.rejected[req_id] = why
            if response_producer is not None:
                response_producer.send_meta(
                    response_topic,
                    {"req_id": req_id, "kind": "error", "error": why},
                )

        def send_delta(req_id: str, token: int, index: int):
            if stream_deltas and response_producer is not None:
                # incremental token delta: metadata-only, no store put — the
                # client's first token beats the full completion
                response_producer.send_meta(
                    response_topic,
                    {"req_id": req_id, "kind": "delta",
                     "token": token, "index": index,
                     "sent_at": time.perf_counter()},
                )

        def finish_if_done(slot_idx: int) -> bool:
            s = self.slots[slot_idx]
            last = s.generated[-1]
            done = (
                last == self.eos_id
                or len(s.generated) >= s.req.max_new_tokens
                or s.pos >= self.max_len - 1
            )
            if done:
                req_id = s.req.req_id
                self._finish(slot_idx)
                send_done(req_id)
            return done

        def pop_next(taken: set) -> tuple[str, Request | None, int, str]:
            """FIFO head-of-line admission decision for one request."""
            with cond:
                if not pending:
                    return ("empty", None, -1, "")
                req = pending[0]
                total = min(len(req.prompt) + req.max_new_tokens, self.max_len)
                if req.req_id in self.pages.live_sequences():
                    pending.popleft()  # one bad request must not crash
                    cond.notify_all()  # every other tenant's serve
                    return (
                        "reject", req, -1,
                        f"req_id {req.req_id!r} is already being served",
                    )
                if len(req.prompt) > self.max_len - 1:
                    pending.popleft()  # prompt alone overflows the cache
                    cond.notify_all()
                    return (
                        "reject", req, -1,
                        f"prompt of {len(req.prompt)} tokens exceeds "
                        f"max_len-1 ({self.max_len - 1})",
                    )
                if self.pages.pages_needed(total) > self.pages.num_pages:
                    pending.popleft()  # can never fit: reject, don't wedge
                    cond.notify_all()
                    return (
                        "reject", req, -1,
                        f"request needs {self.pages.pages_needed(total)} "
                        f"pages; the pool has {self.pages.num_pages}",
                    )
                if not self.pages.can_admit(total) or (
                    self.spec_k and not self.draft_pages.can_admit(total)
                ):
                    # backpressure: head-of-line waits for pages (FIFO —
                    # later requests must not starve an earlier one); under
                    # speculation BOTH pools must cover the full generation
                    self.metrics["queued_admissions"] += 1
                    return ("wait", None, -1, "")
                free = [
                    i for i, s in enumerate(self.slots)
                    if s.req is None and i not in taken
                ]
                if not free:
                    return ("wait", None, -1, "")
                pending.popleft()
                cond.notify_all()  # wake a pull blocked at high water
                return ("admit", req, free[0], "")

        def admit_pending() -> int:
            admitted = 0
            with cond:
                failed, state["failed"] = state["failed"], []
            for rid, why in failed:  # puller-detected per-request failures
                send_reject(rid, why)
            batching = self.paged and self.batch_prefill
            while True:
                batch: list[tuple[Request, int]] = []
                taken: set[int] = set()
                start = time.perf_counter()
                while len(taken) < len(self.slots):
                    action, req, target, why = pop_next(taken)
                    if action == "reject":
                        send_reject(req.req_id, why)
                        continue
                    if action != "admit":
                        break
                    # a request that arrived while the batch formed waited 0
                    wait = max(0.0, start - req.arrived)
                    self.metrics["queue_wait_s"] += wait
                    # allocate now (so can_admit sees this batch's pages);
                    # prefill + insert of each request are dispatched below
                    with self._admit_span("serve.admit.allocate",
                                          prompt_len=len(req.prompt),
                                          wait_us=1e6 * wait):
                        self._allocate_for(req)
                    batch.append((req, target))
                    taken.add(target)
                    if not batching:
                        break
                if not batch:
                    return admitted
                firsts = self._insert_prefill(batch)
                with self._admit_span("serve.admit.emit", reqs=len(batch)):
                    for (req, target), first in zip(batch, firsts):
                        send_delta(req.req_id, first, 0)
                        finish_if_done(target)  # 1-token request: done at admission
                        admitted += 1
                self.metrics["admissions"] += 1

        def serve_loop():
            while True:
                self.metrics["loop_iters"] += 1
                admit_pending()
                active = [
                    i for i, s in enumerate(self.slots) if s.req is not None
                ]
                if not active:
                    with cond:
                        if state["error"] is not None:
                            raise state["error"]
                        if not pending and not state["failed"]:
                            # every pulled request is resolved once pending
                            # is empty and no slot is active
                            if not state["open"] or not want_more():
                                return
                            # notification wait: woken by the puller on
                            # arrival or close; the tick bounds shutdown,
                            # not wake-up
                            self.metrics["idle_waits"] += 1
                            with _span("serve.idle",
                                       step=self.metrics["decode_steps"]):
                                cond.wait(_WAIT_TICK)
                    continue
                if self.spec_k:
                    # speculative multi-token step: draft proposes, target
                    # verifies in one paged forward, accepted run streams out
                    self._spec_decode_step(active, send_delta, finish_if_done)
                    continue
                # batched decode step: every slot's last generated token is
                # fed back at that slot's own position (idle slots decode
                # garbage against the null page — never read)
                step = self.metrics["decode_steps"]
                width = self._bt_width(max(
                    self.pages.pages_needed(self.slots[i].pos + 1)
                    for i in active
                )) if self.paged else 0
                with _span("serve.decode.prepare", step=step,
                           active=len(active), width=width):
                    tokens = np.zeros((len(self.slots),), np.int32)
                    lens = np.zeros((len(self.slots),), np.int32)
                    for i in active:
                        s = self.slots[i]
                        tokens[i] = s.generated[-1]
                        lens[i] = s.pos
                    self._ensure_cache()
                    if self.paged:
                        # the page holding position pos must exist and be
                        # owned before the step writes it: extend — and any
                        # copy-on-write it triggers — happens pre-step
                        for i in active:
                            s = self.slots[i]
                            if self.pages.extend(s.req.req_id, s.pos + 1):
                                s.pages = self.pages.pages_of(s.req.req_id)
                        self._apply_cow()
                        bt = np.full(
                            (len(self.slots), width), self._null_page, np.int32
                        )
                        for i in active:
                            s = self.slots[i]
                            cov = self.pages.pages_needed(s.pos + 1)
                            bt[i, :cov] = s.pages[:cov]
                with _span("serve.decode.dispatch", step=step):
                    if self.paged:
                        self._cache, logits = self._decode(
                            self.params, self._cache, jnp.asarray(bt),
                            jnp.asarray(tokens[:, None]), jnp.asarray(lens),
                        )
                    else:
                        self._cache, logits = self._decode(
                            self.params, self._cache,
                            jnp.asarray(tokens[:, None]), jnp.asarray(lens),
                        )
                with _span("serve.decode.pull", step=step):
                    logits_np = np.asarray(logits, np.float32)
                    nxts = np.argmax(logits_np[active, : self.cfg.vocab], axis=-1)
                with _span("serve.decode.emit", step=step, tokens=len(active)):
                    for i, nxt in zip(active, nxts.tolist()):
                        s = self.slots[i]
                        s.generated.append(nxt)
                        s.pos += 1  # the fed-back token's KV is now cached
                        if not self.paged:
                            self.pages.extend(s.req.req_id, s.pos)
                        self.metrics["tokens"] += 1
                        send_delta(s.req.req_id, nxt, len(s.generated) - 1)
                        finish_if_done(i)
                self.metrics["decode_steps"] += 1

        try:
            serve_loop()
        finally:
            # Whatever exits the loop — drain, max_requests, or an
            # exception (decode failure, a response-store error) — the
            # puller must die with this run: an orphaned puller would keep
            # stealing requests into a dead run's pending deque forever.
            with cond:
                state["stop"] = True
                cond.notify_all()
            puller.join(timeout=5 * _WAIT_TICK)
        if response_producer is not None and close_responses:
            response_producer.close_topic(response_topic)
        return self.completed

    # -- lifecycle -----------------------------------------------------------
    def close(self, *, reclaim_responses: bool = True) -> None:
        """Tear the engine down and end every per-request scope.

        ``reclaim_responses=False`` is for the restart handoff: the
        response stream outlives this engine (``run(close_responses=
        False)`` or an engine replaced mid-stream), so completion bulks a
        lagging client has not resolved yet must stay resident — stream
        payloads resolve blocking, and evicting one under a live client
        wedges it.  Custody then rests with the clients' one-shot
        resolves (and ultimately whoever closes the topic).
        """
        for seq in self.pages.live_sequences():
            self.pages.free_sequence(seq)
        if self.spec_k:
            for seq in self.draft_pages.live_sequences():
                self.draft_pages.free_sequence(seq)
        self._live_prompts.clear()
        # Request-side scopes: persistent prompt bulks were consumed by
        # this engine's puller — always safe to reclaim.
        lifetimes, self._req_lifetimes = self._req_lifetimes, {}
        for lt in lifetimes.values():
            lt.close()
        # Response-side scopes: evict completion bulks no client resolved.
        # Default assumes the driver pattern (clients joined before close;
        # resolved one-shot payloads are already gone, the evict is then a
        # no-op), so in-flight resolves never race this.
        resp, self._resp_lifetimes = self._resp_lifetimes, {}
        if reclaim_responses:
            for lt in resp.values():
                lt.close()
        if self._owns_store:  # never close a store the caller handed in
            self.kv_store.close()
        if self._draft_store is not None:  # always engine-owned
            self._draft_store.close()
