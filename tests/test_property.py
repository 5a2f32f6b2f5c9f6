"""Property-based tests (hypothesis) for the system's invariants."""
from __future__ import annotations

import pickle

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (
    InMemoryConnector,
    OwnershipError,
    Store,
    borrow,
    clone,
    free,
    mut_borrow,
    owned_proxy,
    release,
)
from repro.core.proxy import Proxy, extract, is_resolved
from repro.core.streaming import (
    QueuePublisher,
    QueueSubscriber,
    StreamConsumer,
    StreamProducer,
)

SETTINGS = settings(
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.function_scoped_fixture],
)

# objects a store must round-trip faithfully
objects = st.one_of(
    st.integers(),
    st.floats(allow_nan=False),
    st.text(max_size=64),
    st.lists(st.integers(), max_size=16),
    st.dictionaries(st.text(max_size=8), st.integers(), max_size=8),
    st.binary(max_size=256),
)


@pytest.fixture
def store():
    with Store(f"prop-{np.random.randint(1e9)}") as s:
        yield s


class TestProxyRoundTrip:
    @SETTINGS
    @given(obj=objects)
    def test_proxy_equals_target(self, store, obj):
        """∀ obj: extract(store.proxy(obj)) == obj (pass-by-value fidelity)."""
        p = store.proxy(obj)
        assert extract(p) == obj

    @SETTINGS
    @given(obj=objects)
    def test_proxy_type_transparency(self, store, obj):
        """isinstance(p, type(t)) is true for a proxy p and target t (§III)."""
        p = store.proxy(obj)
        assert isinstance(p, type(obj))

    @SETTINGS
    @given(obj=objects)
    def test_pickled_proxy_still_resolves(self, store, obj):
        """Proxies are self-contained across (de)serialization (§III)."""
        p = store.proxy(obj)
        p2 = pickle.loads(pickle.dumps(p))
        assert extract(p2) == obj

    @SETTINGS
    @given(arr=st.lists(st.floats(allow_nan=False, width=32), min_size=1, max_size=64))
    def test_numpy_fidelity(self, store, arr):
        a = np.asarray(arr, np.float32)
        p = store.proxy(a)
        np.testing.assert_array_equal(extract(p), a)


class TestFutureInvariants:
    @SETTINGS
    @given(obj=objects)
    def test_set_once_then_every_proxy_resolves(self, store, obj):
        fut = store.future()
        proxies = [fut.proxy() for _ in range(3)]
        assert not fut.done()
        fut.set_result(obj)
        assert fut.done()
        for p in proxies:
            assert extract(p) == obj

    @SETTINGS
    @given(obj=objects)
    def test_double_set_always_raises(self, store, obj):
        fut = store.future()
        fut.set_result(obj)
        with pytest.raises(RuntimeError):
            fut.set_result(obj)


class TestStreamOrdering:
    @SETTINGS
    @given(items=st.lists(objects, min_size=1, max_size=12))
    def test_fifo_and_exactly_once(self, store, items):
        """Stream delivers every item exactly once, in order."""
        ns = f"prop-{np.random.randint(1e9)}"
        producer = StreamProducer(QueuePublisher(ns), {"t": store})
        consumer = StreamConsumer(QueueSubscriber("t", ns), timeout=5.0)
        for it in items:
            producer.send("t", it)
            producer.flush_topic("t")
        producer.close_topic("t")
        got = [extract(p) for p in consumer]
        assert got == list(items)


class TestOwnershipInvariants:
    @SETTINGS
    @given(obj=objects, n_refs=st.integers(0, 4))
    def test_borrow_rules(self, store, obj, n_refs):
        """Any number of Refs XOR exactly one RefMut; free only when clear."""
        owner = owned_proxy(store, obj)
        refs = [borrow(owner) for _ in range(n_refs)]
        if n_refs:
            with pytest.raises(OwnershipError):
                mut_borrow(owner)  # Ref(s) outstanding → no RefMut
            with pytest.raises(OwnershipError):
                free(owner)  # cannot free with live borrows
        for r in refs:
            release(r)
        m = mut_borrow(owner)
        with pytest.raises(OwnershipError):
            borrow(owner)  # RefMut outstanding → no Ref
        release(m)
        key = owner.__factory__.key
        free(owner)
        assert not store.exists(key)  # free ⇒ target evicted

    @SETTINGS
    @given(obj=objects)
    def test_clone_is_deep_and_independent(self, store, obj):
        a = owned_proxy(store, obj)
        b = clone(a)
        free(a)
        assert extract(b) == obj  # clone survives original's death
        free(b)


class TestElasticPlanInvariants:
    """The re-plan properties the remesh driver relies on (PR 4)."""

    @SETTINGS
    @given(
        chips=st.integers(1, 4096),
        mp=st.sampled_from([1, 2, 4, 8, 16]),
        cpp=st.sampled_from([64, 128, 256]),
    )
    def test_plan_invariants(self, chips, mp, cpp):
        from repro.dist.fault import elastic_plan

        try:
            plan = elastic_plan(chips, model_parallel=mp, chips_per_pod=cpp)
        except ValueError:
            # only legitimate failure: the surviving chips can't host even
            # one model-parallel group
            assert min(chips, cpp) < mp
            return
        assert plan.model == mp  # model parallelism pinned, always
        assert plan.data >= 1
        assert plan.data & (plan.data - 1) == 0  # power of two, always
        assert plan.chips <= chips  # never oversubscribes the survivors
        assert plan.data * plan.model <= cpp  # a DP group never spans pods

    @SETTINGS
    @given(
        chips=st.integers(16, 4096),
        extra=st.integers(0, 1024),
        mp=st.sampled_from([1, 2, 4, 8]),
    )
    def test_plan_monotone_in_available_chips(self, chips, extra, mp):
        """More surviving chips can never produce a smaller mesh."""
        from repro.dist.fault import elastic_plan

        try:
            a = elastic_plan(chips, model_parallel=mp, chips_per_pod=256)
        except ValueError:
            return
        b = elastic_plan(chips + extra, model_parallel=mp, chips_per_pod=256)
        assert b.chips >= a.chips
        assert b.model == a.model  # pinned on both sides of the loss


class TestPageTableInvariants:
    """Serving KV-page allocator properties (PR 5): no double assignment,
    conservation, total reclamation, monotone extends, reservation safety."""

    def _pt(self, num_pages=12, page_size=4):
        from repro.serve.kvcache import PageTable

        store = Store(f"ptp-{np.random.randint(1e9)}")
        return (
            PageTable(
                num_pages=num_pages, page_size=page_size, store=store,
                page_bytes=8,
            ),
            store,
        )

    @SETTINGS
    @given(ops=st.lists(st.integers(0, 10**6), max_size=40))
    def test_allocator_invariants_hold_under_any_op_sequence(self, ops):
        """allocate/extend/free in any order: pages_in_use + pages_free ==
        num_pages, no page owned twice, reservations never negative."""
        pt, store = self._pt()
        live: dict[str, int] = {}
        next_id = 0
        for code in ops:
            kind, arg = code % 3, code // 3
            if kind == 0:
                tokens = arg % 20 + 1
                sid = f"s{next_id}"
                next_id += 1
                try:
                    pt.allocate(sid, tokens, reserve_tokens=tokens + arg % 9)
                except MemoryError:
                    assert pt.pages_needed(tokens) > pt.pages_available() or (
                        pt.pages_needed(tokens + arg % 9) > pt.pages_available()
                    )
                else:
                    live[sid] = tokens
            elif kind == 1 and live:
                sid = sorted(live)[arg % len(live)]
                before = pt.pages_of(sid)
                new_total = live[sid] + arg % 11
                try:
                    pt.extend(sid, new_total)
                except MemoryError:
                    pass
                else:
                    after = pt.pages_of(sid)
                    assert after[: len(before)] == before  # extend is monotone
                    assert len(after) == max(
                        len(before), pt.pages_needed(new_total)
                    )
                    live[sid] = max(live[sid], new_total)
            elif kind == 2 and live:
                sid = sorted(live)[arg % len(live)]
                pt.free_sequence(sid)
                del live[sid]
            # invariants after every single operation
            assert pt.pages_in_use() + pt.pages_free() == pt.num_pages
            owned = [p for s in live for p in pt.pages_of(s)]
            assert len(owned) == len(set(owned))  # never double-assigned
            assert pt.pages_in_use() == len(owned)
            assert 0 <= pt.pages_reserved() <= pt.pages_free()
        for sid in list(live):
            pt.free_sequence(sid)
        # free always returns every page, and the store holds no cells
        assert pt.pages_free() == pt.num_pages
        assert pt.pages_in_use() == 0
        assert pt.pages_reserved() == 0
        for sid in [f"s{i}" for i in range(next_id)]:
            for p in range(pt.num_pages):
                assert not store.exists(pt.page_key(sid, p))
        store.close()

    @SETTINGS
    @given(
        prompt=st.integers(1, 16),
        growth=st.integers(0, 32),
        n_rivals=st.integers(0, 6),
    )
    def test_reservation_makes_extend_infallible(self, prompt, growth, n_rivals):
        """A sequence allocated with reserve_tokens=T can always extend to
        T, no matter what is admitted after it."""
        pt, store = self._pt(num_pages=16, page_size=4)
        total = prompt + growth
        if pt.pages_needed(total) > pt.num_pages:
            store.close()
            return
        pt.allocate("hero", prompt, reserve_tokens=total)
        for i in range(n_rivals):  # rivals soak up whatever is left
            try:
                pt.allocate(f"rival{i}", 8, reserve_tokens=16)
            except MemoryError:
                break
        for t in range(prompt, total + 1):  # token-by-token, like decode
            pt.extend("hero", t)  # MemoryError here = property violated
        assert len(pt.pages_of("hero")) == pt.pages_needed(total)
        for sid in list(pt.live_sequences()):
            pt.free_sequence(sid)
        assert pt.pages_free() == pt.num_pages
        store.close()

    @SETTINGS
    @given(tokens=st.integers(1, 64))
    def test_free_releases_store_memory(self, tokens):
        pt, store = self._pt(num_pages=16, page_size=4)
        if pt.pages_needed(tokens) > pt.num_pages:
            store.close()
            return
        pages = pt.allocate("m", tokens)
        for p in pages:
            assert store.exists(pt.page_key("m", p))
        pt.free_sequence("m")
        for p in pages:
            assert not store.exists(pt.page_key("m", p))
        store.close()


class TestPrefixSharingInvariants:
    """Refcounted shared KV pages (paged-decode PR): conservation under
    arbitrary allocate/share/extend/free interleavings, copy-on-write
    isolation, no double-free, and exact availability accounting."""

    def _pt(self, num_pages=16, page_size=4):
        from repro.serve.kvcache import PageTable

        store = Store(f"psp-{np.random.randint(1e9)}")
        return (
            PageTable(
                num_pages=num_pages, page_size=page_size, store=store,
                page_bytes=8,
            ),
            store,
        )

    def _check_refcounts(self, pt, live):
        """Every page referenced by any live sequence has a refcount equal
        to the number of live sequences referencing it — creators and
        borrowers indistinguishable to the count, orphans included."""
        refs: dict[int, int] = {}
        for sid in live:
            for p in pt.pages_of(sid):
                refs[p] = refs.get(p, 0) + 1
        for p, n in refs.items():
            assert pt.page_refcount(p) == n, (p, n)
        # conservation: the union of referenced pages IS the in-use set
        assert pt.pages_in_use() == len(refs)
        assert pt.pages_in_use() + pt.pages_free() == pt.num_pages
        assert 0 <= pt.pages_reserved() <= pt.pages_free()
        # orphans are exactly the in-use pages whose creator is dead
        assert pt.orphan_pages() <= set(refs)

    @SETTINGS
    @given(ops=st.lists(st.integers(0, 10**6), max_size=40))
    def test_sharing_interleavings_conserve_refcounts(self, ops):
        pt, store = self._pt()
        live: dict[str, int] = {}
        next_id = 0
        for code in ops:
            kind, arg = code % 4, code // 4
            if kind == 0:  # plain allocate
                tokens = arg % 20 + 1
                sid = f"s{next_id}"
                next_id += 1
                try:
                    pt.allocate(sid, tokens, reserve_tokens=tokens + arg % 9)
                except MemoryError:
                    pass
                else:
                    live[sid] = tokens
            elif kind == 1 and live:  # allocate sharing a live prefix
                parent = sorted(live)[arg % len(live)]
                ptok = arg % (live[parent] + 1)
                tokens = max(1, ptok + arg % 8)
                sid = f"s{next_id}"
                next_id += 1
                try:
                    pt.allocate(
                        sid, tokens, reserve_tokens=tokens + arg % 5,
                        prefix_of=parent, prefix_tokens=ptok,
                    )
                except MemoryError:
                    pass
                else:
                    live[sid] = tokens
            elif kind == 2 and live:  # extend (may cross a COW boundary)
                sid = sorted(live)[arg % len(live)]
                new_total = live[sid] + arg % 11
                try:
                    pt.extend(sid, new_total)
                except MemoryError:
                    pass
                else:
                    live[sid] = max(live[sid], new_total)
            elif kind == 3 and live:  # free (parents may die first)
                sid = sorted(live)[arg % len(live)]
                pt.free_sequence(sid)
                del live[sid]
            self._check_refcounts(pt, live)
        for sid in list(live):
            pt.free_sequence(sid)
        assert pt.pages_free() == pt.num_pages
        assert pt.orphan_pages() == set()
        assert sorted(pt._free) == list(range(pt.num_pages))
        store.close()

    @SETTINGS
    @given(
        ptok=st.integers(1, 16),
        child_extra=st.integers(0, 10),
        grow=st.integers(0, 12),
    )
    def test_cow_never_mutates_parent_and_extend_never_fails(
        self, ptok, child_extra, grow
    ):
        """A sharer crossing its prefix boundary copies, never mutates:
        the parent's page list, cells, and refcounts are untouched, and
        the sharer's reservation priced the COW page in, so token-by-token
        extension to the reserved total never raises."""
        pt, store = self._pt(num_pages=32, page_size=4)
        pt.allocate("par", 16, reserve_tokens=20)
        before = list(pt.pages_of("par"))
        child_tokens = max(1, ptok + child_extra)
        reach = child_tokens + grow
        pt.allocate(
            "ch", child_tokens, reserve_tokens=reach,
            prefix_of="par", prefix_tokens=ptok,
        )
        assert pt.pages_of("par") == before
        for t in range(child_tokens, reach + 1):
            pt.extend("ch", t)  # MemoryError here = reservation violated
        assert pt.pages_of("par") == before
        for p in before:
            assert store.exists(pt.page_key("par", p))  # cells intact
        # once the child outgrew the prefix, any partially-shared boundary
        # page was copied: overlap is confined to *full* shared pages
        eff = min(ptok, child_tokens)
        overlap = set(before) & set(pt.pages_of("ch"))
        if reach > eff:
            assert overlap == set(before[: eff // 4])
        # the child's refcounts on shared pages drop to 1 after its free
        pt.free_sequence("ch")
        assert all(pt.page_refcount(p) == 1 for p in before)
        pt.free_sequence("par")
        assert pt.pages_free() == pt.num_pages
        store.close()

    @SETTINGS
    @given(
        n_children=st.integers(1, 4),
        parent_first=st.booleans(),
        ptok=st.integers(4, 12),
    )
    def test_no_double_free_any_teardown_order(
        self, n_children, parent_first, ptok
    ):
        """Shared pages survive their creator (orphaned, not freed), are
        returned exactly once when the last borrower exits, and freeing a
        dead sequence raises instead of corrupting the free list."""
        pt, store = self._pt(num_pages=32, page_size=4)
        pt.allocate("par", 16, reserve_tokens=16)
        shared = set(pt.pages_of("par")[: ptok // 4])
        for i in range(n_children):
            pt.allocate(
                f"ch{i}", ptok, reserve_tokens=ptok + 4,
                prefix_of="par", prefix_tokens=ptok,
            )
        order = (["par"] + [f"ch{i}" for i in range(n_children)]) if (
            parent_first
        ) else ([f"ch{i}" for i in range(n_children)] + ["par"])
        for k, sid in enumerate(order):
            pt.free_sequence(sid)
            if parent_first and k == 0 and shared:
                # creator died with borrows out: cells orphaned, not freed
                assert shared <= pt.orphan_pages() | {
                    p for c in range(n_children) for p in pt.pages_of(f"ch{c}")
                }
        assert pt.orphan_pages() == set()
        assert pt.pages_free() == pt.num_pages
        assert sorted(pt._free) == list(range(pt.num_pages))
        with pytest.raises(KeyError):
            pt.free_sequence("par")  # double-free is an error, not a leak
        assert pt.pages_free() == pt.num_pages
        store.close()

    @SETTINGS
    @given(ptok=st.integers(0, 16), extra=st.integers(1, 8))
    def test_available_accounting_exact_with_shared_pages(self, ptok, extra):
        """pages_available reflects sharing exactly: a child consumes only
        its fresh pages (plus the priced-in COW page for a partial
        boundary), never re-counts borrowed ones."""
        pt, store = self._pt(num_pages=32, page_size=4)
        pt.allocate("par", 16, reserve_tokens=16)
        avail = pt.pages_available()
        total_before = pt.pages_allocated_total
        tokens = ptok + extra
        pt.allocate(
            "ch", tokens, reserve_tokens=tokens,
            prefix_of="par", prefix_tokens=ptok,
        )
        # tokens > ptok always here, so a partial boundary page COWs at
        # allocate and lands in the fresh count; either way the identity
        # is: fresh pages drawn == pages needed − pages borrowed
        n_borrowed = len(pt.borrowed_pages("ch"))
        fresh_now = pt.pages_allocated_total - total_before
        assert fresh_now == pt.pages_needed(tokens) - n_borrowed
        # availability dropped by exactly the fresh pages (reserve==tokens,
        # so no growth reservation is held back on top)
        assert pt.pages_available() == avail - fresh_now
        pt.free_sequence("ch")
        pt.free_sequence("par")
        assert pt.pages_available() == pt.num_pages
        store.close()


    @SETTINGS
    @given(
        k=st.integers(1, 4),
        accepts=st.lists(st.integers(1, 5), min_size=1, max_size=12),
        share=st.booleans(),
    )
    def test_spec_overextend_rollback_never_leaks(self, k, accepts, share):
        """Speculative decode extends a sequence up to k tokens past its
        accepted length every step and 'rolls back' rejected drafts by
        simply not advancing — the page list never shrinks, extends stay
        inside the admission reservation (never raise), nothing leaks,
        and teardown frees every page exactly once."""
        pt, store = self._pt(num_pages=64, page_size=4)
        prompt = 8
        max_new = sum(min(a, k + 1) for a in accepts)
        total = prompt + max_new
        pt.allocate("seq", prompt, reserve_tokens=total)
        if share:  # a prefix-sharing peer must not perturb any of this
            pt.allocate("peer", prompt, reserve_tokens=prompt + 4,
                        prefix_of="seq", prefix_tokens=prompt)
        pos = prompt
        for a in accepts:
            remaining = total - pos
            if remaining <= 0:
                break
            k_eff = max(0, min(k, remaining - 1))
            before = list(pt.pages_of("seq"))
            pt.extend("seq", pos + k_eff + 1)  # the speculative over-extend
            after = pt.pages_of("seq")
            assert after[: len(before)] == before  # never rolls pages back
            pos += min(a, k_eff + 1)  # accepted prefix only; tail rejected
            assert pt.pages_in_use() + pt.pages_free() == pt.num_pages
        for sid in list(pt.live_sequences()):
            pt.free_sequence(sid)
        assert pt.pages_free() == pt.num_pages
        assert pt.orphan_pages() == set()
        assert sorted(pt._free) == list(range(pt.num_pages))
        with pytest.raises(KeyError):
            pt.free_sequence("seq")  # double-free is an error, not a leak
        for p in range(pt.num_pages):
            assert not store.exists(pt.page_key("seq", p))
        store.close()


class TestSpecAcceptanceInvariants:
    """Greedy speculative acceptance (serve/engine.py): the verify math —
    match the padded draft row against the target argmax row and accept
    ``cumprod(match).sum() + 1`` — emits exactly the target-only greedy
    stream for ANY draft, and every step accepts LCP + 1 tokens."""

    @staticmethod
    def _accept(drafts, outs, k, k_eff):
        # mirror of _spec_verify_body: tokens row is [last, d_1..d_k_eff,
        # -1 padding]; argmaxes are ≥ 0, so padding can never match
        padded = list(drafts[:k_eff]) + [-1] * (k - k_eff)
        match = np.cumprod([int(o == d) for o, d in zip(outs[:k], padded)])
        return min(int(match.sum()) + 1, k_eff + 1)

    @SETTINGS
    @given(
        drafts=st.lists(st.integers(0, 9), min_size=0, max_size=6),
        outs=st.lists(st.integers(0, 9), min_size=7, max_size=7),
        k=st.integers(1, 6),
    )
    def test_accepted_length_is_lcp_plus_one(self, drafts, outs, k):
        k_eff = min(len(drafts), k)
        acc = self._accept(drafts, outs, k, k_eff)
        lcp = 0
        while lcp < k_eff and outs[lcp] == drafts[lcp]:
            lcp += 1
        assert acc == lcp + 1
        assert 1 <= acc <= k_eff + 1

    @SETTINGS
    @given(
        target=st.lists(st.integers(0, 9), min_size=1, max_size=24),
        draft=st.lists(st.integers(0, 9), min_size=1, max_size=24),
        garbage=st.lists(st.integers(0, 9), min_size=1, max_size=8),
        k=st.integers(1, 4),
    )
    def test_spec_stream_equals_target_greedy(self, target, draft, garbage, k):
        """Run the engine's step loop shape over arbitrary (draft, target)
        disagreement patterns: whatever the draft proposes — and whatever
        garbage the target row carries *past* the first mismatch — the
        emitted stream is bit-identical to target-only greedy decode."""

        def draft_at(i):
            return draft[i % len(draft)]

        emitted, pos, per_step = [], 0, []
        while pos < len(target):
            k_eff = min(k, len(target) - pos - 1)
            ds = [draft_at(pos + j) for j in range(k_eff)]
            outs, poisoned = [], False
            for j in range(k_eff + 1):
                # target argmaxes are trustworthy only while the verified
                # prefix matched; after the first mismatch the row is junk
                outs.append(garbage[(pos + j) % len(garbage)] if poisoned
                            else target[pos + j])
                if j < k_eff and outs[j] != ds[j]:
                    poisoned = True
            acc = self._accept(ds, outs, k, k_eff)
            emitted.extend(outs[:acc])
            per_step.append(acc)
            pos += acc
        assert emitted == target  # bit-identical to target-only greedy
        assert sum(per_step) == len(target)
        assert all(1 <= a <= k + 1 for a in per_step)


class TestShardingRules:
    @SETTINGS
    @given(
        dim=st.integers(1, 4096),
        axis=st.sampled_from(["embed", "heads", "mlp", "vocab", "batch", None]),
    )
    def test_spec_always_valid(self, dim, axis):
        """logical_to_spec never produces an indivisible sharding."""
        from jax.sharding import PartitionSpec

        from repro.dist.sharding import DEFAULT_RULES, logical_to_spec
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        spec = logical_to_spec((dim,), (axis,), DEFAULT_RULES, mesh)
        assert isinstance(spec, PartitionSpec)
        for entry, d in zip(spec, (dim,)):
            if entry is not None:
                names = entry if isinstance(entry, tuple) else (entry,)
                size = int(np.prod([mesh.shape[n] for n in names]))
                assert d % size == 0
