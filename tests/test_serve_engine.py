"""Serving-engine suite (PR 5): continuous batching, termination,
per-slot position correctness, admission backpressure, the no-poll loop
contract, and PageTable store-level ownership.

Engine correctness rides on ``_serve_toy.CountingModel``: a deterministic
integer "LM" whose next token depends on the whole prefix *and* the exact
position, so any cache/position/slot bug changes tokens immediately, and
engine-vs-reference comparisons are bit-identical (no float caveats).
"""
from __future__ import annotations

import inspect
import threading
import time

import jax
import numpy as np
import pytest

from _serve_toy import CountingModel, reference_decode
from repro.configs import get_smoke_config
from repro.core.connectors import new_key
from repro.core.store import Store
from repro.core.streaming import (
    QueuePublisher,
    QueueSubscriber,
    StreamConsumer,
    StreamProducer,
)
from repro.serve.engine import ServeEngine, serve_context

CFG = get_smoke_config("smollm-135m")


def make_streams(*, timeout=30.0, resp_timeout=30.0):
    ns = f"se-{new_key()}"
    req_store = Store(f"{ns}-req")
    resp_store = Store(f"{ns}-resp")
    return {
        "producer": StreamProducer(QueuePublisher(ns), {"requests": req_store}),
        "consumer": StreamConsumer(
            QueueSubscriber("requests", ns), timeout=timeout
        ),
        "resp_producer": StreamProducer(
            QueuePublisher(ns), {"responses": resp_store}
        ),
        "resp_consumer": StreamConsumer(
            QueueSubscriber("responses", ns), timeout=resp_timeout
        ),
    }


def make_engine(
    *, slots=2, max_len=32, page_size=4, eos_id=-1, num_pages=None, **kw
):
    ctx = serve_context(CFG)
    engine = ServeEngine(
        ctx,
        {},
        slots=slots,
        max_len=max_len,
        page_size=page_size,
        eos_id=eos_id,
        model=CountingModel(CFG),
        **kw,
    )
    if num_pages is not None:  # shrink the pool to force backpressure
        engine.pages.num_pages = num_pages
        engine.pages._free = list(range(num_pages))
    return engine


def send_request(producer, req_id, prompt, max_new, topic="requests"):
    producer.send(
        topic,
        {"prompt": np.asarray(prompt, np.int32)},
        metadata={"req_id": req_id, "max_new_tokens": max_new},
    )
    producer.flush_topic(topic)


def serve(engine, requests, *, with_responses=False, **run_kw):
    """Publish ``requests`` (req_id → (prompt, max_new)), close, run."""
    s = make_streams()
    for rid, (prompt, max_new) in requests.items():
        send_request(s["producer"], rid, prompt, max_new)
    s["producer"].close_topic("requests")
    resp = s["resp_producer"] if with_responses else None
    completed = engine.run(s["consumer"], resp, **run_kw)
    return completed, s


class TestContinuousBatching:
    def test_serves_more_requests_than_slots(self):
        """2× slots requests drain through refilling slots."""
        rng = np.random.default_rng(0)
        engine = make_engine(slots=2)
        reqs = {
            f"r{i}": (rng.integers(1, CFG.vocab, 5).astype(np.int32), 4)
            for i in range(4)
        }
        completed, _ = serve(engine, reqs)
        assert sorted(completed) == sorted(reqs)
        assert all(len(c["tokens"]) == 4 for c in completed.values())
        engine.close()

    def test_slots_refill_as_requests_finish(self):
        """A short request's slot is reused mid-flight by a later request:
        total decode steps stay near the continuous-batching ideal, far
        under the static-batching cost."""
        rng = np.random.default_rng(1)
        engine = make_engine(slots=2, max_len=64, page_size=4)
        # two long + two short: the shorts' slots must host the 2nd long
        reqs = {
            "long0": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 20),
            "short0": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 2),
            "short1": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 2),
            "long1": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 20),
        }
        completed, _ = serve(engine, reqs)
        assert sorted(completed) == sorted(reqs)
        # static batching would cost ≥ 2 batches × 19 steps = 38; continuous
        # overlaps long1 with long0's tail (first token is prefill-produced,
        # so a k-token request needs k-1 decode steps)
        assert engine.metrics["decode_steps"] <= 25
        engine.close()

    def test_max_requests_stops_early_and_resumes(self):
        """run(max_requests=k) serves exactly k and leaves the rest for a
        later run on the same consumer (the restart path)."""
        rng = np.random.default_rng(2)
        engine = make_engine(slots=2)
        s = make_streams()
        reqs = {
            f"r{i}": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 3)
            for i in range(5)
        }
        for rid, (p, mn) in reqs.items():
            send_request(s["producer"], rid, p, mn)
        s["producer"].close_topic("requests")
        first = dict(engine.run(s["consumer"], max_requests=2))
        assert len(first) == 2
        rest = engine.run(s["consumer"])
        assert sorted(rest) == sorted(reqs)  # completed accumulates
        engine.close()

    def test_completed_bookkeeping(self):
        rng = np.random.default_rng(3)
        engine = make_engine(slots=2)
        reqs = {
            "a": (rng.integers(1, CFG.vocab, 6).astype(np.int32), 5),
            "b": (rng.integers(1, CFG.vocab, 3).astype(np.int32), 2),
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            entry = completed[rid]
            assert len(entry["tokens"]) == max_new
            assert entry["latency"] > 0
            assert 0 < entry["ttft"] <= entry["latency"]
        assert engine.metrics["tokens"] == sum(m for _, m in reqs.values())
        engine.close()


class TestDecodeCorrectness:
    def test_tokens_bit_identical_to_sequential_reference(self):
        """Continuous batching must not change a single token: every
        request's output equals a sequential single-request greedy decode."""
        rng = np.random.default_rng(4)
        engine = make_engine(slots=3, max_len=32)
        reqs = {
            f"r{i}": (
                rng.integers(1, CFG.vocab, int(rng.integers(3, 9))).astype(
                    np.int32
                ),
                int(rng.integers(2, 8)),
            )
            for i in range(7)
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            ref = reference_decode(CFG, prompt, max_new, max_len=32)
            assert completed[rid]["tokens"] == ref, rid
        engine.close()

    def test_idle_slots_do_not_perturb_active_ones(self):
        """A request served alone on a wide engine (3 idle slots decoding
        masked garbage) produces the same tokens as on a 1-slot engine."""
        prompt = np.arange(1, 7, dtype=np.int32)
        wide = make_engine(slots=4)
        narrow = make_engine(slots=1)
        got_wide, _ = serve(wide, {"x": (prompt, 6)})
        got_narrow, _ = serve(narrow, {"x": (prompt, 6)})
        assert got_wide["x"]["tokens"] == got_narrow["x"]["tokens"]
        assert got_wide["x"]["tokens"] == reference_decode(CFG, prompt, 6, max_len=32)
        wide.close()
        narrow.close()

    def test_per_slot_positions_differ(self):
        """Slots decode at different positions in the same batched step —
        staggered admissions (different prompt lengths) stay correct."""
        engine = make_engine(slots=2, max_len=32)
        reqs = {
            "shortp": (np.asarray([5, 9], np.int32), 6),
            "longp": (np.asarray(range(1, 12), np.int32), 6),
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        engine.close()


class TestTermination:
    def test_eos_stops_generation(self):
        prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
        ref = reference_decode(CFG, prompt, 10, max_len=32)
        eos = ref[2]  # make the 3rd greedy token the stop token
        engine = make_engine(slots=2, eos_id=eos)
        completed, _ = serve(engine, {"e": (prompt, 10)})
        assert completed["e"]["tokens"] == ref[:3]  # eos included, then stop
        assert engine.pages.pages_in_use() == 0
        engine.close()

    def test_eos_on_first_token_finishes_at_admission(self):
        prompt = np.asarray([7, 7, 2], np.int32)
        ref = reference_decode(CFG, prompt, 10, max_len=32)
        engine = make_engine(slots=2, eos_id=ref[0])
        completed, _ = serve(engine, {"e": (prompt, 10)})
        assert completed["e"]["tokens"] == [ref[0]]
        assert engine.metrics["decode_steps"] == 0  # prefill alone served it
        engine.close()

    def test_max_new_tokens(self):
        prompt = np.asarray([2, 4, 6], np.int32)
        engine = make_engine(slots=1)
        completed, _ = serve(engine, {"m": (prompt, 4)})
        assert len(completed["m"]["tokens"]) == 4
        engine.close()

    def test_max_len_caps_generation(self):
        """A request whose max_new would overflow the cache stops at the
        engine's max_len boundary."""
        engine = make_engine(slots=1, max_len=16, page_size=4)
        prompt = np.asarray(range(1, 9), np.int32)  # 8 prompt tokens
        completed, _ = serve(engine, {"cap": (prompt, 100)})
        # pos starts at 8; decode may run until pos == max_len - 1
        assert len(completed["cap"]["tokens"]) == 16 - 1 - 8 + 1
        assert engine.pages.pages_in_use() == 0
        engine.close()


class TestAdmissionControl:
    def test_backpressure_queues_when_pool_tight(self):
        """A pool with room for one sequence serves 2×slots requests
        sequentially instead of OOMing."""
        engine = make_engine(slots=2, max_len=32, page_size=4, num_pages=3)
        rng = np.random.default_rng(5)
        reqs = {
            f"r{i}": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 6)
            for i in range(4)
        }
        # each request reserves ceil((4+6)/4) = 3 pages = the whole pool
        completed, _ = serve(engine, reqs)
        assert sorted(completed) == sorted(reqs)
        assert engine.metrics["queued_admissions"] > 0
        assert engine.pages.pages_in_use() == 0
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            )
        engine.close()

    def test_oversized_request_rejected_not_wedged(self):
        """A request that can never fit is rejected onto the response
        stream; later requests still serve."""
        engine = make_engine(slots=2, max_len=32, page_size=4, num_pages=2)
        reqs = {
            "huge": (np.asarray(range(1, 8), np.int32), 20),  # needs 7 pages
            "ok": (np.asarray([1, 2, 3], np.int32), 3),  # needs 2
        }
        completed, s = serve(engine, reqs, with_responses=True)
        assert "huge" in engine.rejected
        assert "huge" not in completed
        assert completed["ok"]["tokens"] == reference_decode(
            CFG, np.asarray([1, 2, 3], np.int32), 3, max_len=32
        )
        kinds = {}
        while True:
            try:
                _, meta = s["resp_consumer"].next_with_metadata(timeout=5)
            except StopIteration:
                break
            kinds.setdefault(meta["req_id"], []).append(meta["kind"])
        assert "error" in kinds["huge"]
        assert kinds["ok"][-1] == "done"
        engine.close()

    def test_overlong_prompt_rejected_not_crashed(self):
        """A prompt that alone overflows the decode cache is rejected at
        admission instead of crashing the jit'd cache insert."""
        engine = make_engine(slots=2, max_len=16, page_size=4)
        reqs = {
            "big": (np.asarray(range(1, 20), np.int32), 2),  # 19 > 15
            "ok": (np.asarray([1, 2], np.int32), 2),
        }
        completed, _ = serve(engine, reqs)
        assert "big" in engine.rejected and "prompt" in engine.rejected["big"]
        assert completed["ok"]["tokens"] == reference_decode(
            CFG, np.asarray([1, 2], np.int32), 2, max_len=16
        )
        engine.close()

    def test_reservation_prevents_mid_decode_oom(self):
        """Two long sequences that would collide on extends are never
        co-admitted: reservations make admission's promise real."""
        # pool: 4 pages; each request: 2-token prompt (1 page) growing to
        # 10 tokens (3 pages).  Naive prompt-only admission would co-admit
        # both (2 pages ≤ 4) and OOM around token 8.
        engine = make_engine(slots=2, max_len=32, page_size=4, num_pages=4)
        reqs = {
            "g0": (np.asarray([1, 2], np.int32), 8),
            "g1": (np.asarray([3, 4], np.int32), 8),
        }
        completed, _ = serve(engine, reqs)  # MemoryError = test failure
        assert sorted(completed) == ["g0", "g1"]
        assert engine.metrics["queued_admissions"] > 0  # g1 waited
        engine.close()


class TestNotificationDrivenLoop:
    def test_no_sleep_poll_in_run(self):
        # the whole engine module must be clean under every ProxyLint rule
        # (no-sleep-poll flags ANY time.sleep here: serve/engine.py is a
        # designated notification-driven hot-path module)
        import repro.serve.engine as engine_mod
        from repro.analysis.lint import lint_paths

        violations = lint_paths([engine_mod.__file__])
        assert violations == [], "\n".join(v.render() for v in violations)
        # and the idle path is a condition-variable wait, not a poll
        src = inspect.getsource(ServeEngine.run)
        assert "cond.wait" in src

    @pytest.mark.multiproc(timeout=60)  # threads + watchdog: never wedge
    def test_gappy_stream_never_busy_waits(self):
        """2× slots requests with stream gaps: the loop runs ~one iteration
        per decode step / admission / wake — a 5 ms sleep-poll (the seed
        engine) or any busy-spin would add hundreds of iterations across
        the ~1.2 s of enforced gaps."""
        engine = make_engine(slots=2)
        s = make_streams()
        rng = np.random.default_rng(6)
        n = 4

        def client():
            for i in range(n):
                time.sleep(0.3)  # stream gap ≫ decode time
                send_request(
                    s["producer"], f"g{i}",
                    rng.integers(1, CFG.vocab, 4).astype(np.int32), 3,
                )
            s["producer"].close_topic("requests")

        t = threading.Thread(target=client)
        t.start()
        completed = engine.run(s["consumer"])
        t.join()
        assert len(completed) == n
        m = engine.metrics
        # every loop iteration is accounted for by real work or a wake
        assert m["loop_iters"] <= m["decode_steps"] + m["idle_waits"] + n + 4
        # idle wakes are notifications (+ the bounded shutdown tick), not a
        # poll: ~1.2 s of gaps at the seed's 5 ms poll would be ~240
        assert m["idle_waits"] <= 6 * n
        engine.close()

    def test_decode_not_delayed_by_open_stream(self):
        """With the request stream still open but slots active, the loop
        decodes instead of blocking on the consumer (the decode deadline)."""
        engine = make_engine(slots=2)
        s = make_streams()
        send_request(
            s["producer"], "now", np.asarray([1, 2, 3], np.int32), 5
        )
        done = {}

        def finish_later():
            time.sleep(2.5)
            s["producer"].close_topic("requests")

        t = threading.Thread(target=finish_later)
        t.start()
        t0 = time.perf_counter()
        completed = engine.run(s["consumer"])
        done["wall"] = time.perf_counter() - t0
        t.join()
        assert "now" in completed
        # the request itself decoded long before the topic closed: its
        # latency must not include the 2.5 s close delay (2.0 leaves
        # headroom for jit warmup + ProxySan stack-capture overhead)
        assert completed["now"]["latency"] < 2.0
        engine.close()


class TestFailurePaths:
    def test_engine_exception_kills_puller_and_frees_the_stream(self):
        """A decode failure must not orphan the puller thread: requests
        published after the crash stay on the stream for the next engine
        instead of being stolen into the dead run's pending deque."""
        engine = make_engine(slots=2)
        s = make_streams()
        send_request(s["producer"], "boom", np.asarray([1, 2, 3], np.int32), 4)

        def explode(*a, **k):
            raise RuntimeError("injected decode failure")

        engine._decode = explode
        with pytest.raises(RuntimeError, match="injected"):
            engine.run(s["consumer"])
        engine.close()
        # the crashed run's puller is gone: this request must be served by
        # a fresh engine on the same consumer, not swallowed by an orphan
        send_request(s["producer"], "after", np.asarray([4, 5], np.int32), 3)
        s["producer"].close_topic("requests")
        engine2 = make_engine(slots=2)
        completed = engine2.run(s["consumer"])
        assert "after" in completed
        engine2.close()

    def test_malformed_request_rejected_not_fatal(self):
        """A request whose bulk can't be used (missing 'prompt') becomes a
        per-request rejection; other tenants' requests still serve and the
        run completes — no dead puller, no engine-wide abort."""
        engine = make_engine(slots=2)
        s = make_streams()
        s["producer"].send(
            "requests", {"noprompt": True},
            metadata={"req_id": "bad", "max_new_tokens": 3},
        )
        s["producer"].flush_topic("requests")
        send_request(s["producer"], "good", np.asarray([1, 2, 3], np.int32), 3)
        s["producer"].close_topic("requests")
        completed = engine.run(s["consumer"], s["resp_producer"])
        assert "bad" in engine.rejected and "bad" not in completed
        assert completed["good"]["tokens"] == reference_decode(
            CFG, np.asarray([1, 2, 3], np.int32), 3, max_len=32
        )
        engine.close()

    def test_unaddressable_event_counted_not_fatal(self):
        """An event with no req_id can't be rejected back — it is counted
        and skipped, and the run still completes."""
        engine = make_engine(slots=2)
        s = make_streams()
        s["producer"].send("requests", {"prompt": [1, 2]}, metadata={})
        s["producer"].flush_topic("requests")
        send_request(s["producer"], "ok", np.asarray([4, 5], np.int32), 2)
        s["producer"].close_topic("requests")
        completed = engine.run(s["consumer"])
        assert engine.metrics["malformed_events"] == 1
        assert "ok" in completed
        engine.close()
        # the skipped event's bulk was reclaimed, not left resident forever
        # (nobody else ever pulls this topic)
        req_store = s["producer"].store_for("requests")
        assert list(req_store.connector.keys()) == []

    def test_stream_level_failure_still_fatal(self):
        """A broker/subscriber failure (not one request's fault) aborts
        the run loudly — that one must never be swallowed."""
        engine = make_engine(slots=2)
        s = make_streams()

        def broken(timeout=None):
            raise RuntimeError("broker down")

        s["consumer"].subscriber.next_event = broken
        with pytest.raises(RuntimeError, match="broker down"):
            engine.run(s["consumer"])
        engine.close()

    def test_duplicate_req_id_rejected_not_fatal(self):
        """A req_id colliding with a live sequence is rejected onto the
        response stream; the original request is unaffected."""
        engine = make_engine(slots=2)
        prompt = np.asarray([1, 2, 3], np.int32)
        s = make_streams()  # sent manually: serve() keys by id (would dedup)
        send_request(s["producer"], "dup", prompt, 8)
        send_request(s["producer"], "dup", prompt, 8)
        s["producer"].close_topic("requests")
        completed = engine.run(s["consumer"], s["resp_producer"])
        assert "dup" in engine.rejected  # the second one
        assert completed["dup"]["tokens"] == reference_decode(
            CFG, prompt, 8, max_len=32
        )
        engine.close()

    def test_pull_ahead_is_bounded(self):
        """The puller resolves at most 2×slots requests ahead of admission
        (the seed engine's slots-bounded drain, kept): a deep request
        backlog must not materialize every prompt into memory."""
        engine = make_engine(slots=2)
        rng = np.random.default_rng(9)
        reqs = {
            f"b{i}": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 3)
            for i in range(12)
        }
        completed, _ = serve(engine, reqs)
        assert sorted(completed) == sorted(reqs)
        assert 0 < engine.metrics["max_pending"] <= 2 * len(engine.slots)
        engine.close()

    def test_free_sequence_while_borrowed_is_retryable(self):
        """A rejected free (outstanding borrow) must leave the sequence
        intact — no leaked pages, no wedged retry."""
        from repro.core.ownership import OwnershipError, borrow, release
        from repro.serve.kvcache import PageTable

        store = Store(f"fb-{new_key()}")
        pt = PageTable(num_pages=8, page_size=4, store=store, page_bytes=16)
        pt.allocate("b", 6)
        ref = borrow(pt._owners["b"])
        with pytest.raises(OwnershipError):
            pt.free_sequence("b")
        assert "b" in pt.live_sequences()  # nothing mutated
        assert pt.pages_in_use() == 2
        release(ref)
        pt.free_sequence("b")  # retry succeeds
        assert pt.pages_free() == 8 and not pt.live_sequences()
        store.close()

    def test_close_spares_a_caller_provided_store(self):
        shared = Store(f"shared-{new_key()}")
        shared.put({"keep": 1}, key="other-data")
        ctx = serve_context(CFG)
        engine = ServeEngine(
            ctx, {}, slots=1, max_len=32, page_size=4,
            model=CountingModel(CFG), kv_store=shared,
        )
        engine.close()
        assert shared.get("other-data") == {"keep": 1}  # store untouched
        shared.close()


class TestServeSharding:
    def test_serve_profile_shards_kv_seq_over_model_axis(self):
        """The serve rules profile resolves the cache's kv_seq axis onto
        the model mesh axis (dict-mesh unit form of the production mesh)."""
        from repro.dist.sharding import RULE_PROFILES, logical_to_spec

        serve_rules, _ = RULE_PROFILES["serve"]
        spec = logical_to_spec(
            (4, 64, 2, 8),
            ("batch", "kv_seq", "kv_heads", None),
            serve_rules,
            {"data": 2, "model": 16},
        )
        assert spec[1] == "model"  # kv_seq claims the model axis
        default_rules, _ = RULE_PROFILES["default"]
        dspec = logical_to_spec(
            (4, 64, 2, 8),
            ("batch", "kv_seq", "kv_heads", None),
            default_rules,
            {"data": 2, "model": 16},
        )
        assert dspec[1] != "model"

    def test_engine_context_uses_serve_rules(self):
        ctx = serve_context(CFG)
        assert "serve" in ctx.rules.name
        assert ctx.rules.get("kv_seq") == ("model",)

    def test_engine_applies_cache_shardings(self):
        engine = make_engine(slots=2)
        engine._ensure_cache()
        import jax

        leaves = jax.tree.leaves(engine._cache)
        shard_leaves = jax.tree.leaves(
            engine._cache_shardings,
            is_leaf=lambda x: hasattr(x, "mesh"),
        )
        assert len(leaves) == len(shard_leaves)
        for leaf, sh in zip(leaves, shard_leaves):
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        engine.close()


class TestPageOwnership:
    def test_free_sequence_releases_store_memory(self):
        """Finishing a sequence evicts its per-page KV cells — the store
        holds zero bytes for it afterwards (the ownership claim, now at
        the store level, not just the free-list level)."""
        engine = make_engine(slots=2, max_len=32, page_size=4)
        store = engine.kv_store
        completed, _ = serve(
            engine, {"s": (np.asarray([1, 2, 3, 4, 5], np.int32), 6)}
        )
        assert completed["s"]["tokens"]
        assert engine.pages.pages_in_use() == 0
        for p in range(engine.pages.num_pages):
            assert not store.exists(engine.pages.page_key("s", p))
        assert not store.exists("pages-s")
        engine.close()

    def test_kv_cells_exist_while_sequence_live(self):
        from repro.serve.kvcache import PageTable

        store = Store(f"pt-{new_key()}")
        pt = PageTable(num_pages=8, page_size=4, store=store, page_bytes=64)
        pages = pt.allocate("seq", 6)  # 2 pages
        assert len(pages) == 2
        for p in pages:
            assert store.exists(pt.page_key("seq", p))
            assert len(store.get(pt.page_key("seq", p))) == 64
        pt.extend("seq", 9)  # 3rd page
        assert pt.pages_in_use() == 3
        pt.free_sequence("seq")
        assert pt.pages_free() == 8
        store.close()

    def test_page_bytes_sized_from_model_cache(self):
        engine = make_engine(slots=2, max_len=32, page_size=4)
        # CountingModel cache: 1 float32 per token per (L=1) layer
        assert engine.pages.page_bytes == 4 * 1 * np.dtype(CFG.dtype).itemsize
        engine.close()


class TestLaunchServe:
    """The launch driver end to end, in-process (the PR 5 exit-path
    regression: a blocked client must never deadlock the driver, and every
    page must be back in the pool at exit)."""

    @pytest.fixture(autouse=True)
    def _restore_compile_cache(self):
        """The launcher points JAX's persistent cache at its directory;
        give the rest of this test process its own setting back."""
        from jax.experimental.compilation_cache import compilation_cache as cc

        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()

    @pytest.mark.multiproc(timeout=240)  # watchdog: a wedged driver fails fast
    def test_launch_serve_smoke_exits_clean(self, capsys):
        from repro.launch import serve as launch_serve

        rc = launch_serve.main(
            ["--smoke", "--requests", "5", "--slots", "2", "--max-new", "4",
             "--max-len", "32", "--prompt-len", "6"]
        )
        out = capsys.readouterr().out
        assert rc == 0, out
        # rc==0 already implies it, but pin the exit-path claims explicitly
        assert "pages in use at exit: 0" in out
        assert "5/5 requests" in out
        assert "(smoke)" in out

    @pytest.mark.multiproc(timeout=240)
    def test_launch_serve_smoke_with_kernels(self, capsys):
        """``--use-kernels`` routes prefill and paged decode through the
        kernel ops (their jnp branch on CPU) and still serves 5/5."""
        from repro.launch import serve as launch_serve

        rc = launch_serve.main(
            ["--smoke", "--use-kernels", "--requests", "5", "--slots", "2",
             "--max-new", "4", "--max-len", "32", "--prompt-len", "6"]
        )
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "5/5 requests" in out
        assert "pages in use at exit: 0" in out

    def test_launcher_defaults_to_published_widths(self, monkeypatch):
        """Without ``--smoke`` the launcher builds the published config;
        the run is stopped at model build (CPU time, not correctness)."""
        from repro.configs import get_config
        from repro.launch import serve as launch_serve

        seen = []

        class Stop(Exception):
            pass

        def stop(ctx):
            seen.append(ctx.cfg)
            raise Stop

        monkeypatch.setattr(launch_serve, "build_model", stop)
        with pytest.raises(Stop):
            launch_serve.main(["--requests", "1"])
        assert seen == [get_config("smollm-135m")]


_CACHE_PROBE = (
    "import jax; from repro.launch.compile_cache import use_compile_cache; "
    "print(use_compile_cache(), jax.config.jax_compilation_cache_dir)"
)


def _cache_dir_in_subprocess(env_dir):
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    ).stdout.split()
    assert out[0] == out[1]  # the helper's answer is what JAX was told
    return out[0]


class TestCompileCache:
    def test_env_var_wins(self, tmp_path):
        assert _cache_dir_in_subprocess(str(tmp_path)) == str(tmp_path)

    def test_fixed_checkout_path_across_processes(self):
        """Unset, the cache sits at one fixed path inside the checkout: two
        processes land on the same directory (no pid, time or temp name)."""
        import os

        first = _cache_dir_in_subprocess(None)
        assert first == _cache_dir_in_subprocess(None)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(root, ".jax_cache")


class TestPagedDecode:
    """The paged pool rework: batched prefill admission, prefix sharing
    with copy-on-write, orphaned shared pages, and the dense fallback —
    all bit-identical to the sequential reference."""

    def test_batched_admission_bit_identical(self):
        """A backlog admitted into 4 free slots goes through one admission
        batch (a one-row prefill and an insert per request), and changes
        no tokens."""
        rng = np.random.default_rng(7)
        engine = make_engine(slots=4, max_len=32, page_size=4)
        reqs = {
            f"b{i}": (rng.integers(1, CFG.vocab, 3 + i).astype(np.int32), 6)
            for i in range(4)
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        assert engine.metrics["batched_prefills"] >= 1
        engine.close()

    def test_batched_prefill_off_still_correct(self):
        rng = np.random.default_rng(8)
        engine = make_engine(slots=4, max_len=32, batch_prefill=False)
        reqs = {
            f"s{i}": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 5)
            for i in range(4)
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        assert engine.metrics["batched_prefills"] == 0
        engine.close()

    @pytest.mark.parametrize(
        "slots,spec_k", [(4, 0), (8, 0), (4, 2)], ids=["4", "8", "4-spec"]
    )
    def test_admission_batch_prefills_one_row_per_request(self, slots, spec_k):
        """A backlog of 2 admitted in one batch runs one one-row prefill at
        the prompt's own length per request, target and draft alike: no
        pad rows, no padded positions, and the same tokens."""
        kw = {"spec_k": spec_k, "draft_model": CountingModel(CFG)} if spec_k else {}
        # r0 reserves the whole 6-page pool, so r1 and r2 queue behind it
        # and are admitted together once it finishes
        engine = make_engine(slots=slots, max_len=32, page_size=4,
                             num_pages=6, **kw)
        rng = np.random.default_rng(11)
        reqs = {
            "r0": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 20),
            "r1": (rng.integers(1, CFG.vocab, 3).astype(np.int32), 5),
            "r2": (rng.integers(1, CFG.vocab, 6).astype(np.int32), 6),
        }
        calls = {"_prefill": [], "_draft_prefill": [], "_insert_pages": []}

        def spy(attr, record):
            fn = getattr(engine, attr)

            def wrapped(*args):
                calls[attr].append(record(*args))
                return fn(*args)

            setattr(engine, attr, wrapped)

        spy("_prefill", lambda p, tokens: tokens.shape)
        # the rows of each inserted cache leaf
        spy("_insert_pages",
            lambda pool, caches, ids: {c.shape[1] for c in jax.tree.leaves(caches)})
        if spec_k:
            spy("_draft_prefill", lambda p, tokens: tokens.shape)
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        want = sorted((1, len(prompt)) for prompt, _ in reqs.values())
        assert sorted(calls["_prefill"]) == want
        assert sorted(calls["_draft_prefill"]) == (want if spec_k else [])
        assert calls["_insert_pages"] == [{1}] * (
            2 * len(reqs) if spec_k else len(reqs)
        )
        assert engine.metrics["prefill_tokens"] == sum(
            len(prompt) for prompt, _ in reqs.values()
        )
        assert engine.metrics["batched_prefills"] == 1
        assert engine.metrics["admissions"] == 2
        engine.close()

    def test_prefix_sharing_aliases_full_pages(self):
        """Two prompts sharing a page-aligned prefix: the second borrows
        the first's pages (no duplicate allocation) and still decodes
        bit-identically."""
        common = np.asarray([5, 6, 7, 8], np.int32)  # exactly one page
        p1 = np.concatenate([common, [1, 2, 3]]).astype(np.int32)
        p2 = np.concatenate([common, [9, 9]]).astype(np.int32)
        engine = make_engine(slots=2, max_len=32, page_size=4)
        completed, _ = serve(engine, {"a": (p1, 5), "b": (p2, 5)})
        for rid, (prompt, max_new) in {"a": (p1, 5), "b": (p2, 5)}.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        assert engine.metrics["prefix_shared_pages"] >= 1
        # everything reclaimed: shared refcounts drained to zero
        assert engine.pages.pages_in_use() == 0
        assert engine.pages.pages_free() == engine.pages.num_pages
        engine.close()

    def test_prefix_sharing_cow_on_divergent_boundary_page(self):
        """A prefix that ends mid-page triggers copy-on-write — at
        allocation when the prompt already diverges inside the boundary
        page, at first extend when it diverges later.  Neither changes a
        token of either sequence."""
        # lcp = 6 ends inside page 2 (page_size 4); "c" diverges at
        # allocate, "d" only when its decode extends past the prefix
        p1 = np.asarray([5, 6, 7, 8, 1, 2, 3], np.int32)
        p_div = np.asarray([5, 6, 7, 8, 1, 2, 9, 9], np.int32)
        p_ext = np.asarray([5, 6, 7, 8, 1, 2], np.int32)
        engine = make_engine(slots=3, max_len=32, page_size=4)
        reqs = {"a": (p1, 5), "c": (p_div, 5), "d": (p_ext, 5)}
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        assert engine.metrics["prefix_shared_pages"] >= 2
        assert engine.metrics["cow_page_copies"] >= 2
        assert engine.pages.pages_in_use() == 0
        engine.close()

    def test_parent_finishing_first_orphans_then_reclaims(self):
        """The prefix creator finishes while a borrower still decodes: the
        shared cells outlive their creator (orphaned, not freed) and the
        borrower's tokens are unaffected; the pool and store drain fully
        once the borrower finishes."""
        common = np.asarray([3, 1, 4, 1, 5, 9, 2, 6], np.int32)  # 2 pages
        p_parent = common
        p_child = np.concatenate([common, [7, 7]]).astype(np.int32)
        engine = make_engine(slots=2, max_len=32, page_size=4)
        store = engine.kv_store
        reqs = {"parent": (p_parent, 1), "child": (p_child, 8)}
        completed, _ = serve(engine, reqs)
        assert completed["child"]["tokens"] == reference_decode(
            CFG, p_child, 8, max_len=32
        )
        assert engine.metrics["prefix_shared_pages"] >= 2
        assert engine.pages.pages_in_use() == 0
        assert engine.pages.orphan_pages() == set()
        assert sorted(engine.pages._free) == list(range(engine.pages.num_pages))
        for key in list(getattr(store, "_data", {})) or []:
            assert not str(key).startswith("kvpage-")
        engine.close()

    def test_dense_fallback_bit_identical(self):
        """paged=False keeps the dense (L, B, S, ...) layout end to end."""
        rng = np.random.default_rng(9)
        engine = make_engine(slots=2, max_len=32, paged=False)
        assert engine.paged is False
        reqs = {
            f"d{i}": (rng.integers(1, CFG.vocab, 5).astype(np.int32), 6)
            for i in range(3)
        }
        completed, _ = serve(engine, reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert completed[rid]["tokens"] == reference_decode(
                CFG, prompt, max_new, max_len=32
            ), rid
        engine.close()

    def test_indivisible_page_size_falls_back_to_dense(self):
        engine = make_engine(slots=1, max_len=30, page_size=4)
        assert engine.paged is False
        prompt = np.asarray([1, 2, 3], np.int32)
        completed, _ = serve(engine, {"x": (prompt, 4)})
        assert completed["x"]["tokens"] == reference_decode(
            CFG, prompt, 4, max_len=30
        )
        engine.close()

    def test_pool_cache_is_page_granular(self):
        """The device cache is (L, P+1, page_size, ...) — page pool plus
        one null scratch page — not (L, B, max_len, ...)."""
        engine = make_engine(slots=2, max_len=32, page_size=4)
        engine._ensure_cache()
        leaf = engine._cache["hist"]
        assert leaf.shape[1] == engine._null_page + 1
        assert leaf.shape[2] == engine.pages.page_size
        engine.close()
