"""Token-delta streaming contract (PR 5): ordered deltas, first-token-
before-completion, clean topic close, and a cross-process FileConnector
client that survives an engine restart (mirrors test_stream_fastpath's
subprocess pattern, under the multiproc watchdog).
"""
from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from _serve_toy import CountingModel, reference_decode
from repro.configs import get_smoke_config
from repro.core import FileConnector, Store
from repro.core.connectors import new_key
from repro.core.streaming import (
    FileLogPublisher,
    FileLogSubscriber,
    QueuePublisher,
    QueueSubscriber,
    StreamConsumer,
    StreamProducer,
)
from repro.serve.client import ServeClient
from repro.serve.engine import ServeEngine, serve_context

CFG = get_smoke_config("smollm-135m")


def make_engine(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("eos_id", -1)
    return ServeEngine(serve_context(CFG), {}, model=CountingModel(CFG), **kw)


def queue_streams():
    ns = f"ss-{new_key()}"
    return {
        "producer": StreamProducer(
            QueuePublisher(ns), {"requests": Store(f"{ns}-req")}
        ),
        "consumer": StreamConsumer(QueueSubscriber("requests", ns), timeout=30.0),
        "resp_producer": StreamProducer(
            QueuePublisher(ns), {"responses": Store(f"{ns}-resp")}
        ),
        "resp_consumer": StreamConsumer(
            QueueSubscriber("responses", ns), timeout=30.0
        ),
    }


def send(producer, req_id, prompt, max_new):
    producer.send(
        "requests",
        {"prompt": np.asarray(prompt, np.int32)},
        metadata={"req_id": req_id, "max_new_tokens": max_new},
    )
    producer.flush_topic("requests")


class TestDeltaContract:
    def _serve_collect(self, reqs, **run_kw):
        s = queue_streams()
        sent_at = {}
        for rid, (p, mn) in reqs.items():
            sent_at[rid] = time.perf_counter()
            send(s["producer"], rid, p, mn)
        s["producer"].close_topic("requests")
        engine = make_engine()
        client = ServeClient(s["resp_consumer"])
        collector = threading.Thread(target=client.collect, daemon=True)
        collector.start()
        engine.run(s["consumer"], s["resp_producer"], **run_kw)
        collector.join(timeout=30)
        assert not collector.is_alive()
        engine.close()
        return client, sent_at

    def test_deltas_arrive_in_order_and_match_final(self):
        rng = np.random.default_rng(0)
        reqs = {
            f"d{i}": (rng.integers(1, CFG.vocab, 5).astype(np.int32), 6)
            for i in range(4)
        }
        client, _ = self._serve_collect(reqs)
        assert not client.out_of_order
        for rid, (prompt, max_new) in reqs.items():
            rec = client.results[rid]
            ref = reference_decode(CFG, prompt, max_new, max_len=32)
            assert rec.stream_tokens == ref  # every delta, in order
            assert rec.result["tokens"] == ref  # bulk completion agrees

    def test_first_token_precedes_completion(self):
        """Streamed TTFT beats full-completion latency for multi-token
        requests — the whole point of delta streaming."""
        prompt = np.asarray(range(1, 7), np.int32)
        client, sent_at = self._serve_collect({"t": (prompt, 12)})
        rec = client.results["t"]
        assert rec.first_delta_at < rec.done_at
        ttft = client.ttft_s(sent_at)["t"]
        total = client.completion_s(sent_at)["t"]
        assert ttft < total
        # engine-side bookkeeping agrees
        assert rec.result["ttft"] < rec.result["latency"]

    def test_single_token_request_still_streams_a_delta(self):
        prompt = np.asarray([2, 3], np.int32)
        client, _ = self._serve_collect({"one": (prompt, 1)})
        rec = client.results["one"]
        assert len(rec.stream_tokens) == 1
        assert rec.stream_tokens == rec.result["tokens"]

    def test_batched_admission_streams_per_request_deltas(self):
        """A backlog admitted in one batched prefill still streams every
        request's deltas in order and bit-identical to the reference (the
        batching is a device-side detail, invisible on the wire)."""
        rng = np.random.default_rng(5)
        s = queue_streams()
        reqs = {
            f"m{i}": (rng.integers(1, CFG.vocab, 4 + i).astype(np.int32), 5)
            for i in range(4)
        }
        for rid, (p, mn) in reqs.items():
            send(s["producer"], rid, p, mn)
        s["producer"].close_topic("requests")
        engine = make_engine(slots=4)
        client = ServeClient(s["resp_consumer"])
        collector = threading.Thread(target=client.collect, daemon=True)
        collector.start()
        engine.run(s["consumer"], s["resp_producer"])
        collector.join(timeout=30)
        assert not collector.is_alive()
        assert engine.metrics["batched_prefills"] >= 1
        assert not client.out_of_order
        for rid, (prompt, max_new) in reqs.items():
            ref = reference_decode(CFG, prompt, max_new, max_len=32)
            rec = client.results[rid]
            assert rec.stream_tokens == ref, rid
            assert rec.result["tokens"] == ref, rid
        engine.close()

    def test_topic_closes_cleanly(self):
        prompt = np.asarray([1, 2, 3], np.int32)
        client, _ = self._serve_collect({"c": (prompt, 3)})
        assert client.closed  # StopIteration, not a timeout
        with pytest.raises(StopIteration):
            client.consumer.next_with_metadata(timeout=0.1)

    def test_close_responses_false_keeps_topic_open(self):
        """An engine 'restart' mid-topic: run #1 leaves the response topic
        open; run #2 on the same topics finishes and closes it."""
        s = queue_streams()
        rng = np.random.default_rng(1)
        reqs = {
            f"r{i}": (rng.integers(1, CFG.vocab, 4).astype(np.int32), 3)
            for i in range(4)
        }
        for rid, (p, mn) in reqs.items():
            send(s["producer"], rid, p, mn)
        s["producer"].close_topic("requests")
        client = ServeClient(s["resp_consumer"])
        collector = threading.Thread(target=client.collect, daemon=True)
        collector.start()

        engine1 = make_engine()
        engine1.run(
            s["consumer"], s["resp_producer"],
            max_requests=2, close_responses=False,
        )
        assert len(engine1.completed) == 2
        # the stream outlives engine1: completion bulks the collector has
        # not resolved yet must survive its close (handoff form)
        engine1.close(reclaim_responses=False)
        assert not client.closed  # topic still open across the restart

        engine2 = make_engine()
        engine2.run(s["consumer"], s["resp_producer"])
        collector.join(timeout=30)
        assert not collector.is_alive()
        assert client.closed
        served = set(engine1.completed) | set(engine2.completed)
        assert served == set(reqs)
        for rid, (prompt, max_new) in reqs.items():
            assert client.results[rid].stream_tokens == reference_decode(
                CFG, prompt, max_new, max_len=32
            )
        engine2.close()


class TestMetaOnlyEvents:
    """The core streaming primitives the delta protocol rides on."""

    def _pair(self, **consumer_kw):
        ns = f"mo-{new_key()}"
        producer = StreamProducer(QueuePublisher(ns), {"t": Store(f"{ns}-s")})
        consumer = StreamConsumer(QueueSubscriber("t", ns), **consumer_kw)
        return producer, consumer

    def test_send_meta_roundtrip_and_ordering(self):
        from repro.core.proxy import extract

        producer, consumer = self._pair(timeout=5)
        producer.send("t", {"big": 1}, metadata={"kind": "bulk"})
        # send_meta flushes buffered sends first: order == call order
        producer.send_meta("t", {"kind": "delta", "i": 0})
        producer.send_meta("t", {"kind": "delta", "i": 1})
        proxy, meta = consumer.next_with_metadata()
        assert proxy is not None and meta["kind"] == "bulk"
        assert extract(proxy) == {"big": 1}  # consume (one-shot: evicts)
        for i in range(2):
            proxy, meta = consumer.next_with_metadata()
            assert proxy is None  # metadata-only: nothing to resolve
            assert meta == {"kind": "delta", "i": i}

    def test_plain_iteration_skips_meta_only(self):
        from repro.core.proxy import extract

        producer, consumer = self._pair(timeout=5)
        producer.send_meta("t", {"kind": "delta"})
        producer.send("t", "payload")
        producer.flush_topic("t")
        producer.send_meta("t", {"kind": "delta"})
        producer.close_topic("t")
        got = [extract(p) for p in consumer]
        assert got == ["payload"]

    def test_prefetch_consumer_passes_meta_only_through(self):
        producer, consumer = self._pair(timeout=5, prefetch=2)
        producer.send_meta("t", {"kind": "delta", "i": 0})
        producer.send("t", "bulk0")
        producer.flush_topic("t")
        producer.close_topic("t")
        proxy, meta = consumer.next_with_metadata()
        assert proxy is None and meta["i"] == 0
        proxy, meta = consumer.next_with_metadata()
        assert proxy is not None
        with pytest.raises(StopIteration):
            consumer.next_with_metadata()

    def test_per_call_timeout_overrides_constructor(self):
        _, consumer = self._pair(timeout=60)
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            consumer.next_with_metadata(timeout=0.05)
        assert time.perf_counter() - t0 < 5  # not the constructor's 60 s

    def test_client_ignores_unknown_event_kinds(self):
        """Heartbeats / future kinds on the response topic must not kill
        the collector (extract(None) on the 'done' path, KeyErrors)."""
        ns = f"mo-{new_key()}"
        producer = StreamProducer(QueuePublisher(ns), {"r": Store(f"{ns}-s")})
        consumer = StreamConsumer(QueueSubscriber("r", ns), timeout=5)
        client = ServeClient(consumer)
        producer.send_meta("r", {"kind": "heartbeat"})  # no req_id
        producer.send_meta("r", {"req_id": "x", "kind": "progress"})
        producer.send_meta("r", {"req_id": "x", "kind": "done"})  # no bulk
        producer.send_meta(
            "r", {"req_id": "x", "kind": "delta", "token": 7, "index": 0}
        )
        producer.close_topic("r")
        client.collect()
        assert len(client.ignored_events) == 3
        assert client.results["x"].stream_tokens == [7]

    def test_client_delta_without_sent_at_reaches_on_delta(self):
        """A delta from an older engine (or built by hand) carries no
        ``sent_at``: the client handles it as before, with no stream hop."""
        ns = f"mo-{new_key()}"
        producer = StreamProducer(QueuePublisher(ns), {"r": Store(f"{ns}-s")})
        consumer = StreamConsumer(QueueSubscriber("r", ns), timeout=5)
        got = []
        client = ServeClient(consumer, on_delta=lambda *a: got.append(a))
        producer.send_meta(
            "r", {"req_id": "y", "kind": "delta", "token": 3, "index": 0}
        )
        producer.send_meta(
            "r", {"req_id": "y", "kind": "delta", "token": 4, "index": 1,
                  "sent_at": time.perf_counter()}
        )
        producer.close_topic("r")
        client.collect()
        assert got == [("y", 3, 0), ("y", 4, 1)]
        assert client.results["y"].stream_tokens == [3, 4]
        assert not client.ignored_events and not client.out_of_order

    def test_client_duplicate_rejection_spares_live_record(self):
        """An engine 'error' for a req_id that is already streaming is the
        duplicate being refused — the live record keeps collecting and
        completes exactly once."""
        ns = f"mo-{new_key()}"
        store = Store(f"{ns}-s")
        producer = StreamProducer(QueuePublisher(ns), {"r": store})
        consumer = StreamConsumer(QueueSubscriber("r", ns), timeout=5)
        done_calls = []
        client = ServeClient(consumer, on_done=lambda r, rec: done_calls.append(r))
        producer.send_meta(
            "r", {"req_id": "d", "kind": "delta", "token": 1, "index": 0}
        )
        producer.send_meta(  # the engine refusing a duplicate 'd'
            "r", {"req_id": "d", "kind": "error", "error": "already serving"}
        )
        producer.send_meta(
            "r", {"req_id": "d", "kind": "delta", "token": 2, "index": 1}
        )
        producer.send(
            "r", {"req_id": "d", "tokens": [1, 2]},
            metadata={"req_id": "d", "kind": "done"},
        )
        producer.flush_topic("r")
        producer.send_meta(  # late duplicate after completion
            "r", {"req_id": "d", "kind": "error", "error": "already serving"}
        )
        producer.close_topic("r")
        client.collect()
        rec = client.results["d"]
        assert rec.error is None and rec.stream_tokens == [1, 2]
        assert rec.result["tokens"] == [1, 2]
        assert done_calls == ["d"]  # exactly one completion callback
        assert len(client.rejections) == 2

    def test_meta_events_respect_filter(self):
        ns = f"mo-{new_key()}"
        producer = StreamProducer(QueuePublisher(ns), {"t": Store(f"{ns}-s")})
        consumer = StreamConsumer(
            QueueSubscriber("t", ns),
            timeout=5,
            filter_=lambda m: m.get("keep", False),
        )
        producer.send_meta("t", {"keep": False, "i": 0})
        producer.send_meta("t", {"keep": True, "i": 1})
        _, meta = consumer.next_with_metadata()
        assert meta["i"] == 1


# ---------------------------------------------------------------------------
# Cross-process client over FileConnector + FileLog, surviving a restart
# ---------------------------------------------------------------------------

_XP_CLIENT = """
import json, sys
sys.path.insert(0, sys.argv[4])  # tests dir, for _serve_toy
import numpy as np
from _serve_toy import reference_decode
from repro.configs import get_smoke_config
from repro.core import FileConnector, Store
from repro.core.streaming import (
    FileLogPublisher, FileLogSubscriber, StreamConsumer, StreamProducer,
)
from repro.serve.client import ServeClient

chdir, logdir, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
cfg = get_smoke_config("smollm-135m")
store = Store("xp-serve-req", FileConnector(chdir))
producer = StreamProducer(FileLogPublisher(logdir), {"requests": store})
rng = np.random.default_rng(42)
prompts = {}
for i in range(n):
    rid = f"x{i}"
    prompts[rid] = rng.integers(1, cfg.vocab, 5).astype(np.int32)
    producer.send(
        "requests",
        {"prompt": prompts[rid]},
        metadata={"req_id": rid, "max_new_tokens": 4},
    )
    producer.flush_topic("requests")
producer.close_topic("requests")

client = ServeClient(
    StreamConsumer(FileLogSubscriber("responses", logdir), timeout=60.0)
)
client.collect()  # until the (restarted) engine closes the topic
ok = True
for rid, prompt in prompts.items():
    ref = reference_decode(cfg, prompt, 4, max_len=32)
    rec = client.results.get(rid)
    if rec is None or rec.stream_tokens != ref or rec.result["tokens"] != ref:
        ok = False
print(json.dumps({
    "ok": ok and client.closed and not client.out_of_order,
    "n_results": len(client.results),
    "deltas": {r: rec.stream_tokens for r, rec in client.results.items()},
}))
"""


class TestCrossProcessClient:
    @pytest.mark.multiproc(timeout=120)
    def test_fileconnector_client_survives_engine_restart(self, tmp_path):
        """A client in another process sends requests and consumes the
        delta/completion stream over FileConnector+FileLog; the engine is
        torn down after 2 of 4 requests and a fresh engine (resuming the
        request topic from the pickled subscriber offset) serves the rest.
        The client sees one continuous, ordered, complete stream."""
        chdir, logdir = str(tmp_path / "ch"), str(tmp_path / "log")
        n = 4
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
        tests_dir = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-c", _XP_CLIENT, chdir, logdir, str(n), tests_dir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        try:
            resp_store = Store("xp-serve-resp", FileConnector(chdir))

            def resp_producer():
                return StreamProducer(
                    FileLogPublisher(logdir), {"responses": resp_store}
                )

            sub1 = FileLogSubscriber("requests", logdir)
            consumer1 = StreamConsumer(sub1, timeout=60.0)
            engine1 = make_engine()
            engine1.run(
                consumer1, resp_producer(),
                max_requests=2, close_responses=False,
            )
            assert len(engine1.completed) == 2
            # handoff form: the external client is still consuming — a
            # reclaiming close would evict completion bulks it has not
            # resolved yet and wedge its blocking resolves
            engine1.close(reclaim_responses=False)

            # restart: a new engine resumes the request topic exactly after
            # the last consumed event (the subscriber pickle carries the
            # consumption offset — PR 3 contract)
            sub2 = pickle.loads(pickle.dumps(sub1))
            consumer2 = StreamConsumer(sub2, timeout=60.0)
            engine2 = make_engine()
            engine2.run(consumer2, resp_producer())
            assert len(engine2.completed) == 2
            engine2.close(reclaim_responses=False)

            out, err = proc.communicate(timeout=90)
        except BaseException:
            proc.kill()
            raise
        assert proc.returncode == 0, err.decode()
        report = json.loads(out.decode().strip().splitlines()[-1])
        assert report["ok"], report
        assert report["n_results"] == n


class TestOrphanReclaimFailure:
    def test_reclaim_failure_counted_and_surfaced_by_proxysan(self):
        """Satellite: the engine's best-effort reclaim of an unaddressable
        request's bulk (no ``req_id`` — nobody will ever pull it again)
        used to swallow eviction failures silently.  A failed reclaim must
        now land in ``metrics['reclaim_failures']`` AND hand the orphan to
        ProxySan so the resident payload shows up in the leak report."""
        from repro.core import sanitize as _sanitize

        ns = f"rf-{new_key()}"
        store = Store(f"{ns}-req", sanitize=True)
        producer = StreamProducer(QueuePublisher(ns), {"requests": store})
        consumer = StreamConsumer(QueueSubscriber("requests", ns), timeout=10.0)
        resp_producer = StreamProducer(
            QueuePublisher(ns), {"responses": Store(f"{ns}-resp")}
        )
        # unaddressable: no req_id in the metadata
        producer.send("requests", {"prompt": np.arange(1, 5, dtype=np.int32)},
                      metadata={"note": "no req_id"})
        producer.flush_topic("requests")
        producer.close_topic("requests")

        evict_attempts = []

        def failing_evict(key):
            evict_attempts.append(key)
            raise RuntimeError("injected channel failure")

        orig_evict = store.connector.evict
        store.connector.evict = failing_evict
        engine = make_engine()
        try:
            engine.run(consumer, resp_producer)
            assert engine.metrics["malformed_events"] == 1
            assert engine.metrics["reclaim_failures"] == 1
            assert len(evict_attempts) == 1
            san = _sanitize.active_for(store.name)
            assert san is not None
            leaked = san.leak_report(store=store.name, kinds=("object",))
            assert any(l["key"] == evict_attempts[0] for l in leaked), leaked
        finally:
            store.connector.evict = orig_evict
            # reclaim for real so the orphan does not outlive the test
            store.connector.evict(evict_attempts[0])
            engine.close()

    def test_reclaim_success_keeps_failure_count_zero(self):
        """Control: a healthy channel reclaims the orphan; no failure is
        counted and nothing is handed to ProxySan."""
        s = queue_streams()
        s["producer"].send(
            "requests", {"prompt": np.arange(1, 5, dtype=np.int32)}, metadata={}
        )
        s["producer"].flush_topic("requests")
        s["producer"].close_topic("requests")
        engine = make_engine()
        engine.run(s["consumer"], s["resp_producer"])
        assert engine.metrics["malformed_events"] == 1
        assert engine.metrics["reclaim_failures"] == 0
        engine.close()
