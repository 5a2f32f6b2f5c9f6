"""The serve engine's and client's profiler spans and their counters.

``ServeEngine.run`` marks each phase of its thread with a leaf
``jax.profiler.TraceAnnotation`` carrying its decode step (and, for
admission, its batch) as stats; ``ServeClient`` marks every delta it
handles with the stream hop.  These tests serve the toy ``CountingModel``
under JAX's profiler on the CPU and read the trace back with
``ProfileData``, the reader the benchmark uses.
"""
from __future__ import annotations

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

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
from repro.serve.client import ServeClient
from repro.serve.engine import ServeEngine, serve_context

CFG = get_smoke_config("smollm-135m")
MAX_LEN = 32
ADMIT = ("serve.admit.allocate", "serve.admit.dispatch", "serve.admit.pull",
         "serve.admit.emit")
DECODE = ("serve.decode.prepare", "serve.decode.dispatch", "serve.decode.pull",
          "serve.decode.emit")
ENGINE = ADMIT + DECODE + ("serve.idle",)
# three requests sent at once to a one-slot engine: the last two queue
REQS = {f"q{i}": (np.arange(1, 4 + i, dtype=np.int32), 4 + i) for i in range(3)}


def _serve(logdir: str | None):
    """Serve ``REQS`` on a one-slot engine that is idle when they arrive;
    with ``logdir``, under JAX's profiler.  Returns (client, engine)."""
    ns = f"sp-{new_key()}"
    producer = StreamProducer(QueuePublisher(ns), {"requests": Store(f"{ns}-req")})
    consumer = StreamConsumer(QueueSubscriber("requests", ns), timeout=30.0)
    responses = StreamProducer(QueuePublisher(ns), {"responses": Store(f"{ns}-resp")})
    client = ServeClient(StreamConsumer(QueueSubscriber("responses", ns), timeout=30.0))
    engine = ServeEngine(serve_context(CFG), {}, model=CountingModel(CFG), slots=1,
                         max_len=MAX_LEN, page_size=4, eos_id=-1)
    if logdir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        server = threading.Thread(target=engine.run, args=(consumer, responses), daemon=True)
        collector = threading.Thread(target=client.collect, daemon=True)
        server.start()
        collector.start()
        deadline = time.monotonic() + 30
        while not engine.metrics["idle_waits"] and time.monotonic() < deadline:
            time.sleep(0.005)
        for rid, (prompt, max_new) in REQS.items():
            producer.send("requests", {"prompt": prompt},
                          metadata={"req_id": rid, "max_new_tokens": max_new})
        producer.flush_topic("requests")
        producer.close_topic("requests")
        server.join(timeout=60)
        collector.join(timeout=60)
        assert not server.is_alive() and not collector.is_alive()
    finally:
        if logdir is not None:
            jax.profiler.stop_trace()
    engine.close()
    return client, engine


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("spans"))
    client, engine = _serve(logdir)
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))[-1]
    lines = [(line.name, [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                           dict(e.stats)) for e in line.events])
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU") for line in plane.lines]
    return client, engine, lines


def _spans(lines, names):
    return [e for _, events in lines for e in events if e[0] in names]


def test_every_span_name_appears(served):
    _, _, lines = served
    seen = {e[0] for e in _spans(lines, ENGINE + ("serve.client.delta",))}
    assert seen == set(ENGINE) | {"serve.client.delta"}


def test_admission_spans_carry_step_and_batch_decode_spans_step(served):
    _, engine, lines = served
    admit = _spans(lines, ADMIT)
    assert all({"step", "batch"} <= set(st) for *_, st in admit)
    assert {st["batch"] for *_, st in admit} == set(range(engine.metrics["admissions"]))
    assert engine.metrics["admissions"] == len(REQS)  # one slot: one request a batch
    decode = _spans(lines, DECODE + ("serve.idle",))
    assert all("step" in st for *_, st in decode)
    steps = {}
    for name, *_, st in _spans(lines, DECODE):
        steps.setdefault(st["step"], []).append(name)
    assert sorted(steps) == list(range(engine.metrics["decode_steps"]))
    assert all(sorted(v) == sorted(DECODE) for v in steps.values())


def test_engine_spans_are_leaves_on_one_thread(served):
    _, _, lines = served
    threads = {name for name, events in lines if any(e[0] in ENGINE for e in events)}
    assert len(threads) == 1
    spans = sorted((a, b) for _, a, b, _ in _spans(lines, ENGINE))
    assert all(b <= a2 for (_, b), (a2, _) in zip(spans, spans[1:]))


def test_one_client_span_per_delta_with_its_hop(served):
    client, _, lines = served
    deltas = _spans(lines, ("serve.client.delta",))
    received = sum(len(r.stream_tokens) for r in client.results.values())
    assert len(deltas) == received == sum(n for _, n in REQS.values())
    assert all(st["hop_us"] >= 0 for *_, st in deltas)


def test_queue_wait_counts_the_requests_behind_a_busy_slot(served):
    _, engine, _ = served
    assert engine.metrics["queue_wait_s"] > 0
    assert engine.metrics["prefills"] == len(REQS)


def test_transcripts_are_identical_with_the_profiler_on_and_off(served):
    traced, _, _ = served
    plain, _ = _serve(None)
    for rid, (prompt, max_new) in REQS.items():
        want = reference_decode(CFG, prompt, max_new, max_len=MAX_LEN)
        assert traced.results[rid].stream_tokens == plain.results[rid].stream_tokens == want
        assert traced.results[rid].result["tokens"] == plain.results[rid].result["tokens"]
