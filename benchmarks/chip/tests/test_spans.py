"""Self-checks of the readers of the serve engine's spans and counters, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

Like ``test_bench.py`` they are not tier-1.  Each reader is checked on a
trace built by hand, where its value is worked out in the comments, and on a
trace recorded here of the toy engine serving through the served path.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

ARCH = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 100}


def _window(traces, **kw):
    import run
    from work import Work

    base = dict(counters=None, arch={**ARCH, "padded_vocab": 100},
                work=Work(run.load_arch("llama")), peak={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, engine={"slots": 4},
        prefilled=[], decoded_ctx=[])
    base.update(kw)
    return run.Window(traces=traces, **base)


def _fake_trace(host, programs, lo=0, hi=100_000_000):
    """A reduced trace built by hand: host spans ``(name, start, end,
    stats)`` and device programs ``(start, end)``, in ns."""
    import trace

    t = trace.Trace.__new__(trace.Trace)
    t.lo, t.hi = lo, hi
    t.host = [trace.Event(n, a, b, n, st) for n, a, b, st in host]
    t.devices = [trace.DeviceTrace([], [trace.Event("p", a, b, "p", {}) for a, b in programs])]
    return t


MS = 1_000_000


def test_span_readers_by_hand():
    import run

    host = [  # the gap before step 0 holds two batches, with an idle wait between
        ("serve.admit.allocate", 0, 1 * MS, {"step": 0, "batch": 0, "wait_us": 500.0}),
        ("serve.admit.dispatch", 1 * MS, 2 * MS, {"step": 0, "batch": 0}),
        ("serve.admit.pull", 2 * MS, 6 * MS, {"step": 0, "batch": 0}),
        ("serve.admit.emit", 6 * MS, 7 * MS, {"step": 0, "batch": 0}),
        ("serve.idle", 7 * MS, 9 * MS, {"step": 0}),
        ("serve.admit.allocate", 9 * MS, 10 * MS, {"step": 0, "batch": 1, "wait_us": 1500.0}),
        ("serve.admit.dispatch", 10 * MS, 11 * MS, {"step": 0, "batch": 1}),
        ("serve.admit.pull", 11 * MS, 13 * MS, {"step": 0, "batch": 1}),
        ("serve.admit.emit", 13 * MS, 14 * MS, {"step": 0, "batch": 1}),
        ("serve.decode.prepare", 14 * MS, 15 * MS, {"step": 0}),
        ("serve.decode.dispatch", 15 * MS, 16 * MS, {"step": 0}),
        ("serve.decode.pull", 16 * MS, 30 * MS, {"step": 0}),
        ("serve.decode.emit", 30 * MS, 32 * MS, {"step": 0}),
        ("serve.decode.prepare", 32 * MS, 33 * MS, {"step": 1}),
        ("serve.decode.dispatch", 33 * MS, 34 * MS, {"step": 1}),
        ("serve.decode.pull", 34 * MS, 50 * MS, {"step": 1}),
        ("serve.decode.emit", 50 * MS, 52 * MS, {"step": 1}),
        ("serve.client.delta", 31 * MS, 31 * MS + 1000, {"hop_us": 100.0}),
        ("serve.client.delta", 51 * MS, 51 * MS + 1000, {"hop_us": 300.0}),
        ("serve.client.delta", 52 * MS, 52 * MS + 1000, {}),  # an older engine's delta
    ]
    # prefills run 1.5-5.5 and 10.5-12.5; decode steps 15.5-29 and 33.5-49
    programs = [(MS + MS // 2, 5 * MS + MS // 2), (10 * MS + MS // 2, 12 * MS + MS // 2),
                (15 * MS + MS // 2, 29 * MS), (33 * MS + MS // 2, 49 * MS)]
    w = _window([_fake_trace(host, programs)], counters={"prefills": 2, "queue_wait_s": 0.002})

    def read(name):
        return run.load_reader(name).read(w)

    assert read("queue_wait_ms.chat") == pytest.approx(1.0)
    # one gap: 0-14 ms less the 2-ms wait
    assert read("admit_stall_ms.chat") == pytest.approx(12.0)
    # batch 0: 7 ms less 4 busy; batch 1: 5 ms less 2 busy
    assert read("admit_idle_ms.chat") == pytest.approx((3.0 + 3.0) / 2)
    # step 0: 18 ms less 13.5 busy; step 1: 20 ms less 15.5 busy
    assert read("decode_idle_ms.chat") == pytest.approx((4.5 + 4.5) / 2)
    assert read("stream_hop_ms.chat") == pytest.approx(0.2)


def test_span_readers_cut_spans_at_the_window():
    import run

    host = [("serve.decode.prepare", 0, 10 * MS, {"step": 0}),
            ("serve.decode.pull", 10 * MS, 30 * MS, {"step": 0})]
    w = _window([_fake_trace(host, [(5 * MS, 25 * MS)], lo=10 * MS)])
    assert run.load_reader("decode_idle_ms.chat").read(w) == pytest.approx(5.0)
    assert run.load_reader("admit_stall_ms.chat").read(w) is None


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    """A CPU trace of the toy engine serving three requests on one slot
    through the served path, and the engine's counters over its window."""
    import threading

    import trace
    from repro.configs import get_smoke_config
    from repro.core.connectors import new_key
    from repro.core.store import Store
    from repro.core.streaming import QueuePublisher, QueueSubscriber, StreamConsumer, StreamProducer
    from repro.serve.client import ServeClient
    from repro.serve.engine import ServeEngine, serve_context
    from repro.serve.toy import CountingModel

    cfg = get_smoke_config("smollm-135m")
    ns = f"bt-{new_key()}"
    producer = StreamProducer(QueuePublisher(ns), {"requests": Store(f"{ns}-req")})
    responses = StreamProducer(QueuePublisher(ns), {"responses": Store(f"{ns}-resp")})
    done = threading.Semaphore(0)
    client = ServeClient(StreamConsumer(QueueSubscriber("responses", ns), timeout=60),
                         on_done=lambda *_: done.release())
    engine = ServeEngine(serve_context(cfg), {}, model=CountingModel(cfg), slots=1, max_len=32,
                         page_size=4, eos_id=-1)
    threads = [threading.Thread(target=engine.run, daemon=True, args=(
                   StreamConsumer(QueueSubscriber("requests", ns), timeout=60), responses)),
               threading.Thread(target=client.collect, daemon=True)]
    for th in threads:
        th.start()

    def serve(rids):
        for i, rid in enumerate(rids):
            producer.send("requests", {"prompt": list(range(1, 4 + i))},
                          metadata={"req_id": rid, "max_new_tokens": 6})
        producer.flush_topic("requests")
        for _ in rids:
            assert done.acquire(timeout=60)

    serve(["warm0", "warm1", "warm2"])  # every shape compiled before the window
    d = str(tmp_path_factory.mktemp("serve-trace"))
    c0 = dict(engine.metrics)
    with trace.capture(d):
        serve(["r0", "r1", "r2"])
    counters = {k: v - c0[k] for k, v in engine.metrics.items()}
    producer.close_topic("requests")
    for th in threads:
        th.join(timeout=60)
        assert not th.is_alive()
    engine.close()
    return trace.Trace.load(d), counters


@pytest.mark.parametrize("metric", ["queue_wait_ms.chat", "admit_stall_ms.chat",
                                    "admit_idle_ms.chat", "decode_idle_ms.chat",
                                    "stream_hop_ms.chat"])
def test_span_readers_on_a_served_cpu_trace(metric, serve_trace):
    import run

    t, counters = serve_trace
    assert counters["prefills"] == 3 and counters["admissions"] == 3
    v = run.load_reader(metric).read(_window([t], counters=counters))
    assert v is not None and 0 <= v < 1e3 * t.window_s
    if metric in ("queue_wait_ms.chat", "admit_stall_ms.chat", "stream_hop_ms.chat"):
        assert v > 0


def test_breakdown_names_the_longest_idle_gap_by_an_engine_phase(serve_trace):
    t, _ = serve_trace
    assert t.breakdown()["idle_gaps"][0][0].startswith("serve.")

