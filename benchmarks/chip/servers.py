"""The system under test, behind the entry points its users call.

``EngineServer``: one ``ServeEngine`` in this process, fed over the request
stream and read back over the response stream by a ``ServeClient``
(``core/streaming``'s in-process queue broker), on one chip.  It calls
``on_delta(req_id, token, index)`` for every token the client receives and
``on_done(req_id, record)`` for every completion or error, in the client's
thread.
"""
from __future__ import annotations

import threading
import time

CLIENT_DEADLINE_S = 3000.0


class EngineServer:
    def __init__(self, cfg, engine: dict, params, *, on_delta, on_done):
        from repro.core.connectors import new_key
        from repro.core.store import Store
        from repro.core.streaming import (
            QueuePublisher, QueueSubscriber, StreamConsumer, StreamProducer,
        )
        from repro.serve.client import ServeClient
        from repro.serve.engine import ServeEngine, serve_context

        ctx = serve_context(cfg, use_kernels=engine["use_kernels"],
                            page_size=engine["page_size"])
        self.engine = ServeEngine(
            ctx, params, slots=engine["slots"], max_len=engine["max_len"],
            page_size=engine["page_size"], eos_id=engine["eos_id"],
        )
        ns = f"bench-{new_key()}"
        self._producer = StreamProducer(
            QueuePublisher(ns), {"requests": Store(f"{ns}-req")}
        )
        consumer = StreamConsumer(QueueSubscriber("requests", ns), timeout=CLIENT_DEADLINE_S)
        responses = StreamProducer(QueuePublisher(ns), {"responses": Store(f"{ns}-resp")})
        self.client = ServeClient(
            StreamConsumer(QueueSubscriber("responses", ns), timeout=CLIENT_DEADLINE_S),
            on_delta=on_delta, on_done=on_done,
        )
        self._errors: list[BaseException] = []
        self._threads = [
            threading.Thread(target=self._guard(self.engine.run, consumer, responses),
                             name="engine", daemon=True),
            threading.Thread(target=self._guard(self.client.collect, deadline=CLIENT_DEADLINE_S),
                             name="client", daemon=True),
        ]
        for t in self._threads:
            t.start()

    def _guard(self, fn, *args, **kw):
        def run():
            try:
                fn(*args, **kw)
            except BaseException as e:  # noqa: BLE001 - re-raised by join()
                self._errors.append(e)
        return run

    def send(self, req) -> None:
        self._producer.send(
            "requests", {"prompt": req.prompt},
            metadata={"req_id": req.rid, "max_new_tokens": req.max_new},
        )
        self._producer.flush_topic("requests")

    def close_intake(self) -> None:
        self._producer.close_topic("requests")

    def join(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.monotonic()))
        if self._errors:
            raise self._errors[0]
        if any(t.is_alive() for t in self._threads):
            raise TimeoutError(f"the engine did not drain within {timeout:g} s")

    def counters(self) -> dict:
        return dict(self.engine.metrics)

    def pages_in_use(self) -> int:
        return self.engine.pages.pages_in_use()

    def stop(self) -> None:
        self.engine.close()
        self.engine.params = self.engine._cache = None
        self.engine = None

