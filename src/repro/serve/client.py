"""Streaming serve client: assembles token deltas back into completions.

The response topic carries two event kinds (see ServeEngine.run):

- ``kind="delta"`` — metadata-only (``StreamProducer.send_meta``): one
  generated token per decode step.  No store payload; the broker event is
  the whole message, so first-token latency is one decode step + one event
  hop, not a full generation.  ``sent_at``, where present, is the engine's
  ``time.perf_counter()`` at the send: comparable across processes on one
  host only.
- ``kind="done"``  — the completion record (tokens, latency, ttft) as bulk
  via proxy; resolving it is the only store round-trip per request.
- ``kind="error"`` — admission rejection (metadata-only).

:class:`ServeClient` consumes the topic with ``next_with_metadata`` and
keeps per-request assembly state; it is the measurement point for the
streamed-vs-complete latency claims (BENCH_serve's ``ttft_speedup``).  It
marks the handling of each delta with a ``serve.client.delta``
``jax.profiler.TraceAnnotation`` whose ``hop_us`` stat is the stream hop:
receipt minus ``sent_at`` (absent where the delta carries no ``sent_at``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from repro.core.proxy import extract
from repro.core.streaming import StreamConsumer


@dataclass
class StreamedResult:
    req_id: str
    stream_tokens: list[int] = field(default_factory=list)
    first_delta_at: float | None = None  # perf_counter of first token delta
    done_at: float | None = None
    result: dict | None = None  # resolved completion bulk
    error: str | None = None

    @property
    def done(self) -> bool:
        return self.done_at is not None or self.error is not None


class ServeClient:
    """Client-side assembler for a serve response topic.

    ``collect(n)`` iterates the topic until ``n`` requests have completed
    (or the topic closes), recording per-request delta order and timing.
    ``on_done(req_id, result)`` fires as completions land — backpressure
    hooks (the launch driver's admission window) attach here.
    """

    def __init__(self, consumer: StreamConsumer, *, on_done=None, on_delta=None):
        self.consumer = consumer
        self.on_done = on_done
        self.on_delta = on_delta
        self.results: dict[str, StreamedResult] = {}
        self.out_of_order: list[tuple[str, int, int]] = []  # (req, got, want)
        self.rejections: list[tuple[str, str]] = []  # duplicate/late errors
        self.ignored_events: list[dict] = []  # unknown kinds, heartbeats
        self.closed = False

    def _rec(self, req_id: str) -> StreamedResult:
        rec = self.results.get(req_id)
        if rec is None:
            rec = self.results[req_id] = StreamedResult(req_id)
        return rec

    def _handle(self, proxy, meta) -> StreamedResult | None:
        """Apply one event; returns the record when it just completed.

        Unknown event kinds (a future heartbeat, someone else's send_meta)
        are counted and ignored, never fatal; an ``error`` for a req_id
        that is already streaming or done is a *rejected duplicate* — it
        lands in ``rejections`` and must not clobber the live record.
        """
        kind = meta.get("kind")
        req_id = meta.get("req_id")
        if (
            req_id is None
            or kind not in ("delta", "error", "done")
            or (kind == "done" and proxy is None)  # done must carry bulk
        ):
            self.ignored_events.append(dict(meta))
            return None
        rec = self._rec(req_id)
        if kind == "delta":
            now = time.perf_counter()
            sent_at = meta.get("sent_at")
            hop = {} if sent_at is None else {"hop_us": 1e6 * (now - sent_at)}
            with TraceAnnotation("serve.client.delta", **hop):
                if rec.first_delta_at is None:
                    rec.first_delta_at = now
                if meta["index"] != len(rec.stream_tokens):
                    self.out_of_order.append(
                        (rec.req_id, meta["index"], len(rec.stream_tokens))
                    )
                rec.stream_tokens.append(meta["token"])
                if self.on_delta is not None:
                    self.on_delta(rec.req_id, meta["token"], meta["index"])
            return None
        if rec.done:  # duplicate error/done for a finished record
            self.rejections.append((req_id, meta.get("error", kind)))
            return None
        if kind == "error":
            if rec.stream_tokens:  # the live request streams on; the
                # rejected duplicate is the one being refused
                self.rejections.append((req_id, meta.get("error", "rejected")))
                return None
            rec.error = meta.get("error", "rejected")
        else:  # "done": the one bulk resolve per request
            rec.result = extract(proxy)
            rec.done_at = time.perf_counter()
        if self.on_done is not None:
            self.on_done(rec.req_id, rec)
        return rec

    def collect(
        self,
        n: int | None = None,
        *,
        timeout: float | None = None,
        deadline: float | None = None,
    ) -> dict[str, StreamedResult]:
        """Consume events until ``n`` completions (or the topic closes when
        ``n`` is None).  ``timeout`` bounds each event wait; ``deadline``
        bounds the whole call — against a dead engine that never publishes
        again, the client surfaces ``TimeoutError`` naming the incomplete
        req_ids instead of blocking forever in the consumer wait."""
        deadline_t = None if deadline is None else time.monotonic() + deadline
        done = sum(1 for r in self.results.values() if r.done)
        while n is None or done < n:
            wait = timeout
            if deadline_t is not None:
                remaining = deadline_t - time.monotonic()
                wait = remaining if wait is None else min(wait, remaining)
                wait = max(wait, 0.0)
            try:
                if wait is None:
                    proxy, meta = self.consumer.next_with_metadata()
                else:
                    proxy, meta = self.consumer.next_with_metadata(timeout=wait)
            except TimeoutError:
                if deadline_t is not None and time.monotonic() >= deadline_t:
                    incomplete = sorted(
                        r for r, rec in self.results.items() if not rec.done
                    )
                    raise TimeoutError(
                        f"serve client deadline ({deadline:g}s) expired; "
                        f"incomplete req_ids: {incomplete}"
                    ) from None
                raise  # caller's per-event timeout contract, unchanged
            except StopIteration:
                self.closed = True
                break
            if self._handle(proxy, meta) is not None:
                done += 1
        return self.results

    # -- derived metrics -----------------------------------------------------
    def ttft_s(self, sent_at: dict[str, float]) -> dict[str, float]:
        """Per-request time-to-first-token against caller-recorded send
        times (same-process ``perf_counter`` values)."""
        return {
            r: rec.first_delta_at - sent_at[r]
            for r, rec in self.results.items()
            if rec.first_delta_at is not None and r in sent_at
        }

    def completion_s(self, sent_at: dict[str, float]) -> dict[str, float]:
        return {
            r: rec.done_at - sent_at[r]
            for r, rec in self.results.items()
            if rec.done_at is not None and r in sent_at
        }
