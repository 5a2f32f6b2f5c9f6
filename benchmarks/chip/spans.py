"""The program's own spans in a traced window, grouped for the readers.

The serve engine marks each phase of its thread with a leaf host span:
``serve.admit.*`` (allocate, dispatch, pull, emit), ``serve.decode.*``
(prepare, dispatch, pull, emit) and ``serve.idle``.  No span encloses
another; each names its parent by a stat instead: ``step``, the decode steps
run when it opened (admission spans of one ``step`` fall into the gap before
that decode step), and on admission spans ``batch``, the admission batches
run.  The client marks each delta it handles with ``serve.client.delta``,
whose ``hop_us`` is the stream hop from the engine's send.

Spans and device programs share the trace's clock, so the device's idle time
inside a span is the span less the programs that ran in it.  A span that
crosses an edge of the window counts only its part inside.
"""
from __future__ import annotations

import sys

ADMIT = "serve.admit."
DECODE = "serve.decode."
IDLE = "serve.idle"
DELTA = "serve.client.delta"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def spans(t, prefix: str) -> list:
    """Host spans of trace ``t`` named ``prefix...`` that overlap its
    window, in order of start."""
    return sorted((e for e in t.host if e.name.startswith(prefix)
                   and e.end > t.lo and e.start < t.hi), key=lambda e: e.start)


def by(events, stat: str) -> dict:
    """The events that carry ``stat``, grouped by its value."""
    out: dict = {}
    for e in events:
        if stat in e.stats:
            out.setdefault(e.stats[stat], []).append(e)
    return out


def extent(t, events) -> tuple[int, int]:
    """First start to last end of ``events``, cut to the window."""
    return max(t.lo, min(e.start for e in events)), min(t.hi, max(e.end for e in events))


def busy(t) -> list[tuple[int, int]]:
    """The merged intervals in which a program ran on the first device: the
    union that ``device_idle`` is read from."""
    d = t.devices[0]
    merged: list[list[int]] = []
    for a, b in sorted((e.start, e.end) for e in (d.modules or d.ops)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def idle_ns(busy_, a: int, b: int) -> int:
    """Nanoseconds of ``[a, b)`` in which no program ran."""
    return (b - a) - sum(max(0, min(y, b) - max(x, a)) for x, y in busy_)
