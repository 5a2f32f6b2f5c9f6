"""How long admission holds the decode loop: over the gaps between two
decode steps (one ``step`` value) that hold any ``serve.admit.*`` span, the
mean engine time from the first admission span's start to the last one's
end, less any ``serve.idle`` wait of the same step inside it."""
import spans

EVENTS = (spans.ADMIT,)


def read(w):
    stalls, batches = [], []
    for t in w.traces:
        idle = spans.by(spans.spans(t, spans.IDLE), "step")
        for step, events in spans.by(spans.spans(t, spans.ADMIT), "step").items():
            a, b = spans.extent(t, events)
            waited = sum(e.clip(a, b) for e in idle.get(step, []))
            stalls.append(b - a - waited)
            batches.append(len(spans.by(events, "batch")))
    if not stalls:
        return None
    spans.log(f"admission stalls: {len(stalls)} gaps, {sum(batches)} batches, "
              f"max {1e-6 * max(stalls):.3f} ms")
    return 1e-6 * sum(stalls) / len(stalls)
