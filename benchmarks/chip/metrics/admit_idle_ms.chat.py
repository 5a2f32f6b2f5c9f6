"""Device idle time per admission batch: for each ``batch`` value, the time
inside the extent of its ``serve.admit.*`` spans (first start to last end) in
which no program ran on the device, mean over batches.  Logs the idle time
inside each admission phase, summed over the window."""
import spans

EVENTS = (spans.ADMIT,)


def read(w):
    idle, phases = [], {}
    for t in w.traces:
        busy = spans.busy(t)
        events = spans.spans(t, spans.ADMIT)
        for batch in spans.by(events, "batch").values():
            idle.append(spans.idle_ns(busy, *spans.extent(t, batch)))
        for e in events:
            a, b = max(e.start, t.lo), min(e.end, t.hi)
            phases[e.name] = phases.get(e.name, 0) + spans.idle_ns(busy, a, b)
    if not idle:
        return None
    spans.log(f"device idle in {len(idle)} admission batches, by phase: " + "; ".join(
        f"{n} {1e-6 * v:.3f} ms" for n, v in sorted(phases.items())))
    return 1e-6 * sum(idle) / len(idle)
