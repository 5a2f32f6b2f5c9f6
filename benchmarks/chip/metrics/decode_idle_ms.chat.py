"""Device idle time per decode step: for each ``step`` value, the time
inside its ``serve.decode.*`` spans in which no program ran on the device,
mean over steps.  Logs that idle time by phase, and where the rest of the
window's idle time fell: admission spans, ``serve.idle`` waits, or no span."""
import spans

EVENTS = (spans.DECODE,)


def _idle(busy, t, events) -> int:
    return sum(spans.idle_ns(busy, max(e.start, t.lo), min(e.end, t.hi)) for e in events)


def read(w):
    steps, phases = [], {}
    for t in w.traces:
        busy = spans.busy(t)
        decode = spans.spans(t, spans.DECODE)
        for events in spans.by(decode, "step").values():
            steps.append(_idle(busy, t, events))
        for e in decode:
            phases[e.name] = phases.get(e.name, 0) + _idle(busy, t, [e])
        total, dec = spans.idle_ns(busy, t.lo, t.hi), _idle(busy, t, decode)
        adm, wait = (_idle(busy, t, spans.spans(t, p)) for p in (spans.ADMIT, spans.IDLE))
        spans.log(f"window idle {1e-6 * total:.3f} ms: decode {1e-6 * dec:.3f}, admission "
                  f"{1e-6 * adm:.3f}, waiting {1e-6 * wait:.3f}, "
                  f"no span {1e-6 * (total - dec - adm - wait):.3f}")
    if not steps:
        return None
    spans.log(f"device idle in {len(steps)} decode steps, by phase: " + "; ".join(
        f"{n} {1e-6 * v:.3f} ms" for n, v in sorted(phases.items())))
    return 1e-6 * sum(steps) / len(steps)
