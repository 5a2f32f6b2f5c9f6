"""Mean wait of an admitted request in the engine's queue: the engine's
``queue_wait_s`` counter (admission-batch start minus the puller's receipt,
summed over admitted requests) over its ``prefills`` counter.  Logs the mean
wait by quarter of the window, from the ``wait_us`` stat of the
``serve.admit.allocate`` spans, so that a growing backlog shows."""
import spans

EVENTS = ()


def read(w):
    c = w.counters
    if not c or not c.get("prefills") or "queue_wait_s" not in c:
        return None
    for t in w.traces:
        quarter = (t.hi - t.lo) / 4
        waits: list[list[float]] = [[], [], [], []]
        for e in spans.spans(t, "serve.admit.allocate"):
            if "wait_us" in e.stats and e.start >= t.lo:
                waits[min(3, int((e.start - t.lo) // quarter))].append(e.stats["wait_us"])
        spans.log("queue wait by quarter of the window: " + "; ".join(
            f"{1e-3 * sum(q) / len(q):.3f} ms over {len(q)}" if q else "none" for q in waits))
    return 1e3 * c["queue_wait_s"] / c["prefills"]
