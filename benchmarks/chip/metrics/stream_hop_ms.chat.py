"""The stream hop of a token delta, from the engine's send to the client's
receipt: mean ``hop_us`` of the ``serve.client.delta`` spans in the window
(a delta without the engine's ``sent_at`` carries none).  Logs p50 and p99."""
import spans
import stats

EVENTS = (spans.DELTA,)


def read(w):
    hops = [e.stats["hop_us"] for t in w.traces for e in spans.spans(t, spans.DELTA)
            if "hop_us" in e.stats]
    if not hops:
        return None
    spans.log(f"stream hop over {len(hops)} deltas: p50 {1e-3 * stats.percentile(hops, 50):.3f} ms, "
              f"p99 {1e-3 * stats.percentile(hops, 99):.3f} ms")
    return 1e-3 * sum(hops) / len(hops)
