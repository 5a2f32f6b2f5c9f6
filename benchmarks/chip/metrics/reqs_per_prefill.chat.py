"""Requests admitted per prefill program run: the engine's ``prefills``
counter over the prefill programs in the trace."""
EVENTS = ("jit__lambda",)


def read(w):
    n = len(w.modules(EVENTS))
    if not n or w.counters is None:
        return None
    return w.counters["prefills"] / n
