"""Rows of recurrent state a decode step reads and writes: the engine's
``state_row_steps`` counter (summed over steps) over its ``decode_steps``
counter.  Every slot while the step runs every slot."""
EVENTS = ()


def read(w):
    c = w.counters
    if not c or not c.get("decode_steps") or "state_row_steps" not in c:
        return None
    return c["state_row_steps"] / c["decode_steps"]
