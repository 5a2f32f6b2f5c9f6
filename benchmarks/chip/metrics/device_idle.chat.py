"""Share of the traced window in which no operation ran on the device."""
EVENTS = ()


def read(w):
    if not w.traced:
        return None
    return 100.0 * (1.0 - w.busy_s() / w.window_s())
