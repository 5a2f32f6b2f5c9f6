"""Device time per decode program (``_decode_paged_body``)."""
EVENTS = ("_decode_paged_body",)


def read(w):
    return w.mean_device_ms(EVENTS)
