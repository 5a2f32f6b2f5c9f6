"""Device time per decode program of a model with slot state
(``_decode_state_body``)."""
EVENTS = ("_decode_state_body",)


def read(w):
    return w.mean_device_ms(EVENTS)
