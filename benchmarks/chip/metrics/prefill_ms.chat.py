"""Device time per prefill program (the engine's batched-prefill lambda)."""
EVENTS = ("jit__lambda",)


def read(w):
    return w.mean_device_ms(EVENTS)
