"""Operations the real prompt tokens need (``work.prefill_flops``, padding
not counted) over the prefill programs' device time times the chip's peak."""
EVENTS = ("jit__lambda",)


def read(w):
    secs = w.device_seconds(w.modules(EVENTS))
    if not secs or not w.prefilled:
        return None
    return 100.0 * w.work.prefill_flops(w.arch, w.prefilled) / (secs * w.peak["bf16_flops_per_s"])
