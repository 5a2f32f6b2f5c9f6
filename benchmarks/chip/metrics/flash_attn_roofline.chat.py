"""Least time for the attention the real prompts need (``work.flash_attention``,
padding rows and padded positions not counted) over the flash kernel's device
time.  The kernel is the Mosaic custom call inside the prefill program (the
engine's ``jit__lambda``); the trace names the call by its HLO place only."""
PROGRAMS = ("jit__lambda",)
EVENTS = ('custom_call_target="tpu_custom_call"',)


def read(w):
    secs = w.device_seconds(w.kernel_ops(PROGRAMS, EVENTS))
    if not secs or not w.prefilled:
        return None
    least, _ = w.work.least_time(*w.work.flash_attention(w.arch, w.prefilled), w.peak)
    return 100.0 * least / secs
