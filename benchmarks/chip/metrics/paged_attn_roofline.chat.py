"""Least time for the decode attention over live KV (``work.paged_attention``)
over the paged kernel's device time.  The kernel is the Mosaic custom call
inside the decode program (``_decode_paged_body``)."""
PROGRAMS = ("_decode_paged_body",)
EVENTS = ('custom_call_target="tpu_custom_call"',)


def read(w):
    secs = w.device_seconds(w.kernel_ops(PROGRAMS, EVENTS))
    if not secs or not w.decoded_ctx:
        return None
    least, _ = w.work.least_time(*w.work.paged_attention(w.arch, w.decoded_ctx), w.peak)
    return 100.0 * least / secs
