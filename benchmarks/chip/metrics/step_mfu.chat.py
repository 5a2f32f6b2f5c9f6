"""Operations needed by every token prefilled and decoded in the traced
window, over the window times the chip's peak."""
EVENTS = ()


def read(w):
    if not w.traced or not (w.prefilled or w.decoded_ctx):
        return None
    flops = w.work.prefill_flops(w.arch, w.prefilled) + w.work.decode_flops(w.arch, w.decoded_ctx)
    return 100.0 * flops / (w.window_s() * w.peak["bf16_flops_per_s"])
