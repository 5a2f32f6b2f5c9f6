"""Least time of the chunked SSD scan (``ssd_scan`` of
``archs/granitemoehybrid.py``) for the prompts admitted inside the traced
window, over the device time of the Pallas SSD kernel (the Mosaic custom
call named ``ssd_scan``) inside the prefill programs (the engine's
``jit__lambda``).  The prompts are those of the window's own
``serve.admit.allocate`` spans (stat ``prompt_len``), so programs and
prompts come from the same 10 s."""
import run
import spans

PROGRAMS = ("jit__lambda",)
EVENTS = ("ssd_scan",)


def read(w):
    ops = [e for e in w.kernel_ops(PROGRAMS, EVENTS) if "tpu_custom_call" in e.text]
    secs = w.device_seconds(ops)
    prompts = [e.stats["prompt_len"] for t in w.traces
               for e in spans.spans(t, "serve.admit.allocate")
               if "prompt_len" in e.stats and t.lo <= e.start < t.hi]
    if not secs or not prompts:
        return None
    spans.log(f"ssd kernel: {len(ops)} calls, {secs:.6f} s; {len(prompts)} prompts admitted")
    scan = run.load_arch("granitemoehybrid").ssd_scan(w.arch, prompts)
    least, bound = w.work.least_time(*scan, w.peak)
    spans.log(f"ssd scan least time {1e3 * least:.3f} ms, {bound}-bound")
    return 100.0 * least / secs
