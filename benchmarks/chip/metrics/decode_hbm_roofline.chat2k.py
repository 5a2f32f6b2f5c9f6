"""Least bytes of the traced window's decode steps over the decode programs'
(``_decode_state_body``) device time at the chip's HBM bandwidth.  Both
sides come from the 10-s trace: the steps are the decode programs that start
in it, and the tokens they decoded and the keys those tokens attended are
the stats ``tokens`` and ``ctx`` of the ``serve.decode.emit`` spans that
start in it.  The least bytes (``decode_hbm_bytes`` of
``archs/granitemoehybrid.py``) are the weights once a step, and for each
decoded token its live K/V in the attention layers and its row of Mamba-2
state read and written once."""
import run
import spans

EVENTS = ("_decode_state_body",)


def read(w):
    progs = [e for t in w.traces for e in t.modules(EVENTS) if t.lo <= e.start < t.hi]
    emits = [e for t in w.traces for e in spans.spans(t, spans.DECODE + "emit")
             if "ctx" in e.stats and t.lo <= e.start < t.hi]
    if not progs or not emits:
        return None
    secs = sum(e.end - e.start for e in progs) / 1e9
    tokens = sum(e.stats["tokens"] for e in emits)
    ctx = sum(e.stats["ctx"] for e in emits)
    spans.log(f"decode: {len(progs)} programs, {secs:.6f} s; {len(emits)} steps emitted "
              f"{tokens} tokens over {ctx} keys")
    arch = run.load_arch("granitemoehybrid")
    least = arch.decode_hbm_bytes(w.arch, len(progs), tokens, ctx)
    return 100.0 * least / (secs * w.peak["hbm_bytes_per_s"])
