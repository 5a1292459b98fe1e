"""The whole decode step's share of the chip's peak: the operations needed
by the tokens the decode loop delivered in the traced window, each at its
live length (all layers, attention over its keys, the head;
``bench/work.py``), over the device time of ``jit_decode_loop`` times the
peak.  Free and frozen slots are no work.  Moves ``tpot_p90_ms``."""
from bench import work


def read(ctx):
    t, n = ctx.red.module_s("jit_decode_loop")
    toks = ctx.decode_tokens()
    if not n or t <= 0 or not toks:
        return None
    flops = work.decode_totals(ctx.spec, toks, ctx.peak)["flops"]
    return flops / (t * ctx.peak["flops_per_s"]) * 100.0
