"""Roofline share of the Pallas decode-attention kernel
(``kernels/decode_attention.py``, ``name="decode_attention"``): the least
time the attention of the delivered tokens needs (each token: the larger
of its operations over peak FLOP/s and its live K/V rows' bytes over peak
bandwidth; ``bench/work.py``) over the kernel's device time in the traced
window.  Counted from live lengths, not from what the kernel reads, so a
kernel that reads fewer rows raises it.  Moves ``tpot_p90_ms``."""
from bench import work


def read(ctx):
    t = ctx.red.op_s("decode_attention")
    toks = ctx.decode_tokens()
    if t <= 0 or not toks:
        return None
    least = work.decode_totals(ctx.spec, toks, ctx.peak)["attn_least_s"]
    return least / t * 100.0
