"""Prefill's share of the chip's peak: the operations the traced window's
prompts need (``bench/work.py``: projections of every token, causal
attention, the last position's head) over the device time of the
``jit_prefill_step`` programs times the peak.  Moves ``ttft_p90_ms``."""
from bench import work


def read(ctx):
    t, n = ctx.red.module_s("jit_prefill_step")
    if not n or t <= 0:
        return None
    flops = n * work.prefill_flops(ctx.spec, int(ctx.mix["prompt_len"]))
    return flops / (t * ctx.peak["flops_per_s"]) * 100.0
