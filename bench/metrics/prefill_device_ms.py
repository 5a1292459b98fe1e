"""Device time of one B=1 prefill (engine admission, ``serving/engine.py``
``prefill_step``), from the trace's ``jit_prefill_step`` programs in the
traced window.  Moves ``ttft_p90_ms``."""


def read(ctx):
    t, n = ctx.red.module_s("jit_prefill_step")
    return t / n * 1e3 if n else None
