"""Device time of one admission boundary (``serving/engine.py``
``admit_boundary``: cache splice and decode-state scatter in one donated
program), from the trace's ``jit_admit_boundary`` programs in the traced
window.  Moves ``ttft_p90_ms``."""


def read(ctx):
    t, n = ctx.red.module_s("jit_admit_boundary")
    return t / n * 1e3 if n else None
