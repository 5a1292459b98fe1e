"""Device time of one decode micro-step of the fused loop
(``make_decode_loop``, program ``jit_decode_loop``): the program's device
time in the traced window over its executions times K.  Moves
``tpot_p90_ms``."""


def read(ctx):
    t, n = ctx.red.module_s("jit_decode_loop")
    K = int(ctx.mix["serving"]["macro_steps"])
    return t / (n * K) * 1e3 if n else None
